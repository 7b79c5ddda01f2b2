"""Mellum2 presets (JetBrains/Mellum2-12B-A2.5B-Instruct): periods of three
window layers (1,024 keys, plain rotary) and one full layer (YaRN), each
over 64 routed experts top-8, with RMSNorm on every q and k head."""

from .transformer import RopeTable, TransformerConfig, TransformerModel

_PATTERN = ("window", "window", "window", "full")

_MELLUM_SIZES = {
    "mellum-tiny": dict(
        hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2,
        head_dim=16, intermediate_size=32, num_experts=8, moe_top_k=2,
        vocab_size=512, max_seq_len=512, attn_window=24,
        rope_tables=(("full", RopeTable(
            theta=500000.0, factor=16.0, original_len=32,
            attention_factor=1.2772588722239782)),),
    ),
    "mellum2-12b-a2.5b": dict(
        hidden_size=2304, num_layers=28, num_heads=32, num_kv_heads=4,
        head_dim=128, intermediate_size=896, num_experts=64, moe_top_k=8,
    ),
}


def mellum_config(size: str = "mellum2-12b-a2.5b", **overrides) -> TransformerConfig:
    base = dict(
        vocab_size=98304,
        max_seq_len=131072,
        pos_embedding="rope",
        rope_theta=500000.0,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        layer_pattern=_PATTERN,
        attn_window=1024,
        rope_tables=(("full", RopeTable(
            theta=500000.0, factor=16.0, original_len=8192,
            beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.2772588722239782)),),
        qk_norm=True,
        # 64 experts top-8: no token dropped needs capacity_factor x 8 >= 64
        moe_capacity_factor=8.0,
        name=size,
    )
    base.update(_MELLUM_SIZES[size])
    base.update(overrides)
    return TransformerConfig(**base)


def mellum(size: str = "mellum2-12b-a2.5b", **overrides) -> TransformerModel:
    return TransformerModel(mellum_config(size, **overrides))
