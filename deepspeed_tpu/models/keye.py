"""Keye-VL-2.0 presets (Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type``
``KeyeVL2``): the LANGUAGE model. 48 layers of grouped-query attention (32
query heads on 4 KV heads of 128, RMSNorm on every q and k head, rotary at
theta 1e7) under a lightning indexer (``sa_config``: 16 heads of 64 over ONE
index key a token, all 64 values rotated; a query attends its 2,048 best
cached tokens, every head inside its own KV group), each over 128 experts of
768 top-8 of a softmax renormalised over the chosen: no shared expert, no
dense layer, nothing dropped (``moe_capacity_factor`` 0).

With text positions the three M-RoPE axes (sections 16 / 24 / 24) are equal
and M-RoPE is the one-axis rotary; prompts that carry image positions, and
the vision tower, are not served (``serving/request.py`` takes token ids)."""

from .transformer import TransformerConfig, TransformerModel

_KEYE_SIZES = {
    "keye-tiny": dict(
        hidden_size=64, num_layers=3, num_heads=4, num_kv_heads=2,
        head_dim=16, intermediate_size=32, num_experts=8, moe_top_k=2,
        index_heads=2, index_dim=8, index_rope_dim=8, index_topk=24,
        vocab_size=512, max_seq_len=512,
    ),
    "keye-vl-2.0-30b-a3b": dict(
        hidden_size=2048, num_layers=48, num_heads=32, num_kv_heads=4,
        head_dim=128, intermediate_size=768, num_experts=128, moe_top_k=8,
        index_heads=16, index_dim=64, index_rope_dim=64, index_topk=2048,
    ),
}


def keye_config(size: str = "keye-vl-2.0-30b-a3b", **overrides) -> TransformerConfig:
    base = dict(
        vocab_size=151936,
        max_seq_len=262144,
        pos_embedding="rope",
        rope_theta=1e7,
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        qk_norm=True,
        moe_gate="softmax",
        moe_capacity_factor=0.0,  # top-8 of 128, renormalised; no drop
        name=size,
    )
    base.update(_KEYE_SIZES[size])
    base.update(overrides)
    return TransformerConfig(**base)


def keye(size: str = "keye-vl-2.0-30b-a3b", **overrides) -> TransformerModel:
    return TransformerModel(keye_config(size, **overrides))
