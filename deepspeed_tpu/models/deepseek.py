"""DeepSeek-V3.2 presets (deepseek-ai/DeepSeek-V3.2): latent attention (a
512-wide latent and one 64-wide rotary key a token for all 128 heads,
queries through a 1536-wide latent), a lightning indexer whose 2,048 best
cached tokens are all that attention sees, and after three dense layers 256
sigmoid-routed experts top-8 in 4 of 8 groups beside a shared expert.

``num_layers`` counts the routed layers of the main stack and
``lead_dense_layers`` the dense ones before it (61 published = 3 + 58)."""

import math

from .transformer import RopeTable, TransformerConfig, TransformerModel


def _yarn(factor: float, original_len: int) -> tuple:
    # mscale == mscale_all_dim: the table's own factor on cos and sin is 1,
    # and the softmax scale carries (0.1 ln factor + 1) ** 2 instead
    return (("full", RopeTable(theta=10000.0, factor=factor,
                               original_len=original_len, beta_fast=32.0,
                               beta_slow=1.0, attention_factor=1.0)),)


def _mscale_sq(factor: float) -> float:
    return (0.1 * math.log(factor) + 1.0) ** 2


_DEEPSEEK_SIZES = {
    "deepseek-tiny": dict(
        hidden_size=64, num_layers=3, lead_dense_layers=1, num_heads=4,
        head_dim=24, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        q_latent_dim=32, kv_latent_dim=16, index_heads=2, index_dim=16,
        index_topk=24, intermediate_size=32, moe_shared_width=32,
        lead_dense_ffn=128, num_experts=4, moe_routed_experts=16,
        moe_top_k=4, moe_groups=4, moe_groups_kept=2, vocab_size=512,
        max_seq_len=512, rope_tables=_yarn(40.0, 32),
    ),
    "deepseek-v3.2": dict(
        hidden_size=7168, num_layers=58, lead_dense_layers=3, num_heads=128,
        head_dim=192, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        q_latent_dim=1536, kv_latent_dim=512, index_heads=64, index_dim=128,
        index_topk=2048, intermediate_size=2048, moe_shared_width=2048,
        lead_dense_ffn=18432, num_experts=256, moe_top_k=8, moe_groups=8,
        moe_groups_kept=4,
    ),
}


def deepseek_config(size: str = "deepseek-v3.2", **overrides) -> TransformerConfig:
    base = dict(
        vocab_size=129280,
        max_seq_len=163840,
        num_kv_heads=1,
        pos_embedding="rope",
        rope_theta=10000.0,
        rope_tables=_yarn(40.0, 4096),
        attn_scale_mult=_mscale_sq(40.0),
        norm="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        moe_gate="sigmoid_groups",
        moe_routed_scale=2.5,
        name=size,
    )
    base.update(_DEEPSEEK_SIZES[size])
    base.update(overrides)
    return TransformerConfig(**base)


def deepseek(size: str = "deepseek-v3.2", **overrides) -> TransformerModel:
    return TransformerModel(deepseek_config(size, **overrides))
