"""GLM-4.7-Flash presets (zai-org/GLM-4.7-Flash, ``glm4_moe_lite``): latent
attention (a 512-wide latent and one 64-wide rotary key a token for all 20
heads, queries through a 768-wide latent, values 256 wide), one leading
dense layer, then 64 sigmoid-routed experts top-4 in one group
(``noaux_tc``: a selection bias that chooses and never weighs, moved towards
balance after every step) beside a shared expert, and one
multi-token-prediction module that shares embedding and head.

``num_layers`` counts the routed layers of the main stack and
``lead_dense_layers`` the dense one before it (47 published = 1 + 46); the
MTP module's block is counted by ``mtp_layers``."""

from .transformer import TransformerConfig, TransformerModel

_GLM_SIZES = {
    "glm-tiny": dict(
        hidden_size=64, num_layers=2, lead_dense_layers=1, num_heads=4,
        head_dim=24, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=24,
        q_latent_dim=32, kv_latent_dim=16, intermediate_size=32,
        moe_shared_width=32, lead_dense_ffn=128, num_experts=4,
        moe_routed_experts=16, moe_top_k=4, vocab_size=512, max_seq_len=128,
    ),
    "glm-4.7-flash": dict(
        hidden_size=2048, num_layers=46, lead_dense_layers=1, num_heads=20,
        head_dim=256, qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256,
        q_latent_dim=768, kv_latent_dim=512, intermediate_size=1536,
        moe_shared_width=1536, lead_dense_ffn=10240, num_experts=64,
        moe_top_k=4,
    ),
}


def glm_config(size: str = "glm-4.7-flash", **overrides) -> TransformerConfig:
    base = dict(
        vocab_size=154880,
        max_seq_len=202752,
        num_kv_heads=1,
        pos_embedding="rope",
        rope_theta=1000000.0,
        norm="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        moe_gate="sigmoid_groups",
        moe_groups=1,
        moe_groups_kept=1,
        moe_routed_scale=1.8,
        # the DeepSeek-V3 report's bias update speed; the config gives none
        moe_bias_update_rate=0.001,
        mtp_layers=1,
        mtp_loss_weight=0.3,
        name=size,
    )
    base.update(_GLM_SIZES[size])
    base.update(overrides)
    return TransformerConfig(**base)


def glm(size: str = "glm-4.7-flash", **overrides) -> TransformerModel:
    return TransformerModel(glm_config(size, **overrides))
