"""GLM-5.3-Flash presets (zai-org/GLM-5.3-Flash, ``model_type``
``glm5_next_text``): hyper-connected residual streams round every half-layer,
Kimi delta attention three layers in four, and latent attention without a
rotary part under an indexer whose keys are pooled by four.

Published layer ``i`` (of 45) is an indexed latent layer (``mla``) where ``i
% 4 == 3`` and a KDA layer elsewhere; the first ``first_k_dense_replace`` = 3
hold a dense SwiGLU (12,288), the others one member's share of 288
sigmoid-routed experts (one group, top-8, weights normed times 2.5) beside a
shared expert; every SwiGLU's inputs are clamped at ``swiglu_limit`` 10.

The residual is ``hc_mult`` = 4 streams (manifold-constrained
hyper-connections, arXiv:2512.24880; models/mixers.py ``hyper_pre`` /
``hyper_post``): the embedding begins all four, every attention and every
MLP reads a learned mix of them and writes back through a doubly-stochastic
4 x 4 mix (20 Sinkhorn rounds a row a half-layer), and the final norm reads
their sum.

``kda``: models/ling.py's mixer at 64 heads of 128 with Kimi Linear's
published projections (``kda_gate_rank`` 128: a low-rank decay and an output
gate a channel). ``mla``: models/decoding._latent_cached_attention through a
1,536-wide query latent, 64 heads of 256 for q, k and v over a 512-wide
latent with NO rotary part (``qk_rope_dim`` 0: a cached row is the latent
alone, scale ``256 ** -0.5``); its indexer (32 heads of 128, the first
``index_rope_dim`` 64 rotated) scores ONE key a block of ``index_kpool`` = 4
tokens, the mean of their rotated keys, chooses ``index_topk`` = 2,048
blocks and always attends the tokens after the last whole block.

Both kinds' stacks, pools and slot leaves are models/ling.py's (the module
that owns ``kda``, and gives ``mla`` a stack beside it). The serving step is
the only forward (``models/transformer._refuse_uncached``); the
multi-token-prediction module and the vision tower are not built.
"""

from .transformer import TransformerConfig, TransformerModel

_GLM5_SIZES = {
    "glm5-tiny": dict(
        hidden_size=64, num_heads=4, head_dim=16, qk_nope_dim=16,
        v_head_dim=16, q_latent_dim=32, kv_latent_dim=32, index_heads=2,
        index_dim=16, index_rope_dim=8, index_topk=6, kda_gate_rank=8,
        intermediate_size=32, moe_shared_width=32, lead_dense_ffn=128,
        num_experts=16, moe_top_k=4, vocab_size=512, max_seq_len=1024,
        published_depth=8, first_dense=2,
    ),
    "glm-5.3-flash": dict(
        hidden_size=4096, num_heads=64, head_dim=128, qk_nope_dim=256,
        v_head_dim=256, q_latent_dim=1536, kv_latent_dim=512, index_heads=32,
        index_dim=128, index_rope_dim=64, index_topk=2048, kda_gate_rank=128,
        intermediate_size=2048, moe_shared_width=2048, lead_dense_ffn=12288,
        num_experts=288, moe_top_k=8, vocab_size=154880,
        max_seq_len=1048576, published_depth=45, first_dense=3,
    ),
}
PERIOD = 4  # every fourth published layer is an indexed latent layer


def glm5_config(size: str = "glm-5.3-flash", layer_ids=None,
                **overrides) -> TransformerConfig:
    """``layer_ids``: the published layers kept, in order (default all): a
    cut keeps each layer's own published index, which decides its mixer
    (``i % 4 == 3``: ``mla``) and its MLP (the first ``first_dense`` are
    dense)."""
    base = dict(_GLM5_SIZES[size])
    depth, first_dense = base.pop("published_depth"), base.pop("first_dense")
    ids = tuple(range(depth)) if layer_ids is None else tuple(
        int(i) for i in layer_ids)
    if list(ids) != sorted(set(ids)):
        raise ValueError(f"layer_ids {ids} is not in published order")
    lead = sum(i < first_dense for i in ids)
    base.update(
        num_layers=len(ids) - lead, lead_dense_layers=lead,
        mixer_types=tuple(
            "mla" if i % PERIOD == PERIOD - 1 else "kda" for i in ids),
        mixer_layer_ids=ids, mixer_depth=depth,
        num_kv_heads=base["num_heads"], conv_kernel=4, kda_lower_bound=-5.0,
        index_kpool=4, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        swiglu_limit=10.0,
        # no layer's attention rotates; the indexer's table (the release
        # gives none: GLM-4.7-Flash's)
        pos_embedding="rope", rope_theta=1000000.0, norm="rmsnorm",
        norm_eps=1e-5, activation="swiglu", use_bias=False,
        tie_embeddings=False, moe_gate="sigmoid_groups", moe_groups=1,
        moe_groups_kept=1, moe_routed_scale=2.5, name=size,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def glm5(size: str = "glm-5.3-flash", **overrides) -> TransformerModel:
    return TransformerModel(glm5_config(size, **overrides))
