"""LFM2 presets (LiquidAI/LFM2-8B-A1B, ``model_type`` ``lfm2_moe``) and the
gated short convolution its layers run three to one beside grouped-query
attention of 64-wide heads.

Published layer ``i`` is ``layer_types[i]``: attention at 2, 6, 10, 14, 18
and 21 of 24, a gated short convolution elsewhere; its MLP is dense (width
``lead_dense_ffn``) where ``i < num_dense_layers`` and an expert layer
elsewhere: 32 experts, the 4 best of ``sigmoid + bias`` weighed by their
sigmoids over ``sum + 1e-6`` (``moe/sharded_moe.sigmoid_group_gate`` at one
group, ``cfg.moe_norm_eps``), no shared expert. Mixer kind and MLP are
independent, so each has a parameter stack of its own (``conv_layers``,
``attn_layers``: ``ln1`` + ``attn``; ``lead_layers`` / ``layers``: ``ln2`` +
``mlp``), read in published order by the one layer walk (models/mixers.py).

``conv`` (``d`` wide, ``K`` = ``conv_kernel`` taps): ``[B | C | x~] = x
W_in``, three equal runs of columns; ``u = B * x~``; ``c_t = sum_j w_j
u_{t - (K - 1) + j}``, depthwise and causal, NO activation; ``y = W_out (C *
c)``. A slot keeps the last ``K - 1`` rows of the PRODUCT ``u`` (``conv``),
carried across chunk boundaries and zero where a request starts: the mixer's
only leaf, a slot's and no page. No state matrix, no decay.

``full``: models/decoding._cached_attention over the K / V pool on the one
page table with ``qk_norm`` (a plain scale a head, before rotary) and a
whole-head rotary. Heads are 64 wide: the pool holds two KV heads a 128-lane
row (``ops/pallas/paged_attention.paired_pool_row``), the bytes of a
row-major ``[page_size, KV, 64]`` page in the same order, which is what the
chip can hold unpadded and the paged kernel reads as it lies.

Every norm is the plain RMSNorm ``x / rms(x) * w``; the head is the
embedding transposed (``tie_embeddings``).

The serving step is the only forward (``models/transformer._refuse_uncached``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .ling import carried_conv
from .transformer import Params, TransformerConfig, TransformerModel

CONV_KIND, FULL = "conv", "full"
STACK = {CONV_KIND: "conv_layers", FULL: "attn_layers"}
MLP_STACK = {"dense": "lead_layers", "routed": "layers"}
CONV = "conv"  # the leaf a slot keeps for its conv layers

_SIZES = {
    "lfm2-tiny": dict(
        hidden_size=128, num_heads=4, num_kv_heads=2, head_dim=64,
        intermediate_size=32, lead_dense_ffn=96, num_experts=8, moe_top_k=2,
        vocab_size=512, max_seq_len=1024, published_depth=8,
        full_at=(2, 6), first_dense=2,
    ),
    "lfm2-8b-a1b": dict(
        hidden_size=2048, num_heads=32, num_kv_heads=8, head_dim=64,
        intermediate_size=1792, lead_dense_ffn=7168, num_experts=32,
        moe_top_k=4, vocab_size=65536, max_seq_len=128000,
        published_depth=24, full_at=(2, 6, 10, 14, 18, 21), first_dense=2,
    ),
}


def lfm2_config(size: str = "lfm2-8b-a1b", layer_ids=None,
                **overrides) -> TransformerConfig:
    """``layer_ids``: the published layers kept, in order (default all): a
    cut keeps each layer's own published index, which decides its mixer
    (``full_at`` are attention) and its MLP (the first ``first_dense`` are
    dense)."""
    base = dict(_SIZES[size])
    depth, full_at = base.pop("published_depth"), base.pop("full_at")
    first_dense = base.pop("first_dense")
    ids = tuple(range(depth)) if layer_ids is None else tuple(
        int(i) for i in layer_ids)
    if list(ids) != sorted(set(ids)):
        raise ValueError(f"layer_ids {ids} is not in published order")
    lead = sum(i < first_dense for i in ids)
    base.update(
        num_layers=len(ids) - lead, lead_dense_layers=lead,
        mixer_types=tuple(FULL if i in full_at else CONV_KIND for i in ids),
        mixer_layer_ids=ids, mixer_depth=depth, conv_kernel=3,
        pos_embedding="rope", rope_theta=1e6, norm="rmsnorm", norm_eps=1e-5,
        activation="swiglu", use_bias=False, tie_embeddings=True,
        qk_norm=True,
        # the 4 best of sigmoid + bias among ALL the experts (one group),
        # weighed by their sigmoids over sum + 1e-6, times 1
        moe_gate="sigmoid_groups", moe_groups=1, moe_groups_kept=1,
        moe_routed_scale=1.0, moe_norm_eps=1e-6,
        name=size,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def lfm2(size: str = "lfm2-8b-a1b", **overrides) -> TransformerModel:
    return TransformerModel(lfm2_config(size, **overrides))


# ---------------------------------------------------------------- shapes
def mixer_params(cfg: TransformerConfig, kind: str) -> int:
    d = cfg.hidden_size
    if kind == CONV_KIND:  # W_in, W_out, taps
        return 3 * d * d + d * d + cfg.conv_kernel * d
    H, KV, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 2 * hd


def num_params(cfg: TransformerConfig) -> int:
    d, R = cfg.hidden_size, cfg.routed_experts
    mixers = sum(mixer_params(cfg, kind) + d for kind in cfg.mixer_types)
    lead = cfg.lead_dense_layers * (3 * d * cfg.lead_dense_ffn + d)
    routed = cfg.num_layers * (
        d * R + R + 3 * d * cfg.ffn * cfg.num_experts + d)
    return mixers + lead + routed + cfg.vocab_size * d + d


def init(cfg: TransformerConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Every matrix normal at ``initializer_range`` (residual outputs over
    ``sqrt(2 L)``), norm scales one, the selection bias N(0,
    ``initializer_range``) like every leaf; taps ``N(0, 1 / conv_kernel)``
    (the release gives no draw in its config)."""
    std = cfg.initializer_range
    out_std = std / math.sqrt(2 * cfg.total_layers)
    d, K = cfg.hidden_size, cfg.conv_kernel
    keys = jax.random.split(rng, 5)

    def nrm(key, *shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    def ones(*shape):
        return {"scale": jnp.ones(shape, dtype)}

    params = {"embed": {"tok": nrm(keys[0], cfg.vocab_size, d)},
              "final_norm": ones(d)}
    Lc, Lf = cfg.kind_count(CONV_KIND), cfg.kind_count(FULL)
    if Lc:
        k = jax.random.split(keys[1], 3)
        params[STACK[CONV_KIND]] = {"ln1": ones(Lc, d), "attn": {
            "win": nrm(k[0], Lc, d, 3 * d),  # [B | C | x~]
            "wout": nrm(k[1], Lc, d, d, scale=out_std),
            "conv": nrm(k[2], Lc, K, d, scale=1.0 / K),
        }}
    if Lf:
        k = jax.random.split(keys[2], 4)
        H, KV, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
        params[STACK[FULL]] = {"ln1": ones(Lf, d), "attn": {
            "wq": nrm(k[0], Lf, d, H * hd),
            "wk": nrm(k[1], Lf, d, KV * hd), "wv": nrm(k[2], Lf, d, KV * hd),
            "wo": nrm(k[3], Lf, H * hd, d, scale=out_std),
            "q_norm": ones(Lf, hd), "k_norm": ones(Lf, hd),
        }}
    if cfg.lead_dense_layers:
        Ld, f = cfg.lead_dense_layers, cfg.lead_dense_ffn
        k = jax.random.split(keys[3], 3)
        params[MLP_STACK["dense"]] = {"ln2": ones(Ld, d), "mlp": {
            "wi": nrm(k[0], Ld, d, f), "wg": nrm(k[1], Ld, d, f),
            "wo": nrm(k[2], Ld, f, d, scale=out_std)}}
    if cfg.num_layers:
        L, E, R, f = (cfg.num_layers, cfg.num_experts, cfg.routed_experts,
                      cfg.ffn)
        k = jax.random.split(keys[4], 5)
        params[MLP_STACK["routed"]] = {"ln2": ones(L, d), "mlp": {
            "router": nrm(k[0], L, d, R), "sel_bias": nrm(k[1], L, R),
            "wi": nrm(k[2], L, E, d, f), "wg": nrm(k[3], L, E, d, f),
            "wo": nrm(k[4], L, E, f, d, scale=out_std),
        }}
    return params


def slot_leaves(cfg: TransformerConfig, max_slots: int, dtype) -> dict:
    """What a slot keeps for its conv layers, no page: the last
    ``conv_kernel - 1`` rows of the product ``B * x~``, in the type they
    were computed in (a carried row is the row itself)."""
    return {CONV: jax.ShapeDtypeStruct(
        (cfg.kind_count(CONV_KIND), max_slots, cfg.conv_kernel - 1,
         cfg.hidden_size), dtype)}


def init_pools(cfg: TransformerConfig, num_pages: int, page_size: int,
               max_slots: int, dtype) -> dict:
    """The arena: the attention layers' K and V on the one page table
    (``[L_full, pages + 1, page_size, *paired_pool_row]``: two 64-wide KV
    heads a 128-lane row), and the slot leaf by SLOT."""
    from ..ops.pallas.paged_attention import paired_pool_row

    row = (cfg.kind_count(FULL), int(num_pages) + 1, page_size,
           *paired_pool_row(cfg.kv_heads, cfg.hd))
    pools = {"k": jnp.zeros(row, dtype), "v": jnp.zeros(row, dtype)}
    pools.update({k: jnp.zeros(v.shape, v.dtype) for k, v in
                  slot_leaves(cfg, max_slots, dtype).items()})
    return pools


# ----------------------------------------------------------------- mixer
def conv_mixer(cfg, p, x, rows, pools, index, cache_len, num_new, note):
    """A conv layer's mixer over the normed rows ``x`` that ``rows``
    computes: (out, in x's layout, and the pools with ``conv[index]``
    advanced in place). Everything runs on the computed rows; the carried
    rows are a slot's."""
    d = cfg.hidden_size
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (rows.B,))
    bcx = x @ p["win"]
    gate_in, gate_out, xt = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    c, conv = carried_conv(cfg, p["conv"], gate_in * xt, rows, pools[CONV],
                           index, cache_len, num_new)
    note("short_conv", (), CONV_KIND)  # plain lines on either backend
    out = (gate_out.astype(jnp.float32) * c).astype(x.dtype)
    return out @ p["wout"], {**pools, CONV: conv}
