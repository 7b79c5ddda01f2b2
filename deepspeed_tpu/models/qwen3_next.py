"""Qwen3-Next presets (Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type``
``qwen3_next``) and the Gated DeltaNet mixer its layers run three to one
beside gated grouped-query attention.

Published layer ``i`` is gated attention where ``(i + 1) %
full_attention_interval == 0`` (``full_attention_interval`` 4) and Gated
DeltaNet elsewhere; every layer holds one member's share of 512
softmax-routed experts (top-10, renormalised, nothing dropped:
``moe/sharded_moe.route_dropless``) beside a shared expert times ``sigmoid(x
w_sg)``, one value a token. Mixer kind and MLP are independent, so each has a
parameter stack of its own (``gdn_layers``, ``attn_layers``: ``ln1`` +
``attn``; ``layers``: ``ln2`` + ``mlp``), read in published order by the one
layer walk (models/mixers.py).

``gdn`` (``Hk`` key heads, ``Hv`` value heads of ``gdn_head_dim``; value
heads ``r j .. r j + r - 1`` read key head ``j``): ``[q~ | k~ | v~ | z] = x
W_qkvz``, ``[b | a] = x W_ba``; a depthwise causal convolution over time of
``conv_kernel`` taps on ``[q~ | k~ | v~]`` (unequal widths, ONE convolution),
then SiLU; q and k L2-normalised a head; ``beta = sigmoid(b)`` and ``g =
-exp(A_log) softplus(a + dt_bias)``, a SCALAR a value head a token with no
lower bound; the gated delta rule over a float32 state ``[dk, dv]`` a value
head a SLOT (ops/pallas/gated_delta.py); ``y = W_o (RMSNorm_head(o) *
SiLU(z))``, a gate a CHANNEL. Beside the state a slot keeps the last
``conv_kernel - 1`` PRE-convolution rows (``conv``), carried across chunk
boundaries and zero where a request starts: both leaves are a slot's and no
page.

``full``: models/decoding._cached_attention over the K / V pool on the one
page table with what the configuration asks of it: ``attn_out_gate`` (the
gate a channel from the second half of a head's ``W_q``), ``rotary_dim``
(the first 64 of a head's 256 values rotated) and ``qk_norm``.

Every RMSNorm of the release but the one inside ``gdn`` is ``x / rms(x) *
(1 + w)``; the scale leaves here hold ``1 + w`` (a loader adds the one), so
the norm is the plain ``* scale``.

The serving step is the only forward (``models/transformer._refuse_uncached``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .ling import L2_EPS, carried_conv
from .transformer import (Params, TransformerConfig, TransformerModel,
                          _rms_last)

GDN, FULL = "gdn", "full"
STACK = {GDN: "gdn_layers", FULL: "attn_layers"}
MLP_STACK = {"dense": "lead_layers", "routed": "layers"}
STATE, CONV = "state", "conv"  # the leaves a slot keeps for its gdn layers

_SIZES = {
    "qwen3next-tiny": dict(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        rotary_dim=4, gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=16,
        intermediate_size=32, moe_shared_width=32, num_experts=8, moe_top_k=2,
        vocab_size=512, max_seq_len=1024, published_depth=8, interval=4,
    ),
    "qwen3-next-80b-a3b": dict(
        hidden_size=2048, num_heads=16, num_kv_heads=2, head_dim=256,
        rotary_dim=64, gdn_key_heads=16, gdn_value_heads=32, gdn_head_dim=128,
        intermediate_size=512, moe_shared_width=512, num_experts=512,
        moe_top_k=10, vocab_size=151936, max_seq_len=262144,
        published_depth=48, interval=4,
    ),
}


def qwen3_next_config(size: str = "qwen3-next-80b-a3b", layer_ids=None,
                      **overrides) -> TransformerConfig:
    """``layer_ids``: the published layers kept, in order (default all): a
    cut keeps each layer's own published index, which decides its mixer
    (every ``interval``-th is gated attention)."""
    base = dict(_SIZES[size])
    depth, interval = base.pop("published_depth"), base.pop("interval")
    ids = tuple(range(depth)) if layer_ids is None else tuple(
        int(i) for i in layer_ids)
    if list(ids) != sorted(set(ids)):
        raise ValueError(f"layer_ids {ids} is not in published order")
    base.update(
        num_layers=len(ids),
        mixer_types=tuple(
            FULL if (i + 1) % interval == 0 else GDN for i in ids),
        mixer_layer_ids=ids, mixer_depth=depth, conv_kernel=4,
        pos_embedding="rope", rope_theta=1e7, norm="rmsnorm", norm_eps=1e-6,
        activation="swiglu", use_bias=False, tie_embeddings=False,
        qk_norm=True, attn_out_gate=True,
        moe_gate="softmax", moe_capacity_factor=0.0,  # top-k renormalised
        name=size,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def qwen3_next(size: str = "qwen3-next-80b-a3b", **overrides) -> TransformerModel:
    return TransformerModel(qwen3_next_config(size, **overrides))


# ---------------------------------------------------------------- shapes
def gdn_widths(cfg: TransformerConfig):
    """(key width ``Hk x dk``, value width ``Hv x dv``, the convolution's
    channels ``2 x key + value``)."""
    key = cfg.gdn_key_heads * cfg.gdn_head_dim
    val = cfg.gdn_value_heads * cfg.gdn_head_dim
    return key, val, 2 * key + val


def mixer_params(cfg: TransformerConfig, kind: str) -> int:
    d = cfg.hidden_size
    if kind == GDN:
        key, val, conv = gdn_widths(cfg)
        Hv = cfg.gdn_value_heads
        # wqkvz, wba, wo; taps; A_log, dt_bias, o_norm
        return (d * (conv + val) + d * 2 * Hv + val * d
                + conv * cfg.conv_kernel + 2 * Hv + cfg.gdn_head_dim)
    H, KV, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    return 2 * d * H * hd + 2 * d * KV * hd + H * hd * d + 2 * hd


def num_params(cfg: TransformerConfig) -> int:
    d, R = cfg.hidden_size, cfg.routed_experts
    mixers = sum(mixer_params(cfg, kind) + d for kind in cfg.mixer_types)
    routed = cfg.num_layers * (
        d * R + 3 * d * cfg.ffn * cfg.num_experts
        + 3 * d * cfg.moe_shared_width + d + d)  # shared_gate, ln2
    return mixers + routed + 2 * cfg.vocab_size * d + d


def init(cfg: TransformerConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Every matrix normal at ``initializer_range`` (residual outputs over
    ``sqrt(2 L)``), norm scales one (``1 + w`` at ``w`` 0). The gdn layers'
    own draws (the release gives none in its config): ``A_log = log U(0,
    16)`` and ``dt_bias ~ N(0, 1)``, so a head's ``g`` runs from next to
    nothing to -20 and below a row; taps ``N(0, 1 / conv_kernel)``."""
    std = cfg.initializer_range
    out_std = std / math.sqrt(2 * cfg.total_layers)
    d, K = cfg.hidden_size, cfg.conv_kernel
    keys = jax.random.split(rng, 5)

    def nrm(key, *shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    def ones(*shape):
        return {"scale": jnp.ones(shape, dtype)}

    params = {
        "embed": {"tok": nrm(keys[0], cfg.vocab_size, d)},
        "final_norm": ones(d),
        "lm_head": nrm(keys[1], d, cfg.vocab_size),
    }
    Lg, Lf = cfg.kind_count(GDN), cfg.kind_count(FULL)
    if Lg:
        k = jax.random.split(keys[2], 6)
        _, val, conv = gdn_widths(cfg)
        Hv = cfg.gdn_value_heads
        params[STACK[GDN]] = {"ln1": ones(Lg, d), "attn": {
            "wqkvz": nrm(k[0], Lg, d, conv + val),
            "wba": nrm(k[1], Lg, d, 2 * Hv),
            "wo": nrm(k[2], Lg, val, d, scale=out_std),
            "conv": nrm(k[3], Lg, K, conv, scale=1.0 / K),
            "A_log": jnp.log(jax.random.uniform(
                k[4], (Lg, Hv), jnp.float32, 1e-3, 16.0)).astype(dtype),
            "dt_bias": nrm(k[5], Lg, Hv, scale=1.0),
            "o_norm": ones(Lg, cfg.gdn_head_dim),
        }}
    if Lf:
        k = jax.random.split(keys[3], 4)
        H, KV, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
        params[STACK[FULL]] = {"ln1": ones(Lf, d), "attn": {
            "wq": nrm(k[0], Lf, d, 2 * H * hd),  # [q | gate] a head
            "wk": nrm(k[1], Lf, d, KV * hd), "wv": nrm(k[2], Lf, d, KV * hd),
            "wo": nrm(k[3], Lf, H * hd, d, scale=out_std),
            "q_norm": ones(Lf, hd), "k_norm": ones(Lf, hd),
        }}
    L, E, R = cfg.num_layers, cfg.num_experts, cfg.routed_experts
    k = jax.random.split(keys[4], 8)
    f, fs = cfg.ffn, cfg.moe_shared_width
    params[MLP_STACK["routed"]] = {"ln2": ones(L, d), "mlp": {
        "router": nrm(k[0], L, d, R),
        "wi": nrm(k[1], L, E, d, f), "wg": nrm(k[2], L, E, d, f),
        "wo": nrm(k[3], L, E, f, d, scale=out_std),
        "shared": {"wi": nrm(k[4], L, d, fs), "wg": nrm(k[5], L, d, fs),
                   "wo": nrm(k[6], L, fs, d, scale=out_std)},
        "shared_gate": nrm(k[7], L, d, 1),
    }}
    return params


def slot_leaves(cfg: TransformerConfig, max_slots: int, dtype) -> dict:
    """What a slot keeps for its gdn layers, neither a page: the float32
    state ``[L_gdn, max_slots, Hv, dk, dv]`` and the convolution's last
    ``conv_kernel - 1`` pre-convolution rows of ``[q~ | k~ | v~]``, in the
    type they were computed in (a carried row is the row itself)."""
    Lg, hd = cfg.kind_count(GDN), cfg.gdn_head_dim
    return {
        STATE: jax.ShapeDtypeStruct(
            (Lg, max_slots, cfg.gdn_value_heads, hd, hd), jnp.float32),
        CONV: jax.ShapeDtypeStruct(
            (Lg, max_slots, cfg.conv_kernel - 1, gdn_widths(cfg)[2]), dtype),
    }


def init_pools(cfg: TransformerConfig, num_pages: int, page_size: int,
               max_slots: int, dtype) -> dict:
    """The arena: the gated-attention layers' K and V on the one page table
    (``[L_full, pages + 1, page_size, KV, hd]``), and the slot leaves by
    SLOT."""
    row = (cfg.kind_count(FULL), int(num_pages) + 1, page_size, cfg.kv_heads,
           cfg.hd)
    pools = {"k": jnp.zeros(row, dtype), "v": jnp.zeros(row, dtype)}
    pools.update({k: jnp.zeros(v.shape, v.dtype) for k, v in
                  slot_leaves(cfg, max_slots, dtype).items()})
    return pools


# ----------------------------------------------------------------- mixer
def gdn_mixer(cfg, p, x, rows, pools, index, cache_len, num_new, note):
    """A gdn layer's mixer over the normed rows ``x`` that ``rows``
    computes: (out, in x's layout, and the pools with ``state[index]`` and
    ``conv[index]`` advanced in place). Projections, the convolution, the
    norms and the gate run on the computed rows; the delta rule and its
    state take the slot layout."""
    from ..ops.pallas import gated_delta as gd
    from .minicpm import _kernels_registered

    Bc, Sc, _ = x.shape
    Hk, Hv, hd = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_head_dim
    key, val, wide = gdn_widths(cfg)
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (rows.B,))
    qkvz = x @ p["wqkvz"]
    pre, z = qkvz[..., :wide], qkvz[..., wide:]
    y, conv = carried_conv(cfg, p["conv"], pre, rows, pools[CONV], index,
                           cache_len, num_new)
    y = jax.nn.silu(y)
    q = y[..., :key].reshape(Bc, Sc, Hk, hd)
    k = y[..., key:2 * key].reshape(Bc, Sc, Hk, hd)
    v = y[..., 2 * key:].reshape(Bc, Sc, Hv, hd)
    unit = lambda t: t * lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
    q, k = unit(q), unit(k)
    ba = (x @ p["wba"]).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    # one log-decay a value head a row, at most 0 and as low as it comes
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + p["dt_bias"].astype(jnp.float32))
    q, k, v = (rows.unpack(t.astype(x.dtype)) for t in (q, k, v))
    g, beta = rows.unpack(g), rows.unpack(beta)
    scale = hd ** -0.5
    if _kernels_registered():
        note("gdn_kernel", (), GDN)
        whole, first, state = gd.gated_delta_attention(
            q, k, v, g, beta, pools[STATE], cache_len, num_new, layer=index,
            scale=scale)
        o = rows.pack_split(whole, first, num_new > 1).reshape(Bc, Sc, Hv, hd)
    else:
        note("dense", ("the registered attention is not the kernel one",),
             GDN)
        o, after = gd.dense_gated_delta(
            q, k, v, g, beta,
            lax.dynamic_index_in_dim(pools[STATE], index, 0, False),
            cache_len, num_new, scale=scale)
        state = lax.dynamic_update_index_in_dim(pools[STATE], after, index, 0)
        o = rows.pack(o.astype(x.dtype))
    o = _rms_last(o, p["o_norm"]["scale"], cfg.norm_eps)
    gate = jax.nn.silu(z.astype(jnp.float32)).reshape(Bc, Sc, Hv, hd)
    out = (gate * o.astype(jnp.float32)).astype(x.dtype).reshape(Bc, Sc, val)
    return out @ p["wo"], {**pools, STATE: state, CONV: conv}
