"""Brumby presets (manifestai/Brumby-14B-Base, ``model_type`` ``brumby``:
Qwen3-14B's weights with every attention layer replaced by a power-retention
layer) and the retention mixer.

``retention``: with ``xn`` the normed rows, ``q = xn W_q`` (``num_heads`` x
hd), ``k = xn W_k``, ``v = xn W_v`` (``num_kv_heads`` x hd), RMSNorm a head
on q and k, rotary, and a log-gate a KV HEAD a token ``g = log sigmoid(xn
W_g)`` (no bias); query head ``h`` of kv head ``c`` attends ``a_ij = exp(G_i
- G_j) (q_i . k_j / sqrt(hd))^2`` (degree 2, ``G`` the running sum of ``g``)
and ``o_i = sum_j a_ij v_j / (sum_j a_ij + retention_eps)``; ``y =
concat_h(o) W_o``, no output gate and no output norm. A kv head keeps no
keys: its cache is the state of the symmetric square of its keys and the
normaliser beside it, both float32 and both a SLOT's
(ops/pallas/power_retention.py), so NO layer of this model keeps a page: the
arena is the two slot leaves alone. A layer's dense SwiGLU lies in its
mixer's stack.

The serving step is the only forward (``models/transformer._refuse_uncached``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .transformer import (Params, TransformerConfig, TransformerModel,
                          _rope)

RETENTION = "retention"
STACK = {RETENTION: "retention_layers"}
# a layer's dense MLP lies in its mixer's stack, at the mixer's index
MLP_STACK = {"dense": None, "routed": None}
STATE, NORM = "state", "norm"  # the leaves a slot keeps for a layer

_BRUMBY_SIZES = {
    "brumby-tiny": dict(
        hidden_size=64, num_heads=10, num_kv_heads=2, head_dim=16,
        intermediate_size=128, vocab_size=512, max_seq_len=1024,
        published_depth=6,
    ),
    "brumby-14b": dict(
        hidden_size=5120, num_heads=40, num_kv_heads=8, head_dim=128,
        intermediate_size=17408, vocab_size=151936, max_seq_len=32768,
        published_depth=40,
    ),
}


def brumby_config(size: str = "brumby-14b", layer_ids=None,
                  **overrides) -> TransformerConfig:
    """``layer_ids``: the published layers kept, in order (default all)."""
    base = dict(_BRUMBY_SIZES[size])
    depth = base.pop("published_depth")
    ids = tuple(range(depth)) if layer_ids is None else tuple(
        int(i) for i in layer_ids)
    if list(ids) != sorted(set(ids)):
        raise ValueError(f"layer_ids {ids} is not in published order")
    base.update(
        num_layers=len(ids), mixer_types=(RETENTION,) * len(ids),
        mixer_layer_ids=ids, mixer_depth=depth, qk_norm=True,
        pos_embedding="rope", rope_theta=1000000.0, norm="rmsnorm",
        norm_eps=1e-6, activation="swiglu", use_bias=False,
        tie_embeddings=False, name=size,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def brumby(size: str = "brumby-14b", **overrides) -> TransformerModel:
    return TransformerModel(brumby_config(size, **overrides))


# ---------------------------------------------------------------- shapes
def num_params(cfg: TransformerConfig) -> int:
    d, hd, f = cfg.hidden_size, cfg.hd, cfg.ffn
    wide, kv = cfg.num_heads * hd, cfg.kv_heads * hd
    # wq wo, wk wv, wg, the two head norms; the MLP; ln1 ln2
    layer = (2 * d * wide + 2 * d * kv + d * cfg.kv_heads + 2 * hd
             + 3 * d * f + 2 * d)
    return cfg.num_layers * layer + 2 * cfg.vocab_size * d + d


def init(cfg: TransformerConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    std = cfg.initializer_range
    out_std = std / math.sqrt(2 * cfg.num_layers)
    d, hd, f, L = cfg.hidden_size, cfg.hd, cfg.ffn, cfg.num_layers
    wide, kv = cfg.num_heads * hd, cfg.kv_heads * hd
    k = jax.random.split(rng, 10)

    def nrm(key, *shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    def ones(*shape):
        return {"scale": jnp.ones(shape, dtype)}

    return {
        "embed": {"tok": nrm(k[0], cfg.vocab_size, d)},
        "final_norm": ones(d),
        "lm_head": nrm(k[1], d, cfg.vocab_size),
        STACK[RETENTION]: {
            "ln1": ones(L, d), "ln2": ones(L, d),
            "attn": {
                "wq": nrm(k[2], L, d, wide), "wk": nrm(k[3], L, d, kv),
                "wv": nrm(k[4], L, d, kv),
                "wg": nrm(k[5], L, d, cfg.kv_heads),
                "wo": nrm(k[6], L, wide, d, scale=out_std),
                "q_norm": ones(L, hd), "k_norm": ones(L, hd),
            },
            "mlp": {"wi": nrm(k[7], L, d, f), "wg": nrm(k[8], L, d, f),
                    "wo": nrm(k[9], L, f, d, scale=out_std)},
        },
    }


def slot_leaves(cfg: TransformerConfig, max_slots: int, dtype) -> dict:
    """What a slot keeps, neither a page: a kv head's float32 state ``[L,
    max_slots, KV, R, hd, hd]`` (the symmetric square of a key packed into
    ``R = hd / 2 + 1`` rows of ``hd`` lanes, ops/pallas/power_retention.py:
    packed row, value channel, lane) and its normaliser ``[L, max_slots, KV,
    R, 1, hd]``."""
    lead = (cfg.num_layers, max_slots, cfg.kv_heads, cfg.hd // 2 + 1)
    return {STATE: jax.ShapeDtypeStruct((*lead, cfg.hd, cfg.hd), jnp.float32),
            NORM: jax.ShapeDtypeStruct((*lead, 1, cfg.hd), jnp.float32)}


def init_pools(cfg: TransformerConfig, num_pages: int, page_size: int,
               max_slots: int, dtype) -> dict:
    """The arena: the two slot leaves and NO page pool, whatever the page
    table's geometry."""
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in
            slot_leaves(cfg, max_slots, dtype).items()}


# ----------------------------------------------------------------- mixer
def retention_mixer(cfg, p, x, rows, pools, index, cache_len, num_new, note):
    """A retention layer's mixer over the normed rows ``x`` that ``rows``
    computes: (out, in x's layout, and the pools with ``state[index]`` and
    ``norm[index]`` advanced in place). Projections, norms, rotary and the
    gate run on the computed rows; the retention and its state take the
    slot layout."""
    from ..ops.pallas import power_retention as pr
    from .minicpm import _heads, _kernels_registered

    H, hd = cfg.num_heads, cfg.hd
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (rows.B,))
    q, k, v = _heads(cfg, p, x, cfg.kv_heads)  # q and k normed a head
    g = jax.nn.log_sigmoid((x @ p["wg"]).astype(jnp.float32))
    q, k = _rope(q, k, rows.positions, cfg.rope_of(RETENTION))
    q, k, v, g = map(rows.unpack, (q, k, v, g))
    at = dict(scale=hd ** -0.5, eps=cfg.retention_eps)
    why = ["the registered attention is not the kernel one"]
    if _kernels_registered():
        why = pr.kernel_reasons(q, k, jax.default_backend() != "tpu")
    if not why:
        note("retention_kernel", (), RETENTION)
        o, state, norm = pr.power_retention(
            q, k, v, g, pools[STATE], pools[NORM], cache_len, num_new,
            layer=index, **at)
    else:
        note("dense", tuple(why), RETENTION)
        held = lambda name: lax.dynamic_index_in_dim(
            pools[name], index, 0, False)
        o, after, z = pr.dense_power_retention(
            q, k, v, g, held(STATE), held(NORM), cache_len, num_new, **at)
        state = lax.dynamic_update_index_in_dim(pools[STATE], after, index, 0)
        norm = lax.dynamic_update_index_in_dim(pools[NORM], z, index, 0)
    o = rows.pack(o.astype(x.dtype).reshape(rows.B, rows.S, H * hd))
    return o @ p["wo"], {**pools, STATE: state, NORM: norm}
