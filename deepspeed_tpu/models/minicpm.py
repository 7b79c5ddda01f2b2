"""MiniCPM-SALA presets (openbmb/MiniCPM-SALA, ``model_type``
``minicpm_sala``) and the two mixers its layers alternate between.

``mixer_types`` names each layer as published, in an order that is no period:
``minicpm4`` ("sparse" here: grouped-query attention without rotary
positions, QK-norm, an output gate, every kv group attending a learned
selection of 64-token blocks of the paged cache) and ``lightning-attn``
("lightning": linear attention with a decay a head, rotary positions,
QK-norm, an output norm and an output gate, whose cache is a float32 state
``[heads, hd, hd]`` a SLOT and no page). The two kinds differ in parameter
shapes (a lightning layer's ``wk`` / ``wv`` are full width), so each has a
stack of its own (``sparse_layers``, ``lightning_layers``), read in published
order by the one layer walk (models/mixers.py): runs of one kind are one
``lax.scan`` each over the cache carry.

muP: the embedding times ``scale_emb``, every residual branch times
``scale_depth / sqrt(published depth)``, the hidden state over
``hidden_size / dim_model_base`` before the head.

The serving step is the only forward: a lightning layer's state and a sparse
layer's compressed keys exist in the paged arena alone
(``models/transformer._refuse_uncached``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .transformer import (Params, TransformerConfig, TransformerModel,
                          _rms_last, _rope)

SPARSE, LIGHTNING = "sparse", "lightning"
MIXER_KINDS = (SPARSE, LIGHTNING)
_PUBLISHED_KINDS = {"minicpm4": SPARSE, "lightning-attn": LIGHTNING}
STACK = {SPARSE: "sparse_layers", LIGHTNING: "lightning_layers"}
# a layer's dense MLP lies in its mixer's stack, at the mixer's index
MLP_STACK = {"dense": None, "routed": None}
# the pools' leaves: compressed keys (a row a page a kv head) and the state
COMPRESSED, STATE = "kc", "state"

# openbmb/MiniCPM-SALA config.json, mixer_types: the 8 sparse layers
_SALA_SPARSE_AT = (0, 9, 16, 17, 22, 29, 30, 31)
_SALA_MIXERS = tuple(
    "minicpm4" if i in _SALA_SPARSE_AT else "lightning-attn"
    for i in range(32))

_MINICPM_SIZES = {
    "minicpm-sala-tiny": dict(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, vocab_size=512, max_seq_len=1024,
        dim_model_base=16,
        published_mixers=("minicpm4", "lightning-attn", "lightning-attn",
                          "minicpm4", "minicpm4", "lightning-attn"),
        sparse=dict(topk=5, window_size=64, dense_len=384),
    ),
    "minicpm-sala": dict(
        hidden_size=4096, num_heads=32, num_kv_heads=2, head_dim=128,
        intermediate_size=16384, vocab_size=73448, max_seq_len=524288,
        dim_model_base=256, published_mixers=_SALA_MIXERS, sparse={},
    ),
}


def minicpm_config(size: str = "minicpm-sala", layer_ids=None,
                   **overrides) -> TransformerConfig:
    """``layer_ids``: the published layers kept, in order (default all): a
    cut keeps each layer's own published index (its decay) and the
    published depth (the residual scale)."""
    from ..ops.pallas.block_sparse_attention import BlockSparse

    base = dict(_MINICPM_SIZES[size])
    published = base.pop("published_mixers")
    sparse = dict(base.pop("sparse"), **overrides.pop("sparse", {}))
    ids = tuple(range(len(published))) if layer_ids is None else tuple(
        int(i) for i in layer_ids)
    base.update(
        num_layers=len(ids),
        mixer_types=tuple(_PUBLISHED_KINDS[published[i]] for i in ids),
        mixer_layer_ids=ids, mixer_depth=len(published),
        block_sparse=BlockSparse(**sparse),
        scale_emb=12.0, scale_depth=1.4, qk_norm=True,
        pos_embedding="rope", rope_theta=10000.0, norm="rmsnorm",
        norm_eps=1e-6, activation="swiglu", use_bias=False,
        tie_embeddings=False, name=size,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def minicpm(size: str = "minicpm-sala", **overrides) -> TransformerModel:
    return TransformerModel(minicpm_config(size, **overrides))


# ---------------------------------------------------------------- shapes
def layer_params(cfg: TransformerConfig, kind: str) -> int:
    d, hd, f = cfg.hidden_size, cfg.hd, cfg.ffn
    wide = cfg.num_heads * hd
    kv = wide if kind == LIGHTNING else cfg.kv_heads * hd
    attn = 3 * d * wide + 2 * d * kv + 2 * hd  # wq wo wgate, wk wv, qk norms
    if kind == LIGHTNING:
        attn += wide  # the output norm
    return attn + 3 * d * f + 2 * d


def num_params(cfg: TransformerConfig) -> int:
    layers = sum(layer_params(cfg, kind) for kind in cfg.mixer_types)
    return layers + 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size


def init(cfg: TransformerConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    std = cfg.initializer_range
    out_std = std / math.sqrt(2 * cfg.num_layers)
    d, hd, f = cfg.hidden_size, cfg.hd, cfg.ffn
    wide = cfg.num_heads * hd
    keys = jax.random.split(rng, 4)

    def nrm(key, *shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    def ones(*shape):
        return {"scale": jnp.ones(shape, dtype)}

    def stack(key, kind):
        L = cfg.kind_count(kind)
        kv = wide if kind == LIGHTNING else cfg.kv_heads * hd
        k = jax.random.split(key, 8)
        attn = {
            "wq": nrm(k[0], L, d, wide), "wk": nrm(k[1], L, d, kv),
            "wv": nrm(k[2], L, d, kv),
            "wo": nrm(k[3], L, wide, d, scale=out_std),
            "wgate": nrm(k[4], L, d, wide),
            "q_norm": ones(L, hd), "k_norm": ones(L, hd),
        }
        if kind == LIGHTNING:
            attn["o_norm"] = ones(L, wide)
        return {
            "ln1": ones(L, d), "ln2": ones(L, d), "attn": attn,
            "mlp": {"wi": nrm(k[5], L, d, f), "wg": nrm(k[6], L, d, f),
                    "wo": nrm(k[7], L, f, d, scale=out_std)},
        }

    params = {
        "embed": {"tok": nrm(keys[0], cfg.vocab_size, d)},
        "final_norm": ones(d),
        "lm_head": nrm(keys[1], d, cfg.vocab_size),
    }
    for key, kind in zip(keys[2:], MIXER_KINDS):
        if cfg.kind_count(kind):
            params[STACK[kind]] = stack(key, kind)
    return params


def log_decay(cfg: TransformerConfig, layer_id) -> jax.Array:
    """``log lambda`` [heads] of the lightning layer at published index
    ``layer_id`` (traced or not): ``-s_h (1 - l / (L - 1) + 1e-5)``, ``s_h =
    2 ** (-8 (h + 1) / heads)`` (Lightning Attention as MiniMax-Text-01
    publishes it)."""
    H = cfg.num_heads
    slope = 2.0 ** (-8.0 * (jnp.arange(H, dtype=jnp.float32) + 1.0) / H)
    depth = max(cfg.mixer_depth - 1, 1)
    return -slope * (1.0 - jnp.asarray(layer_id, jnp.float32) / depth + 1e-5)


def slot_leaves(cfg: TransformerConfig, max_slots: int, dtype) -> dict:
    """What a slot keeps that is no page: the lightning layers' float32
    state ``[L_lightning, max_slots, heads, hd, hd]``."""
    return {STATE: jax.ShapeDtypeStruct(
        (cfg.kind_count(LIGHTNING), max_slots, cfg.num_heads, cfg.hd,
         cfg.hd), jnp.float32)}


def init_pools(cfg: TransformerConfig, num_pages: int, page_size: int,
               max_slots: int, dtype) -> dict:
    """The arena of a model with mixers: K / V pages and compressed keys of
    the sparse layers on one page table, and the lightning layers' state by
    SLOT ``[L_lightning, max_slots, heads, hd, hd]`` float32."""
    if page_size != cfg.block_sparse.kernel_stride:
        from ..config import DeepSpeedConfigError

        raise DeepSpeedConfigError(
            f"serving.page_size {page_size} is refused: a sparse layer keeps "
            "one compressed key a page, so a page is the selection's "
            f"kernel_stride, {cfg.block_sparse.kernel_stride} tokens")
    P1, Ls = int(num_pages) + 1, cfg.kind_count(SPARSE)
    kv = (Ls, P1, page_size, cfg.kv_heads, cfg.hd)
    pools = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
             COMPRESSED: jnp.zeros((Ls, P1, cfg.kv_heads, cfg.hd), dtype)}
    if cfg.has_state:
        pools.update({k: jnp.zeros(v.shape, v.dtype) for k, v in
                      slot_leaves(cfg, max_slots, dtype).items()})
    return pools


# ---------------------------------------------------------------- mixers
def _heads(cfg, p, x, kv_heads: int):
    """Normed hidden states (the computed rows: [B,S,d], or [1,T,d] packed)
    -> (q [B,S,H,hd], k, v [B,S,kv_heads,hd]) in the same layout, RMSNorm
    over every head of q and k."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.hd)
    k = (x @ p["wk"]).reshape(B, S, kv_heads, cfg.hd)
    v = (x @ p["wv"]).reshape(B, S, kv_heads, cfg.hd)
    return (_rms_last(q, p["q_norm"]["scale"], cfg.norm_eps),
            _rms_last(k, p["k_norm"]["scale"], cfg.norm_eps), v)


def _gated_out(p, x, o):
    """``W_o (sigmoid(W_g x) * o)``: o [B,S,H*hd] in x's dtype."""
    gate = jax.nn.sigmoid((x @ p["wgate"]).astype(jnp.float32))
    return (gate * o.astype(jnp.float32)).astype(x.dtype) @ p["wo"]


def _kernels_registered() -> bool:
    from ..ops.attention import _resolve

    return _resolve() == "flash"


def lightning_mixer(cfg, p, x, rows, state, index, layer_id, cache_len,
                    num_new, note):
    """A lightning layer's mixer over the normed rows ``x`` that ``rows``
    computes: (out, in x's layout, and the state stack with ``[index]``
    advanced in place). Projections, norms, rotary and the gate run on the
    computed rows; the attention and its state take the slot layout."""
    from ..ops.pallas import lightning_attention as la

    q, k, v = _heads(cfg, p, x, cfg.num_heads)
    q, k = _rope(q, k, rows.positions, cfg.rope_of(LIGHTNING))
    q, k, v = map(rows.unpack, (q, k, v))
    ll, scale = log_decay(cfg, layer_id), cfg.hd ** -0.5
    if _kernels_registered():
        note("lightning_kernel", (), LIGHTNING)
        o, state = la.lightning_attention(
            q, k, v, ll, state, cache_len, num_new, layer=index, scale=scale)
    else:
        note("dense", ("the registered attention is not the kernel one",),
             LIGHTNING)
        o, after = la.dense_lightning(
            q, k, v, ll, lax.dynamic_index_in_dim(state, index, 0, False),
            cache_len, num_new, scale=scale)
        state = lax.dynamic_update_index_in_dim(state, after, index, 0)
    o = _rms_last(rows.pack(o.reshape(rows.B, rows.S, -1)),
                  p["o_norm"]["scale"], cfg.norm_eps)
    return _gated_out(p, x, o), state


def sparse_mixer(cfg, p, x, pools, index, cache_len, num_new, page_table,
                 rows, note, page_rows):
    """A sparse layer's mixer over the normed rows ``x`` that ``rows``
    computes: (out, in x's layout, and the pools with this layer's keys,
    values and compressed keys written in place). The keys and values go to
    the pool as they were computed, each row to its place ``page_rows``
    (``ChunkRows.page_rows``); the attention takes ``q`` by slot."""
    from ..ops.pallas import block_sparse_attention as bsa
    from ..ops.pallas.paged_attention import paged_attention
    from .decoding import _paged_gather, _paged_write

    B, S, geom = rows.B, rows.S, cfg.block_sparse
    positions = rows.slot_positions
    q, k, v = _heads(cfg, p, x, cfg.kv_heads)
    q = rows.unpack(q)
    pools = dict(pools)
    for name, new in (("k", k), ("v", v)):
        pools[name] = _paged_write(pools[name], new.astype(pools[name].dtype),
                                   index, page_rows)
    pools[COMPRESSED] = bsa.write_compressed_keys(
        pools[COMPRESSED], pools["k"], index, cache_len, num_new, page_table,
        geom, S)
    at = dict(layer=index, num_new=num_new)
    why = ["the registered attention is not the kernel one"]
    if _kernels_registered():
        why = bsa.kernel_reasons(q, pools["k"], page_table, geom,
                                 jax.default_backend() != "tpu")
    if not why:
        note("block_sparse_kernel", (), SPARSE)

        def selected():
            return bsa.block_sparse(
                q, pools["k"], pools["v"], pools[COMPRESSED], cache_len,
                page_table, geom=geom, **at)[0]

        def every():
            # a step whose every row is inside dense_len selects nothing:
            # the paged kernel of the dense GQA models attends all its keys
            plain, _ = paged_attention(
                q, pools["k"], pools["v"], cache_len, page_table,
                name="paged_attention_full", **at)
            return selected() if plain is None else plain

        out = lax.cond(
            jnp.any((num_new > 0) & (cache_len + num_new > geom.dense_len)),
            selected, every)
    else:
        note("dense", why, SPARSE)
        mp, ps = page_table.shape[1], pools["k"].shape[2]
        planes = bsa.plane_view(pools[COMPRESSED], index, page_table, geom,
                                geom.blocks(mp * ps))
        kept = bsa.dense_block_selection(q, planes, positions, geom)
        view = lambda name: _paged_gather(
            lax.dynamic_index_in_dim(pools[name], index, 0, False),
            page_table)
        out = bsa.dense_block_attention(q, view("k"), view("v"), kept,
                                        positions, geom)
    out = rows.pack(out.reshape(B, S, -1).astype(x.dtype))
    return _gated_out(p, x, out), pools
