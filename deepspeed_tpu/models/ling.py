"""Ling-3.0-flash presets (inclusionAI/Ling-3.0-flash, ``model_type``
``bailing_hybrid``) and the KDA mixer its layers run five to one beside
latent attention.

Published layer ``i`` is latent attention where ``(i + 1) % layer_group_size
== 0`` (``layer_group_size`` 6) and Kimi delta attention (KDA) elsewhere; the
first ``first_k_dense_replace`` layers hold a dense SwiGLU, the others one
member's share of 512 sigmoid-routed experts (8 groups, 4 kept, top-8,
weights times 2.5) beside a shared expert. Mixer kind and MLP kind are
independent, so each has a parameter stack of its own (``kda_layers``,
``latent_layers``: ``ln1`` + ``attn``; ``lead_layers``, ``layers``: ``ln2`` +
``mlp``), read in published order by the one layer walk (models/mixers.py).

``kda``: ``q~, k~, v~ = x W``; a depthwise causal convolution over time
(``conv_kernel`` taps a channel) then SiLU; q and k L2-normalised a head, no
rotary; a log-decay a CHANNEL ``g = kda_lower_bound sigmoid(exp(A_log_h) (x
W_alpha + dt_bias))`` and a step size a head ``beta = sigmoid(x W_beta)``;
the gated delta rule over a float32 state ``[hd, hd]`` a head a SLOT
(ops/pallas/kda_attention.py); ``y = W_o (sigmoid(x W_g)_h * RMSNorm_head
(o))``, one gate a head. Beside the state a slot keeps the last ``conv_kernel
- 1`` PRE-convolution rows of q~, k~, v~ (``conv``), carried across chunk
boundaries and zero where a request starts: both leaves are a slot's and no
page.

``latent``: models/decoding._latent_cached_attention without a query latent
(one ``W_q``) and without an indexer, over the latent pool on the one page
table, with the same head-wise gate.

This module also owns the stacks and pools of a model that runs its kda
layers beside ``mla`` layers (models/glm5.py): latent attention through a
query latent, without a gate and, with ``index_topk``, under an indexer
whose keys lie in a pool of their own (``ki``; one a block of
``index_kpool`` tokens, the unfinished block's keys a slot leaf,
``ki_tail``). ``kda_gate_rank`` > 0 is Kimi Linear's published form of the
kda projections: the decay ``x W_a_down W_a_up`` and an output gate a CHANNEL
``sigmoid(x W_g_down W_g_up)``. ``hc_mult`` > 1 adds each half-layer's
hyper-connection leaves (``hc``, models/mixers.py) beside its norm.

The serving step is the only forward (``models/transformer._refuse_uncached``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .transformer import (Params, TransformerConfig, TransformerModel,
                          _latent_attn_params, _rms_last)

KDA, LATENT_KIND, MLA = "kda", "latent", "mla"
STACK = {KDA: "kda_layers", LATENT_KIND: "latent_layers", MLA: "mla_layers"}
MLP_STACK = {"dense": "lead_layers", "routed": "layers"}
STATE, CONV = "state", "conv"  # the leaves a slot keeps for its kda layers
L2_EPS = 1e-6

# inclusionAI/Ling-3.0-flash config.json: the clamp of the SwiGLU's inputs a
# layer (0 = none). ``swiglu_limit`` is one limit a model, so a cut keeps
# layers of one limit
_FLASH_EXPERT_LIMIT = (0,) * 35 + (4,) * 7
_FLASH_SHARED_LIMIT = (0,) * 34 + (5,) * 6 + (7,) * 2

_LING_SIZES = {
    "ling-tiny": dict(
        hidden_size=64, num_heads=4, head_dim=16, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, kv_latent_dim=16, intermediate_size=32,
        moe_shared_width=32, lead_dense_ffn=128, num_experts=16, moe_top_k=4,
        moe_groups=4, moe_groups_kept=2, vocab_size=512, max_seq_len=1024,
        published_depth=13, group=6, first_dense=2,
        expert_limit=(0,) * 13, shared_limit=(0,) * 12 + (3,),
    ),
    "ling-3.0-flash": dict(
        hidden_size=2560, num_heads=32, head_dim=128, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, kv_latent_dim=512,
        intermediate_size=768, moe_shared_width=768, lead_dense_ffn=6144,
        num_experts=512, moe_top_k=8, moe_groups=8, moe_groups_kept=4,
        vocab_size=157184, max_seq_len=262144,
        published_depth=42, group=6, first_dense=2,
        expert_limit=_FLASH_EXPERT_LIMIT, shared_limit=_FLASH_SHARED_LIMIT,
    ),
}


def ling_config(size: str = "ling-3.0-flash", layer_ids=None,
                **overrides) -> TransformerConfig:
    """``layer_ids``: the published layers kept, in order (default all): a
    cut keeps each layer's own published index, which decides its mixer
    (every ``group``-th is latent) and its MLP (the first ``first_dense``
    are dense). The kept layers share one SwiGLU clamp (``swiglu_limit``,
    one a model): a cut across layers the release clamps differently, or
    whose experts and shared expert it clamps differently, is refused."""
    base = dict(_LING_SIZES[size])
    depth, group = base.pop("published_depth"), base.pop("group")
    first_dense = base.pop("first_dense")
    limits = (base.pop("expert_limit"), base.pop("shared_limit"))
    ids = tuple(range(depth)) if layer_ids is None else tuple(
        int(i) for i in layer_ids)
    clamps = sorted({limit[i] for i in ids for limit in limits})
    if len(clamps) > 1:
        raise ValueError(
            f"layer_ids keeps published layers {list(ids)}, whose SwiGLU "
            f"inputs the release clamps at {clamps} "
            "(expert_swiglu_limit_list / share_expert_swiglu_limit_list): "
            f"swiglu_limit is one limit a model, so {size} is served in a "
            "cut whose layers share one")
    if list(ids) != sorted(set(ids)):
        raise ValueError(f"layer_ids {ids} is not in published order")
    lead = sum(i < first_dense for i in ids)
    base.update(
        num_layers=len(ids) - lead, lead_dense_layers=lead,
        mixer_types=tuple(
            LATENT_KIND if (i + 1) % group == 0 else KDA for i in ids),
        mixer_layer_ids=ids, mixer_depth=depth,
        num_kv_heads=base["num_heads"], conv_kernel=4, kda_lower_bound=-5.0,
        pos_embedding="rope", rope_theta=6000000.0, norm="rmsnorm",
        norm_eps=1e-6, activation="swiglu", use_bias=False,
        tie_embeddings=False, moe_gate="sigmoid_groups",
        moe_routed_scale=2.5, swiglu_limit=float(clamps[0]) if clamps else 0.0,
        name=size,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def ling(size: str = "ling-3.0-flash", **overrides) -> TransformerModel:
    return TransformerModel(ling_config(size, **overrides))


# ---------------------------------------------------------------- shapes
def mixer_params(cfg: TransformerConfig, kind: str) -> int:
    d, H = cfg.hidden_size, cfg.num_heads
    if kind == KDA:
        wide, r = H * cfg.hd, cfg.kda_gate_rank
        # wq wk wv wo; wbeta; taps; A_log, dt_bias, o_norm
        fixed = (4 * d * wide + d * H + 3 * wide * cfg.conv_kernel
                 + H + wide + cfg.hd)
        # the decay and the output gate: low-rank both, or walpha and wgate
        return fixed + (2 * r * (d + wide) if r else d * wide + d * H)
    kl, ql = cfg.kv_latent_dim, cfg.q_latent_dim
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    shared = (d * cfg.latent_width + kl
              + kl * H * (cfg.qk_nope_dim + cfg.v_head_dim)
              + H * cfg.v_head_dim * d)
    if kind == LATENT_KIND:  # one W_q, a gate a head
        return shared + d * H * qk + d * H
    indexer = (ql * cfg.index_heads * cfg.index_dim + d * cfg.index_dim
               + 2 * cfg.index_dim + d * cfg.index_heads
               ) if cfg.index_topk else 0
    return shared + d * ql + ql + ql * H * qk + indexer


def hyper_params(cfg: TransformerConfig) -> int:
    """Leaves of one half-layer's hyper-connection: the projection of the
    streams to ``n`` + ``n`` + ``n x n`` mixing values, its three gains and
    its biases (0 without ``hc_mult``)."""
    n = cfg.hc_mult
    return (n * cfg.hidden_size + 1) * (n * n + 2 * n) + 3 if n else 0


def num_params(cfg: TransformerConfig) -> int:
    d, R = cfg.hidden_size, cfg.routed_experts
    mixers = sum(mixer_params(cfg, kind) + d for kind in cfg.mixer_types)
    lead = cfg.lead_dense_layers * (3 * d * cfg.lead_dense_ffn + d)
    routed = cfg.num_layers * (
        d * R + R + 3 * d * cfg.ffn * cfg.num_experts
        + 3 * d * cfg.moe_shared_width + d)
    return (mixers + lead + routed + 2 * cfg.total_layers * hyper_params(cfg)
            + 2 * cfg.vocab_size * d + d)


def init(cfg: TransformerConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Every matrix normal at ``initializer_range`` (residual outputs over
    ``sqrt(2 L)``), norm scales one. The KDA layers' own draws (the release
    gives none in its config): ``A_log = log U(1, 16)`` and ``dt_bias ~
    N(0, 1)``, so the channels of a head decay at every rate between none
    and the bound; taps ``N(0, 1 / conv_kernel)``."""
    std = cfg.initializer_range
    out_std = std / math.sqrt(2 * cfg.total_layers)
    d, H, hd, K = cfg.hidden_size, cfg.num_heads, cfg.hd, cfg.conv_kernel
    wide = H * hd
    keys = jax.random.split(rng, 6)

    def nrm(key, *shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    def ones(*shape):
        return {"scale": jnp.ones(shape, dtype)}

    def swiglu(k, L, f):
        return {"wi": nrm(k[0], L, d, f), "wg": nrm(k[1], L, d, f),
                "wo": nrm(k[2], L, f, d, scale=out_std)}

    params = {
        "embed": {"tok": nrm(keys[0], cfg.vocab_size, d)},
        "final_norm": ones(d),
        "lm_head": nrm(keys[1], d, cfg.vocab_size),
    }
    Lk, Ll = cfg.kind_count(KDA), cfg.kind_count(LATENT_KIND)
    if Lk:
        k = jax.random.split(keys[2], 11)
        r = cfg.kda_gate_rank
        if r:  # Kimi Linear's: the decay and a gate a channel, low-rank
            ka, kg = jax.random.split(k[3]), jax.random.split(k[5])
            decay_gate = {
                "wa_down": nrm(ka[0], Lk, d, r), "wa_up": nrm(ka[1], Lk, r, wide),
                "wg_down": nrm(kg[0], Lk, d, r), "wg_up": nrm(kg[1], Lk, r, wide)}
        else:
            decay_gate = {"walpha": nrm(k[3], Lk, d, wide),
                          "wgate": nrm(k[5], Lk, d, H)}
        params[STACK[KDA]] = {"ln1": ones(Lk, d), "attn": {
            "wq": nrm(k[0], Lk, d, wide), "wk": nrm(k[1], Lk, d, wide),
            "wv": nrm(k[2], Lk, d, wide), **decay_gate,
            "wbeta": nrm(k[4], Lk, d, H),
            "wo": nrm(k[6], Lk, wide, d, scale=out_std),
            "conv": nrm(k[7], Lk, K, 3 * wide, scale=1.0 / K),
            "A_log": jnp.log(jax.random.uniform(
                k[8], (Lk, H), jnp.float32, 1.0, 16.0)).astype(dtype),
            "dt_bias": nrm(k[9], Lk, wide, scale=1.0),
            "o_norm": ones(Lk, hd),
        }}
    if Ll:
        k = jax.random.split(keys[3], 13)
        attn = _latent_attn_params(cfg, nrm, k, Ll, out_std, dtype)
        attn["wgate"] = nrm(k[12], Ll, d, H)
        params[STACK[LATENT_KIND]] = {"ln1": ones(Ll, d), "attn": attn}
    Lm = cfg.kind_count(MLA)
    if Lm:
        k = jax.random.split(jax.random.fold_in(keys[3], 1), 13)
        params[STACK[MLA]] = {"ln1": ones(Lm, d), "attn": _latent_attn_params(
            cfg, nrm, k, Lm, out_std, dtype)}
    if cfg.lead_dense_layers:
        Ld = cfg.lead_dense_layers
        params[MLP_STACK["dense"]] = {"ln2": ones(Ld, d), "mlp": swiglu(
            jax.random.split(keys[4], 3), Ld, cfg.lead_dense_ffn)}
    if cfg.num_layers:
        L, E, R = cfg.num_layers, cfg.num_experts, cfg.routed_experts
        k = jax.random.split(keys[5], 8)
        params[MLP_STACK["routed"]] = {"ln2": ones(L, d), "mlp": {
            "router": nrm(k[0], L, d, R), "sel_bias": nrm(k[1], L, R),
            "wi": nrm(k[2], L, E, d, cfg.ffn),
            "wg": nrm(k[3], L, E, d, cfg.ffn),
            "wo": nrm(k[4], L, E, cfg.ffn, d, scale=out_std),
            "shared": swiglu(k[5:8], L, cfg.moe_shared_width),
        }}
    if cfg.hc_mult:  # a hyper-connection round every half-layer
        n = cfg.hc_mult
        mixes = n * n + 2 * n
        for i, name in enumerate(dict.fromkeys(
                (*(STACK[kind] for kind in cfg.mixer_types),
                 *(s for s in MLP_STACK.values() if s in params)))):
            L = jax.tree.leaves(params[name])[0].shape[0]
            k = jax.random.split(jax.random.fold_in(rng, 100 + i), 2)
            # gains of one and biases N(0, 1/2): the mixes depend on the
            # rows and differ a stream from the first layer on
            params[name]["hc"] = {
                "w": nrm(k[0], L, n * d, mixes), "gain": ones(L, 3),
                "bias": nrm(k[1], L, mixes, scale=0.5)}
    return params


def slot_leaves(cfg: TransformerConfig, max_slots: int, dtype) -> dict:
    """What a slot keeps for its kda layers, neither a page: the float32
    state ``[L_kda, max_slots, heads, hd, hd]`` and the convolution's last
    ``conv_kernel - 1`` pre-convolution rows of q~, k~, v~ side by side, in
    the type they were computed in (a carried row is the row itself)."""
    Lk, H, hd = cfg.kind_count(KDA), cfg.num_heads, cfg.hd
    leaves = {
        STATE: jax.ShapeDtypeStruct((Lk, max_slots, H, hd, hd), jnp.float32),
        CONV: jax.ShapeDtypeStruct(
            (Lk, max_slots, cfg.conv_kernel - 1, 3 * H * hd), dtype),
    }
    if cfg.slot_leaves_of(MLA) and cfg.kind_count(MLA):
        from .decoding import INDEX_TAIL

        # the rotated index keys of the block a slot has not finished
        leaves[INDEX_TAIL] = jax.ShapeDtypeStruct(
            (cfg.kind_count(MLA), max_slots, cfg.index_kpool - 1,
             cfg.index_dim), dtype)
    return leaves


def init_pools(cfg: TransformerConfig, num_pages: int, page_size: int,
               max_slots: int, dtype) -> dict:
    """The arena: the latent (or mla) layers' rows on the one page table,
    an indexer's keys beside them (one a block of ``index_kpool`` tokens, so
    ``page_size / index_kpool`` a page), and the slot leaves by SLOT."""
    from .decoding import INDEX, LATENT, latent_row_width

    paged = cfg.kind_count(LATENT_KIND) + cfg.kind_count(MLA)
    P1 = int(num_pages) + 1
    pools = {LATENT: jnp.zeros(
        (paged, P1, page_size, latent_row_width(cfg)), dtype)}
    if cfg.index_topk:
        if page_size % cfg.index_kpool:
            raise ValueError(
                f"a page of {page_size} tokens is not whole blocks of "
                f"index_kpool {cfg.index_kpool}")
        pools[INDEX] = jnp.zeros(
            (paged, P1, page_size // cfg.index_kpool, cfg.index_dim), dtype)
    pools.update({k: jnp.zeros(v.shape, v.dtype) for k, v in
                  slot_leaves(cfg, max_slots, dtype).items()})
    return pools


# ----------------------------------------------------------------- mixer
def short_conv(cfg, taps, pre, rows, prev, num_new):
    """The depthwise causal convolution over time of the pre-convolution
    rows ``pre`` (the computed rows' layout, [B,S,C] or [1,T,C] packed), a
    slot's rows continued to the left by its carried rows ``prev`` [slots,
    K-1, C] (zeros where its request begins): (the convolved rows float32,
    the rows to carry [slots, K-1, C]: the last K-1 of each slot's carried
    and REAL rows, so a slot with no real row keeps what it held)."""
    K = cfg.conv_kernel
    S = pre.shape[1]
    slot, off = rows.origin()
    taps = taps.astype(jnp.float32)
    out = pre.astype(jnp.float32) * taps[K - 1]
    for back in range(1, K):
        inside = jnp.pad(pre, ((0, 0), (back, 0), (0, 0)))[:, :S]
        carried = prev[slot, jnp.clip(K - 1 + off - back, 0, K - 2)]
        out = out + jnp.where((off >= back)[..., None], inside, carried
                              ).astype(jnp.float32) * taps[K - 1 - back]
    # row j of what is carried on: row nn + j of [prev ; the real rows]
    at = num_new[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    carry = jnp.where(
        (at >= K - 1)[..., None],
        rows.take(pre, jnp.clip(at - (K - 1), 0, rows.S - 1)),
        jnp.take_along_axis(prev, jnp.clip(at, 0, K - 2)[..., None], axis=1))
    return out, carry


def carried_conv(cfg, taps, pre, rows, conv, index, cache_len, num_new):
    """:func:`short_conv` of ``pre`` over the layer's carried rows
    ``conv[index]`` (zeros where a slot's request begins): (the convolved
    rows float32, the stack ``conv`` with ``[index]`` advanced in place)."""
    prev = lax.dynamic_index_in_dim(conv, index, 0, False)
    fresh = (cache_len == 0) & (num_new > 0)
    prev = jnp.where(fresh[:, None, None], jnp.zeros((), prev.dtype), prev)
    y, carry = short_conv(cfg, taps, pre, rows, prev, num_new)
    return y, lax.dynamic_update_index_in_dim(
        conv, carry.astype(conv.dtype), index, 0)


def kda_mixer(cfg, p, x, rows, pools, index, cache_len, num_new, note):
    """A kda layer's mixer over the normed rows ``x`` that ``rows``
    computes: (out, in x's layout, and the pools with ``state[index]`` and
    ``conv[index]`` advanced in place). Projections, the convolution, the
    norms and the gates run on the computed rows; the delta rule and its
    state take the slot layout."""
    from ..ops.pallas import kda_attention as ka
    from .minicpm import _kernels_registered

    Bc, Sc, _ = x.shape
    H, hd = cfg.num_heads, cfg.hd
    wide = H * hd
    cache_len = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (rows.B,))
    pre = jnp.concatenate([x @ p["wq"], x @ p["wk"], x @ p["wv"]], axis=-1)
    y, conv = carried_conv(cfg, p["conv"], pre, rows, pools[CONV], index,
                           cache_len, num_new)
    y = jax.nn.silu(y)
    q, k, v = (y[..., i * wide:(i + 1) * wide].reshape(Bc, Sc, H, hd)
               for i in range(3))
    unit = lambda t: t * lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
    q, k = unit(q), unit(k)
    pace = jnp.exp(p["A_log"].astype(jnp.float32))[:, None]  # [H, 1]
    low_rank = "wa_down" in p  # Kimi Linear's decay and gate (the tree says)
    alpha = (x @ p["wa_down"]) @ p["wa_up"] if low_rank else x @ p["walpha"]
    g = cfg.kda_lower_bound * jax.nn.sigmoid(pace * (
        alpha.astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32)).reshape(Bc, Sc, H, hd))
    beta = jax.nn.sigmoid((x @ p["wbeta"]).astype(jnp.float32))
    q, k, v = (rows.unpack(t.astype(x.dtype)) for t in (q, k, v))
    g, beta = rows.unpack(g), rows.unpack(beta)
    scale = hd ** -0.5
    if _kernels_registered():
        note("kda_kernel", (), KDA)
        whole, first, state = ka.kda_attention(
            q, k, v, g, beta, pools[STATE], cache_len, num_new, layer=index,
            scale=scale)
        o = rows.pack_split(whole, first, num_new > 1).reshape(Bc, Sc, H, hd)
    else:
        note("dense", ("the registered attention is not the kernel one",),
             KDA)
        o, after = ka.dense_kda(
            q, k, v, g, beta,
            lax.dynamic_index_in_dim(pools[STATE], index, 0, False),
            cache_len, num_new, scale=scale)
        state = lax.dynamic_update_index_in_dim(pools[STATE], after, index, 0)
        o = rows.pack(o.astype(x.dtype))
    o = _rms_last(o, p["o_norm"]["scale"], cfg.norm_eps)
    if low_rank:  # a gate a channel
        gate = jax.nn.sigmoid(((x @ p["wg_down"]) @ p["wg_up"]).astype(
            jnp.float32)).reshape(Bc, Sc, H, hd)
    else:
        gate = jax.nn.sigmoid((x @ p["wgate"]).astype(jnp.float32))[..., None]
    out = (gate * o.astype(jnp.float32)).astype(x.dtype).reshape(Bc, Sc, wide)
    return out @ p["wo"], {**pools, STATE: state, CONV: conv}
