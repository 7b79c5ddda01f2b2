"""The ``deepseek`` family: how its configuration files spell their sizes, the
plain reference of what they compute, and what its attention kernels need.

DeepSeek-V3.2 (deepseek-ai/DeepSeek-V3.2, config.json; ``model_type``
``deepseek_v32``): embedding -> ``first_k_dense_replace`` dense blocks -> routed
blocks -> RMSNorm -> untied head. Every block is pre-norm (RMSNorm, no
biases): latent attention under a learned selection, then a gated MLP.

Latent attention of a token with normed input ``x``: ``c_q = RMSNorm(x W_qa)``
(``q_lora_rank``), ``q = c_q W_qb`` -> heads of ``[q_nope | q_pe]``;
``kv_a = x W_kva`` -> ``c_kv = RMSNorm(kv_a[:kv_lora_rank])`` and ONE rotary
key ``k_pe = kv_a[kv_lora_rank:]`` for all heads; ``q_pe`` and ``k_pe``
rotated by the YaRN table of ``rope_scaling`` (its own factor on cos and sin
is 1: ``mscale == mscale_all_dim``); head ``h``'s ``[k_nope | v] = c_kv
W_kvb[h]``. Scores ``(q_nope . k_nope + q_pe . k_pe) * (nope + rope) ** -0.5
* m ** 2`` with ``m = 0.1 ln(factor) + 1``, softmax over the ALLOWED keys,
times ``v``, heads concatenated through ``W_o``.

The allowed keys (lightning indexer): ``q^I = c_q W^I_qb`` -> ``index_n_heads``
heads of ``index_head_dim``; ``k^I = LayerNorm(x W^I_k)``; the first
``qk_rope_head_dim`` values of each rotated (same table, plain positions);
``w = x W^I_w * index_n_heads ** -0.5``. ``I(t, s) = sum_j w_j relu(q^I_j .
k^I_s) * index_head_dim ** -0.5`` for ``s <= t``; token ``t`` attends its
``index_topk`` largest ``I(t, .)`` (all of them while ``t < index_topk``),
ties to the lower position.

Routed blocks: ``s = sigmoid(x W_g)`` in float32 over ALL ``published``
experts; ``c = s + b`` (``b`` the selection bias); a group (``n_group`` groups
of consecutive experts) scores the sum of its two largest ``c``; the
``topk_group`` best groups stay; the ``num_experts_per_tok`` largest ``c``
inside them are chosen; weights ``s_e / sum(chosen s) * routed_scaling_factor``.
Output ``Shared(x) + sum_e w_e Expert_e(x)``, every one ``W2(silu(W1 x) * W3
x)``. The configuration holds ONE member's share of an expert-parallel layer:
the experts ``0 .. n_routed_experts - 1`` of the published count are computed
here, what the others would add is left out (as in the program), and no token
is dropped. The vocabulary is the slice the file gives.

Both rotations pair value ``i`` with ``i + half`` (the release interleaves
pairs in attention and splits halves in the indexer; under random weights the
pairing is a relabelling, and the program uses this one for both).

``FAULTS`` names the ways the reference can be broken on purpose, each what
one fault of a serving engine does to the arithmetic. Nothing sets one in a
measured run.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref
from benchmarks.flops import Shape

PAGE = 16  # tokens a page of the served cache holds (page_dropped's unit)

FAULTS = (
    "selection_off",           # every key at or before a query attended
    "selection_recent",        # the last index_topk keys instead of the best
    "indexer_rope_off",        # index queries and keys not rotated
    "bias_in_weight",          # the selection bias weighs as well as chooses
    "group_limit_off",         # top-k over all experts, no group kept or cut
    "shared_expert_off",       # the shared expert left out
    "scaling_off",             # routed weights not times routed_scaling_factor
    "latent_rope_off_by_one",  # q_pe rotated for the position after its own
    "page_dropped",            # one 16-position page of the context not attended
    "weights_int8",            # every matrix rounded to 8 bits a column
)
QUERY_BLOCK = 64    # query rows a step of the selection scores at once
ATTN_BLOCK = 128    # query rows a step of attention
HEAD_CHUNK = 8      # heads whose q, k and v exist at once


@dataclass(frozen=True)
class DeepseekShape(Shape):
    """``flops.Shape`` (``layers`` the ROUTED layers, ``experts`` the experts
    held here, ``ffn`` their width, ``kv_heads`` 1: one latent a token,
    ``hd`` the qk width) plus what this family needs."""

    dense_layers: int = 0      # leading dense layers, beside ``layers``
    dense_ffn: int = 0
    shared_ffn: int = 0
    routed: int = 0            # experts the router chooses among
    groups: int = 1
    groups_kept: int = 1
    routed_scale: float = 1.0
    q_rank: int = 0
    kv_rank: int = 0
    nope: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    rope: tuple = ()           # rope_scaling as sorted (key, value) pairs

    @property
    def mscale(self) -> float:
        return 0.1 * math.log(float(dict(self.rope)["factor"])) + 1.0

    def layer_matmul_params(self, active: bool = True) -> int:
        """A routed layer outside and inside its experts; ``active``: what
        one token touches here (the shared expert and, on average,
        ``top_k x experts / routed`` of the held ones)."""
        h = self.heads
        attn = (self.d * self.q_rank + self.q_rank * h * self.hd
                + self.d * (self.kv_rank + self.rope_dim)
                + self.kv_rank * h * (self.nope + self.v_dim)
                + h * self.v_dim * self.d
                + self.q_rank * self.index_heads * self.index_dim
                + self.d * self.index_dim + self.d * self.index_heads)
        expert = 3 * self.d * self.ffn
        n = self.top_k * self.experts / self.routed if active else self.experts
        return int(attn + self.d * self.routed + 3 * self.d * self.shared_ffn
                   + n * expert)

    def attention_flops_per_token(self, context: float) -> float:
        """Indexer scores over the context, attention over the selection."""
        depth = self.layers + self.dense_layers
        index = 2 * self.index_heads * self.index_dim * context
        attend = 2 * self.heads * (self.hd + self.v_dim) * min(
            context, self.index_topk)
        return depth * (index + attend)


def shape_of(config: dict) -> DeepseekShape:
    """The published keys of DeepSeek-V3.2's ``config.json``; the experts
    the router sees are the ``published`` count, those computed here
    ``n_routed_experts``."""
    dense = int(config["first_k_dense_replace"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    return DeepseekShape(
        config["family"], int(config["hidden_size"]),
        int(config["num_hidden_layers"]) - dense,
        int(config["num_attention_heads"]), 1, nope + rope,
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["n_routed_experts"]), int(config["num_experts_per_tok"]),
        True, bool(config.get("tie_word_embeddings", False)),
        float(config["rms_norm_eps"]), float(config["rope_theta"]),
        dense_layers=dense, dense_ffn=int(config["intermediate_size"]),
        shared_ffn=int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        routed=int(config["published"]["n_routed_experts"]),
        groups=int(config["n_group"]), groups_kept=int(config["topk_group"]),
        routed_scale=float(config["routed_scaling_factor"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=nope, rope_dim=rope, v_dim=int(config["v_head_dim"]),
        index_heads=int(config["index_n_heads"]),
        index_dim=int(config["index_head_dim"]),
        index_topk=int(config["index_topk"]),
        rope=tuple(sorted(config["rope_scaling"].items())))


def rope_table(shape: DeepseekShape) -> np.ndarray:
    """Inverse frequencies float32 [rope_dim / 2] of ``rope_scaling``: YaRN
    (Peng et al. 2023, eq. 23 with the linear ramp of its reference code)."""
    sec, hd, theta = dict(shape.rope), shape.rope_dim, shape.rope_theta
    extra = theta ** -(np.arange(0, hd, 2, dtype=np.float64) / hd)
    factor = float(sec["factor"])
    length = float(sec["original_max_position_embeddings"])

    def dim(rotations):  # the pair that turns ``rotations`` times in ``length``
        return hd * math.log(length / (2 * math.pi * rotations)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(float(sec["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(sec["beta_slow"]))), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rotate(x, inv, first=0):
    """x [S, ..., rd] at positions first..first+S-1 (``first`` may be
    traced); pairs (i, i + rd/2)."""
    rd = x.shape[-1]
    ang = (first + jnp.arange(x.shape[0])).astype(ref.F32)[:, None] * (
        inv[None, :])
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), rd // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : rd // 2], x[..., rd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _best(score, seen, k: int):
    """bool like ``score`` [Q, S]: each row's ``k`` largest scores among
    ``seen`` (all of them where fewer are seen), ties to the lower position.
    The ``k``-th largest value is found by bisection on the scores' bits (as
    integers of the same order: 32 counts a row), not by a sort: a sort of
    20,000 scores a row took most of the reference's time on the chip (my
    chip run, PR 34)."""
    score = jnp.where(score == 0.0, 0.0, score)  # -0.0 counts as 0.0
    bits = jax.lax.bitcast_convert_type(score.astype(ref.F32), jnp.int32)
    low = jnp.iinfo(jnp.int32).min
    key = jnp.where(seen, bits ^ ((bits >> 31) & 0x7FFFFFFF), low)

    def count(hit):
        return jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)

    def bit(i, lo):  # the largest T with count(key >= T) >= k, bit by bit
        cand = lo + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key >= cand) >= k, cand, lo)

    thr = jax.lax.fori_loop(
        0, 31, bit, jnp.where(count(key >= 0) >= k, 0, low))
    over = key > thr
    tie = seen & (key == thr)
    first = jnp.cumsum(tie, axis=-1, dtype=jnp.int32) <= k - count(over)
    return jnp.where(count(seen) <= k, seen, over | (tie & first))


def _blocks(fn, rows, block: int, *per_row):
    """``fn(first row, *blocks of per_row)`` over blocks of ``block`` rows,
    one after the other (``lax.map``), the last padded; [rows, ...] back."""
    n = -(-rows // block)
    pad = n * block - rows
    cut = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        n, block, *a.shape[1:]) for a in per_row]
    out = jax.lax.map(lambda t: fn(t[0], *t[1:]),
                      (jnp.arange(n) * block, *cut))
    return out.reshape(n * block, *out.shape[2:])[:rows]


@partial(jax.jit, static_argnames=("bits", "rows"))
def _up(w, bits: int = 0, rows=None):
    """A served matrix (or vector) as the reference reads it: float32 and,
    with ``bits``, rounded to that many bits (symmetric, to nearest, one
    scale a column of the WHOLE matrix); ``rows`` (lo, hi) cuts rows out
    after the scale is known, so that a slice rounds as the whole does."""
    w = w.astype(ref.F32)
    if bits and w.ndim == 2:
        top = 2 ** (bits - 1) - 1
        scale = jnp.abs(w).max(axis=0, keepdims=True) / top
        if rows is not None:
            w = w[rows[0]:rows[1]]
        return jnp.clip(jnp.round(w / scale), -top - 1, top) * scale
    return w if rows is None else w[rows[0]:rows[1]]


@partial(jax.jit, static_argnames=("heads", "dim", "rd", "topk", "eps",
                                   "fault"))
def _allowed(h, c_q, ix, inv, *, heads, dim, rd, topk, eps, fault=None):
    """bool [S, S]: the keys each token attends."""
    S = h.shape[0]
    pos = jnp.arange(S)
    if fault == "selection_off":
        return pos[None, :] <= pos[:, None]
    if fault == "selection_recent":
        return (pos[None, :] <= pos[:, None]) & (
            pos[None, :] > pos[:, None] - topk)
    rotated = fault != "indexer_rope_off"
    k = ref.layernorm(h @ ix["wk"], ix["k_norm"], eps)
    if rotated:
        k = jnp.concatenate([_rotate(k[..., :rd], inv), k[..., rd:]], -1)

    def block(first, cb, hb):
        q = (cb @ ix["wq_b"]).reshape(-1, heads, dim)
        if rotated:
            q = jnp.concatenate(
                [_rotate(q[..., :rd], inv, first), q[..., rd:]], -1)
        w = (hb @ ix["w_proj"]) * heads ** -0.5
        s = jnp.einsum("qhd,kd->qhk", q, k)
        score = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], 1) * dim ** -0.5
        qpos = first + jnp.arange(q.shape[0])
        seen = pos[None, :] <= qpos[:, None]
        return _best(score, seen, topk)

    return _blocks(block, S, QUERY_BLOCK, c_q, h)


@partial(jax.jit, static_argnames=("nope", "rd", "vd", "scale", "off"))
def _attend(c_q, c_kv, k_pe, allowed, wq_b, wkv_b, wo, inv, *, nope, rd, vd,
            scale, off=0):
    """The heads of one chunk (``wq_b`` [q_rank, n x (nope+rd)], ``wkv_b``
    [kv_rank, n x (nope+vd)], ``wo`` [n x vd, d], float32) -> their part of
    the block's output [S, d]."""
    S = c_q.shape[0]
    q = (c_q @ wq_b).reshape(S, -1, nope + rd)
    kv = (c_kv @ wkv_b).reshape(S, -1, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], inv, off)], -1)
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_pe[:, None, :], (S, kv.shape[1], rd))], -1)
    v = kv[..., nope:]

    def block(first, qb, ab):
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        p = jax.nn.softmax(jnp.where(ab[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = _blocks(block, S, ATTN_BLOCK, q, allowed)
    return o.reshape(S, -1) @ wo


def _attn(x, ln1, a, shape: DeepseekShape, inv, fault=None, bits: int = 0):
    """One attention block under its selection. ``a``: the layer's attention
    leaves as served; each is upcast where it is used, the three large ones
    (``wq_b``, ``wkv_b``, ``wo``) a chunk of heads at a time."""
    S = x.shape[0]
    H, nope, rd, vd = shape.heads, shape.nope, shape.rope_dim, shape.v_dim
    up = partial(_up, bits=bits)
    h = ref.rmsnorm(x, ln1, shape.eps)
    c_q = ref.rmsnorm(h @ up(a["wq_a"]), jax.tree.map(up, a["q_norm"]),
                      shape.eps)
    kv_a = h @ up(a["wkv_a"])
    c_kv = ref.rmsnorm(kv_a[:, :shape.kv_rank],
                       jax.tree.map(up, a["kv_norm"]), shape.eps)
    k_pe = _rotate(kv_a[:, shape.kv_rank:], inv)
    allowed = _allowed(
        h, c_q, jax.tree.map(up, a["idx"]), inv, heads=shape.index_heads,
        dim=shape.index_dim, rd=rd, topk=shape.index_topk, eps=shape.eps,
        fault=fault if fault in ("selection_off", "selection_recent",
                                 "indexer_rope_off") else None)
    del h, kv_a
    if fault == "page_dropped":  # one page in the middle, for later tokens
        lo = PAGE * (S // (2 * PAGE))
        pos = jnp.arange(S)
        allowed &= ~((pos[None, :] >= lo) & (pos[None, :] < lo + PAGE)
                     & (pos[:, None] >= lo + PAGE))
    scale = (nope + rd) ** -0.5 * shape.mscale ** 2
    for lo in range(0, H, HEAD_CHUNK):
        hi = min(lo + HEAD_CHUNK, H)
        x = x + _attend(
            c_q, c_kv, k_pe, allowed,
            up(a["wq_b"][:, lo * (nope + rd):hi * (nope + rd)]),
            up(a["wkv_b"][:, lo * (nope + vd):hi * (nope + vd)]),
            up(a["wo"], rows=(lo * vd, hi * vd)), inv,
            nope=nope, rd=rd, vd=vd, scale=scale,
            off=int(fault == "latent_rope_off_by_one"))
    return x


@partial(jax.jit, static_argnames=("top_k", "groups", "groups_kept", "scale",
                                   "first", "held", "eps", "fault"))
def _route(x, ln2, router, bias, *, top_k, groups, groups_kept, scale, first,
           held, eps, fault=None):
    """(normed input, routing weights [S, held] of the experts held here,
    zero where not chosen; margin [S]: the least change of a biased score
    that would move an expert held here, or its group, into or out of the
    choice)."""
    h = ref.rmsnorm(x, ln2, eps)
    s = jax.nn.sigmoid(h @ router)
    c = s + bias[None, :]
    S, E = c.shape
    masked = c
    if fault != "group_limit_off":
        best2, _ = jax.lax.top_k(c.reshape(S, groups, E // groups), 2)
        _, kept = jax.lax.top_k(best2.sum(-1), groups_kept)
        in_kept = (kept[:, :, None] == jnp.arange(groups)).any(1)
        masked = jnp.where(jnp.repeat(in_kept, E // groups, axis=1), c, -jnp.inf)
    top, idx = jax.lax.top_k(masked, top_k + 1)
    # how far the experts HELD here are from changing sides: a chosen one
    # from the first left out, another from the last chosen (an expert held
    # elsewhere changes nothing here but the normalisation, by a per cent)
    mine = masked[:, first:first + held]
    last_in, first_out = top[:, top_k - 1, None], top[:, top_k, None]
    margin = jnp.where(mine >= last_in, mine - first_out, last_in - mine).min(1)
    if fault != "group_limit_off" and groups_kept < groups:
        # ... and their groups from being kept or cut
        score = best2.sum(-1)
        edge, _ = jax.lax.top_k(score, groups_kept + 1)
        per = E // groups
        for g in range(first // per, (first + held - 1) // per + 1):
            margin = jnp.minimum(margin, jnp.where(
                in_kept[:, g], score[:, g] - edge[:, groups_kept],
                edge[:, groups_kept - 1] - score[:, g]))
    idx = idx[:, :top_k]
    weigh = c if fault == "bias_in_weight" else s
    w = jnp.take_along_axis(weigh, idx, axis=1)
    w = w / w.sum(-1, keepdims=True) * (1.0 if fault == "scaling_off" else scale)
    full = jnp.zeros_like(c).at[jnp.arange(S)[:, None], idx].set(w)
    return h, full[:, first:first + held], margin


@partial(jax.jit, static_argnames="bits")
def _add_experts(x, h, w, wg, wi, wo, i, bits: int = 0):
    """x + every held expert of routed layer ``i`` on EVERY token, each
    weighted by its routing weight (zero for tokens not routed to it), one
    expert after the other: plain and wasteful on purpose. The banks come
    whole ([L, E, ...], as served); one matrix at a time is cut out and
    upcast inside the loop."""
    def mat(bank, e):
        m = jax.lax.dynamic_slice(
            bank, (i, e, 0, 0), (1, 1, *bank.shape[2:]))[0, 0].astype(ref.F32)
        return _up(m, bits=bits)

    def add(e, x):
        y = (jax.nn.silu(h @ mat(wg, e)) * (h @ mat(wi, e))) @ mat(wo, e)
        return x + y * jax.lax.dynamic_index_in_dim(
            w, e, 1, keepdims=False)[:, None]

    return jax.lax.fori_loop(0, wg.shape[1], add, x)


@jax.jit
def _gated(h, m):
    return (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]


def _gated_served(h, m, bits: int = 0, chunk: int = 2048):
    """:func:`_gated` of an MLP as served (``m`` not yet upcast), ``chunk``
    of its inner width at a time: the three float32 matrices of an
    18432-wide MLP are 1.6 GB at once."""
    out = 0.0
    for lo in range(0, m["wi"].shape[1], chunk):
        hi = min(lo + chunk, m["wi"].shape[1])
        out = out + _gated(h, {
            "wg": _up(m["wg"][:, lo:hi], bits=bits),
            "wi": _up(m["wi"][:, lo:hi], bits=bits),
            "wo": _up(m["wo"], bits=bits, rows=(lo, hi))})
    return out


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords: every fault here is arithmetic of the reference itself,
    but the rounding, which is done as each matrix is upcast (``bits``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault is not None and fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
    return dict(params=params, fault=fault)


def _loader(device, bits: int):
    """How a subtree of the weights reaches the reference: brought to the
    device, upcast to float32 and, under ``weights_int8``, every matrix
    rounded (a stack of matrices one at a time)."""
    if not bits:
        return partial(ref.f32, device=device)
    return lambda tree: jax.tree.map(partial(_up, bits=bits),
                                     ref.f32(tree, device))


def hidden(params, ids, shape, device=None, fault=None, bits: int = 0,
           first_expert: int = 0):
    """[S] token ids -> (hidden before the final norm [S,d] float32, the
    smallest routing margin of each position over the layers [S]).
    ``first_expert``: which share of the layer the held experts are (the
    configuration's member is 0; the shares-add-up test asks for others)."""
    load = _loader(device, bits)
    inv = jax.device_put(rope_table(shape), device)
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        for i in range(shape.dense_layers + shape.layers):
            dense = i < shape.dense_layers
            L = params["lead_layers" if dense else "layers"]
            j = i if dense else i - shape.dense_layers
            at = lambda sub: load(ref.layer(L[sub], j))
            on_device = lambda sub: jax.tree.map(
                lambda w: jax.device_put(w, device), ref.layer(L[sub], j))
            x = _attn(x, at("ln1"), on_device("attn"), shape, inv, fault,
                      bits)
            m = L["mlp"]
            if dense:
                x = x + _gated_served(ref.rmsnorm(x, at("ln2"), shape.eps),
                                      on_device("mlp"), bits)
                continue
            h, w, mg = _route(
                x, at("ln2"), load(m["router"][j]),
                load(m["sel_bias"][j]), top_k=shape.top_k,
                groups=shape.groups, groups_kept=shape.groups_kept,
                scale=shape.routed_scale, first=first_expert,
                held=shape.experts, eps=shape.eps,
                fault=fault if fault in ("bias_in_weight", "group_limit_off",
                                         "scaling_off") else None)
            margin = jnp.minimum(margin, mg)
            if fault != "shared_expert_off":
                x = x + _gated(h, load(ref.layer(m["shared"], j)))
            x = _add_experts(x, h, w, m["wg"], m["wi"], m["wo"], j, bits=bits)
    return x, margin


def logits(params, ids, shape, device=None, last: int | None = None,
           with_margin: bool = False, fault: str | None = None,
           bits: int = 0):
    """Logits float32 over the vocabulary slice for the last ``last``
    positions (all if None); with ``with_margin`` also each of those
    positions' smallest routing margin over the layers: how near an expert
    held here (or its group) was to changing sides. Between the 8th and 9th
    biased score of all 256 it is under 0.01 at every position, the sigmoid
    being saturated at the top; what moves THIS member's output is one of
    its own 16 changing sides, which is rarer.
    ``fault`` and ``bits`` break the reference on purpose: ``faulted`` makes
    both from a name."""
    x, margin = hidden(params, ids, shape, device, fault, bits)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    load = _loader(device, bits)
    with ref.HIGHEST():
        out = ref.rmsnorm(x, load(params["final_norm"]),
                          shape.eps) @ load(params["lm_head"])
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def indexer_cost(shape: DeepseekShape, context_keys: float, index_keys: float,
                 query_rows: float, itemsize: int = 2):
    """The indexer's scoring and selection of ONE layer: (flops, bytes) the
    work needs. ``context_keys``: for every real query token, the cached
    tokens at or before it, summed: each pair costs a dot product of every
    index head, 2 x index_heads x index_head_dim (the ReLU, the weighted sum
    over heads and the selection itself are counted as free). Bytes: the
    index key of the ``index_keys`` tokens in the pages that hold a slot's
    context, once a slot, and the real rows' index queries and head
    weights in; nothing out (a selection that stays on the chip)."""
    flops = 2 * shape.index_heads * shape.index_dim * context_keys
    keys = shape.index_dim * itemsize * index_keys
    q = shape.index_heads * (shape.index_dim * itemsize + 4) * query_rows
    return flops, keys + q


def sparse_attention_cost(shape: DeepseekShape, attended_keys: float,
                          chosen_rows: float, query_rows: float,
                          itemsize: int = 2):
    """Attention of ONE layer over the selection: (flops, bytes) the work
    needs, in the absorbed form (which is the cheaper one over cached rows).
    ``attended_keys``: for every real query token ``min(context,
    index_topk)``, summed: each pair costs, for every head, a dot product
    over the latent and the rotary key (kv_lora_rank + rope) and a weighted
    sum of the latent (kv_lora_rank). Bytes: each of the ``chosen_rows``
    latent rows some query of a slot chose, once a slot (the caller gives a
    count that is certainly reached: the last query's), and the absorbed
    queries in and the attended latents out for ``query_rows`` rows."""
    width = shape.kv_rank + shape.rope_dim
    flops = 2 * shape.heads * (width + shape.kv_rank) * attended_keys
    rows = width * itemsize * chosen_rows
    q_out = shape.heads * (width + shape.kv_rank) * itemsize * query_rows
    return flops, rows + q_out
