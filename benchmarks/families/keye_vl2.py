"""The ``keye_vl2`` family: how its configuration files spell their sizes, the
plain reference of what they compute, and what its attention kernels need.

Keye-VL-2.0-30B-A3B (Kwai-Keye/Keye-VL-2.0-30B-A3B, config.json;
``model_type`` ``KeyeVL2``), the LANGUAGE model: embedding -> N x [RMSNorm ->
grouped-query attention under a learned selection -> residual -> RMSNorm ->
routed SwiGLU experts -> residual] -> RMSNorm -> untied head. No bias, no
shared expert, no dense layer (``mlp_only_layers`` is empty). The vision tower
is left out: every position is a text position.

Attention of a token with normed input ``x``: ``q = RMSNorm_head(x W_q)``
(``num_attention_heads`` heads of ``head_dim``), ``k = RMSNorm_head(x W_k)``
(``num_key_value_heads``), ``v = x W_v``; q and k rotated by M-RoPE: the
``head_dim / 2`` frequencies of theta ``rope_theta`` are cut into
``mrope_section`` runs (16 / 24 / 24: temporal, height, width, the chunked
layout of Qwen2-VL) and run ``a`` turns by the position's axis ``a``; a text
token's three axes are equal, which this reference spells out and the program
does not (its plain rotary is the same table: a test says so). Scores ``q k /
sqrt(head_dim)``, softmax over the ALLOWED keys of the head's KV group,
times ``v``, heads concatenated through ``W_o``.

The allowed keys (lightning indexer, ``sa_config``): ``q^I = x W^I_q`` ->
``indexer_num_heads`` heads of ``indexer_head_dim`` (from the normed hidden
state: there is no query latent); ``k^I = LayerNorm(x W^I_k)``, ONE key a
token; every value of both rotated (half-split pairs, theta ``rope_theta``
over the indexer's own width, the text position); ``w = x W^I_w *
heads ** -0.5``. ``I(t, s) = sum_j w_j relu(q^I_j . k^I_s) * dim ** -0.5`` for
``s <= t``; token ``t`` attends its ``topk`` largest ``I(t, .)`` (all of them
while ``t < topk``), ties to the lower position, the same set in every head.
``q_chunk_size`` / ``kv_chunk_size`` tile the release's scoring and change
nothing.

Routed blocks: ``p = softmax(x W_r)`` in float32 over ALL ``num_local_experts``
outputs; the ``num_experts_per_tok`` largest are chosen and renormalised to
sum to one (``norm_topk_prob``). Output ``sum_e p_e W_o,e (silu(x W_g,e) * x
W_i,e)`` over the chosen experts HELD here: the configuration holds ONE
member's share of an expert-parallel layer, experts ``first .. first +
num_experts - 1`` of the router's outputs (the configuration's member is
0); what the others would add is left out (as in the program), and no token
is dropped. The vocabulary is the slice the file gives.

``FAULTS`` names the ways the reference can be broken on purpose, each what
one fault of a serving engine does to the arithmetic. Nothing sets one in a
measured run.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref
# the selection by bisection, the blocked map, the upcast that rounds under
# ``weights_int8`` and the held experts one after the other are DeepSeek's
from benchmarks.families.deepseek import _add_experts, _best, _blocks, _up
from benchmarks.flops import Shape

PAGE = 16  # tokens a page of the served cache holds (page_dropped's unit)

FAULTS = (
    "selection_off",        # every key at or before a query attended
    "selection_recent",     # the last topk keys instead of the best
    "indexer_rope_off",     # index queries and keys not rotated
    "index_norm_off",       # the index key not LayerNormed
    "qk_norm_off",          # q and k heads not RMSNormed
    "renorm_off",           # routing weights not renormalised over the 8
    "held_renorm",          # ... renormalised over the HELD chosen alone
    "kv_group_off_by_one",  # every query head reads its neighbour group's K/V
    "page_dropped",         # one 16-position page of the context not attended
    "weights_int8",         # every matrix rounded to 8 bits a column
)
QUERY_BLOCK = 64   # query rows a step of the selection scores at once
ATTN_BLOCK = 128   # query rows a step of attention


@dataclass(frozen=True)
class KeyeShape(Shape):
    """``flops.Shape`` (``experts`` the experts held here, ``ffn`` their
    width) plus what this family needs."""

    routed: int = 0            # experts the router chooses among
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    sections: tuple = ()       # mrope_section
    dense_layers: int = 0      # (none: the readers of layer counts ask)

    def layer_matmul_params(self, active: bool = True) -> int:
        """A layer outside and inside its experts; ``active``: what one
        token touches here (on average ``top_k x experts / routed`` of the
        held ones)."""
        attn = (2 * self.d * self.heads * self.hd
                + 2 * self.d * self.kv_heads * self.hd
                + self.d * self.index_heads * self.index_dim
                + self.d * self.index_dim + self.d * self.index_heads)
        n = self.top_k * self.experts / self.routed if active else self.experts
        return int(attn + self.d * self.routed + n * 3 * self.d * self.ffn)

    def attention_flops_per_token(self, context: float) -> float:
        """Indexer scores over the context, attention over the selection."""
        index = 2 * self.index_heads * self.index_dim * context
        attend = 2 * 2 * self.heads * self.hd * min(context, self.index_topk)
        return self.layers * (index + attend)


def shape_of(config: dict) -> KeyeShape:
    """The published keys of Keye-VL-2.0's ``config.json``; the experts the
    router sees are ``num_local_experts``, those computed here
    ``num_experts``."""
    sa = config["sa_config"]
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("the indexer keeps ONE key a token "
                         "(sa_config.indexer_num_kv_heads 1)")
    if config.get("mlp_only_layers") or not config.get("norm_topk_prob", True):
        raise ValueError("every layer is routed and the chosen weights are "
                         "renormalised (mlp_only_layers [], norm_topk_prob)")
    hd = int(config["head_dim"])
    sections = tuple(int(n) for n in config["rope_scaling"]["mrope_section"])
    if sum(sections) != hd // 2:
        raise ValueError(f"mrope_section {sections} does not fill the "
                         f"{hd // 2} rotary pairs of a head")
    return KeyeShape(
        config["family"], int(config["hidden_size"]),
        int(config["num_hidden_layers"]), int(config["num_attention_heads"]),
        int(config["num_key_value_heads"]), hd,
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["num_experts"]), int(config["num_experts_per_tok"]), True,
        bool(config.get("tie_word_embeddings", False)),
        float(config["rms_norm_eps"]), float(config["rope_theta"]),
        routed=int(config["num_local_experts"]),
        index_heads=int(sa["indexer_num_heads"]),
        index_dim=int(sa["indexer_head_dim"]), index_topk=int(sa["topk"]),
        sections=sections)


def inv_freq(theta: float, width: int) -> np.ndarray:
    """Inverse frequencies float32 [width / 2] of the plain rotary table."""
    return (theta ** -(np.arange(0, width, 2, dtype=np.float64) / width)
            ).astype(np.float32)


def mrope_angles(positions, inv, sections):
    """Angles [S, hd / 2] of M-RoPE: ``positions`` [3, S] (temporal, height,
    width), frequency ``i`` turned by the axis whose section holds it."""
    axis = np.repeat(np.arange(len(sections)), sections)  # [hd / 2]
    ang = positions.astype(ref.F32)[:, :, None] * inv[None, None, :]
    return jnp.take_along_axis(ang, jnp.asarray(axis)[None, None, :], 0)[0]


def text_positions(S: int, first=0):
    """The three equal axes of ``S`` text tokens from position ``first``."""
    return jnp.broadcast_to(first + jnp.arange(S), (3, S))


def rotate(x, ang):
    """x [S, ..., w] by angles [S, w / 2]; pairs (i, i + w / 2)."""
    half = x.shape[-1] // 2
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "dim", "topk", "eps", "fault"))
def _allowed(h, ix, inv, *, heads, dim, topk, eps, fault=None):
    """bool [S, S]: the keys each token attends (every head's)."""
    S = h.shape[0]
    pos = jnp.arange(S)
    if fault == "selection_off":
        return pos[None, :] <= pos[:, None]
    if fault == "selection_recent":
        return (pos[None, :] <= pos[:, None]) & (
            pos[None, :] > pos[:, None] - topk)
    rotated = fault != "indexer_rope_off"
    k = h @ ix["wk"]
    if fault != "index_norm_off":
        k = ref.layernorm(k, ix["k_norm"], eps)
    if rotated:
        k = rotate(k, pos.astype(ref.F32)[:, None] * inv[None, :])

    def block(first, hb):
        q = (hb @ ix["wq"]).reshape(-1, heads, dim)
        qpos = first + jnp.arange(q.shape[0])
        if rotated:
            q = rotate(q, qpos.astype(ref.F32)[:, None] * inv[None, :])
        w = (hb @ ix["w_proj"]) * heads ** -0.5
        s = jnp.einsum("qhd,kd->qhk", q, k)
        score = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], 1) * dim ** -0.5
        return _best(score, pos[None, :] <= qpos[:, None], topk)

    return _blocks(block, S, QUERY_BLOCK, h)


@jax.jit
def _attend(q, k, v, allowed):
    """q [S,KV,G,hd] against k, v [S,KV,hd] under ``allowed`` [S,S], in
    query blocks: the score matrix of a long context never exists whole."""
    scale = q.shape[-1] ** -0.5

    def block(first, qb, ab):
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) * scale
        p = jax.nn.softmax(jnp.where(ab[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    return _blocks(block, q.shape[0], ATTN_BLOCK, q, allowed)


def _attn(x, ln1, a, shape: KeyeShape, inv, inv_index, fault=None):
    """One attention block under its selection; ``a`` the layer's attention
    leaves, float32."""
    S = x.shape[0]
    H, KV, hd, eps = shape.heads, shape.kv_heads, shape.hd, shape.eps
    h = ref.rmsnorm(x, ln1, eps)
    q = (h @ a["wq"]).reshape(S, H, hd)
    k = (h @ a["wk"]).reshape(S, KV, hd)
    v = (h @ a["wv"]).reshape(S, KV, hd)
    if fault != "qk_norm_off":
        q = ref.rmsnorm(q, a["q_norm"], eps)  # over each head's values
        k = ref.rmsnorm(k, a["k_norm"], eps)
    ang = mrope_angles(text_positions(S), inv, shape.sections)
    q, k = rotate(q, ang), rotate(k, ang)
    if fault == "kv_group_off_by_one":
        k, v = jnp.roll(k, 1, axis=1), jnp.roll(v, 1, axis=1)
    allowed = _allowed(
        h, a["idx"], inv_index, heads=shape.index_heads, dim=shape.index_dim,
        topk=shape.index_topk, eps=eps,
        fault=fault if fault in ("selection_off", "selection_recent",
                                 "indexer_rope_off", "index_norm_off")
        else None)
    if fault == "page_dropped":  # one page in the middle, for later tokens
        lo = PAGE * (S // (2 * PAGE))
        pos = jnp.arange(S)
        allowed &= ~((pos[None, :] >= lo) & (pos[None, :] < lo + PAGE)
                     & (pos[:, None] >= lo + PAGE))
    o = _attend(q.reshape(S, KV, H // KV, hd), k, v, allowed)
    return x + o.reshape(S, H * hd) @ a["wo"]


@partial(jax.jit, static_argnames=("top_k", "first", "held", "eps", "fault"))
def _route(x, ln2, router, *, top_k, first, held, eps, fault=None):
    """(normed input, routing weights [S, held] of the experts held here,
    zero where not chosen; margin [S]: the least change of a router logit
    that would move an expert held here into or out of the choice)."""
    h = ref.rmsnorm(x, ln2, eps)
    logit = h @ router
    p = jax.nn.softmax(logit, axis=-1)
    _, idx = jax.lax.top_k(p, top_k + 1)
    edge = jnp.take_along_axis(logit, idx[:, top_k - 1:], axis=-1)
    last_in, first_out = edge[:, :1], edge[:, 1:]
    mine = logit[:, first:first + held]
    margin = jnp.where(mine >= last_in, mine - first_out, last_in - mine).min(1)
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(p, idx, axis=1)
    if fault == "held_renorm":  # over the chosen that are held here alone
        here = (idx >= first) & (idx < first + held)
        w = w / jnp.maximum((w * here).sum(-1, keepdims=True), 1e-20)
    elif fault != "renorm_off":
        w = w / w.sum(-1, keepdims=True)
    full = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(w)
    return h, full[:, first:first + held], margin


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
    rounded."""
    return lambda tree: jax.tree.map(partial(_up, bits=bits),
                                     ref.f32(tree, device))


def hidden(params, ids, shape, device=None, fault=None, bits: int = 0,
           first_expert: int = 0, bank=None):
    """[S] token ids -> (hidden before the final norm [S,d] float32, the
    smallest routing margin of each position over the layers [S]).
    ``first_expert``: which share of the layer the held experts are (the
    configuration's member is 0), and ``bank`` the expert banks to read them
    from (``params``' own by default): the shares-add-up test asks for other
    members' and for the uncut layer."""
    load = _loader(device, bits)
    inv = jax.device_put(inv_freq(shape.rope_theta, shape.hd), device)
    inv_index = jax.device_put(
        inv_freq(shape.rope_theta, shape.index_dim), device)
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        L = params["layers"]
        m = bank or L["mlp"]
        for i in range(shape.layers):
            at = lambda sub: load(ref.layer(L[sub], i))
            x = _attn(x, at("ln1"), at("attn"), shape, inv, inv_index, fault)
            h, w, mg = _route(
                x, at("ln2"), load(L["mlp"]["router"][i]), top_k=shape.top_k,
                first=first_expert, held=m["wi"].shape[1], eps=shape.eps,
                fault=fault if fault in ("renorm_off", "held_renorm")
                else None)
            margin = jnp.minimum(margin, mg)
            x = _add_experts(x, h, w, m["wg"], m["wi"], m["wo"], i, bits=bits)
    return x, margin


def logits(params, ids, shape, device=None, last: int | None = None,
           with_margin: bool = False, fault: str | None = None,
           bits: int = 0, **share):
    """Logits float32 over the vocabulary slice for the last ``last``
    positions (all if None); with ``with_margin`` also each of those
    positions' smallest routing margin over the layers: how near an expert
    held here was to changing sides, in router logits. ``fault`` and
    ``bits`` break the reference on purpose (``faulted`` makes both from a
    name); ``share`` is :func:`hidden`'s ``first_expert`` / ``bank``."""
    x, margin = hidden(params, ids, shape, device, fault, bits, **share)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    load = _loader(device, bits)
    with ref.HIGHEST():
        out = ref.rmsnorm(x, load(params["final_norm"]),
                          shape.eps) @ load(params["lm_head"])
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def indexer_cost(shape: KeyeShape, context_keys: float, index_keys: float,
                 query_rows: float, itemsize: int = 2):
    """The indexer's scoring and selection of ONE layer: (flops, bytes) the
    work needs. ``context_keys``: for every real query token, the cached
    tokens at or before it, summed: each pair costs a dot product of every
    index head, 2 x heads x width (the ReLU, the weighted sum over heads and
    the selection itself are counted as free). Bytes: the index key (its own
    width: what a pool pads it to is the pool's affair) of the
    ``index_keys`` tokens in the pages that hold a slot's context, once a
    slot, and the real rows' index queries and head weights in; nothing out
    (a selection that stays on the chip)."""
    flops = 2 * shape.index_heads * shape.index_dim * context_keys
    keys = shape.index_dim * itemsize * index_keys
    q = shape.index_heads * (shape.index_dim * itemsize + 4) * query_rows
    return flops, keys + q


def sparse_attention_cost(shape: KeyeShape, attended_keys: float,
                          chosen_rows: float, query_rows: float,
                          itemsize: int = 2):
    """Grouped-query attention of ONE layer over the selection: (flops,
    bytes) the work needs. ``attended_keys``: for every real query token
    ``min(context, topk)``, summed: each pair costs QK^T and PV in every
    head, 2 x 2 x heads x head_dim. Bytes: K and V (every KV head) of each
    of the ``chosen_rows`` tokens some query of a slot chose, once a slot
    (the caller gives a count that is certainly reached: the last query's),
    and the queries in and the outputs out for ``query_rows`` rows."""
    flops = 2 * 2 * shape.heads * shape.hd * attended_keys
    kv = 2 * shape.kv_heads * shape.hd * itemsize * chosen_rows
    q_out = 2 * shape.heads * shape.hd * itemsize * query_rows
    return flops, kv + q_out
