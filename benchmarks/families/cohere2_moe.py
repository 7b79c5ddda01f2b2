"""The ``cohere2_moe`` family: how its configuration files spell their sizes,
the plain reference of what they compute, and what its kernels need.

Command A+ (CohereLabs/command-a-plus-05-2026, config.json; ``model_type``
``cohere2_moe``): embedding -> N x parallel block -> LayerNorm -> the TIED
embedding as head (``logit_scale`` is 1: no scale). One layer, ``h`` [T, d]:

1. ``n = (h - mean(h)) / sqrt(var(h) + eps) * w_ln``: Cohere's LayerNorm,
   mean-centred, a scale and NO bias; ONE norm a layer.
2. ``q = n W_q`` [T, H, hd], ``k = n W_k``, ``v = n W_v`` [T, KV, hd]; no
   bias, no norm on q or k.
3. ``layer_types`` ``sliding_attention`` (three of four, first in the
   period): q and k rotated over all ``hd`` values by the plain table of
   ``rope_theta``, pairs ``(2i, 2i + 1)`` (``rope_gptj``); row ``i`` sees keys
   ``i - sliding_window < j <= i``. ``full_attention``: NO rotation (NoPE),
   causal.
4. ``A = concat_h softmax(q_h k_{h // G}^T hd^-0.5) v_{h // G} W_o``.
5. ``s = sigmoid(n W_r)`` in float32 over ALL published experts; ``S`` its
   ``num_experts_per_tok`` largest; ``g_e = s_e / sum_{j in S} s_j``; ``R =
   sum_{e in S, e held} g_e E_e(n)``, ``E(x) = (silu(x W_g) * (x W_u)) W_d``.
   The configuration holds ONE member's share of an expert-parallel layer:
   the weights are over all chosen experts wherever they live, the experts
   ``first .. first + num_experts - 1`` are computed here, what the others
   would add is left out (as in the program), no token is dropped.
6. ``Sh = (1 / n_sh) sum_j E^sh_j(n)``: the shared experts, AVERAGED.
7. ``h' = h + A + R + Sh`` (``use_parallel_block``).

The program's tree holds ``W_q`` and ``W_k`` with the columns of every head
permuted (even columns first, then odd: ``deepspeed_tpu/models/cohere.py``
``half_split_columns``), which makes its rotate-half the published
interleaved rotation. The reference undoes the permutation
(:func:`published_columns`) and rotates interleaved pairs, as published.

Everything is computed in blocks of query rows with one matrix upcast to
float32 at a time, so a 20 k-token sample fits beside a 12 GB engine.

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

FAULTS = (
    "block_sequential",     # the expert layer reads the post-attention stream
    "shared_summed",        # the shared experts summed, not averaged
    "rope_on_full",         # the NoPE layer rotated like a window layer
    "rope_off_window",      # no rotation on the window layers
    "pairs_half_split",     # rotate-half on the published column order
    "softmax_router",       # softmax scores instead of sigmoid
    "weights_unnormalised",  # the chosen scores not renormalised to one
    "held_offset_off",      # the NEXT member's experts' columns of the router
    "norm_not_centred",     # the norm without its mean (an RMS norm)
    "window_off",           # window layers see the whole context
    "window_off_by_one",    # ... one key more than the window
    "gqa_mispaired",        # every query head reads its neighbour group's K/V
    "weights_int8",         # every matrix rounded to 8 bits a column
)
SLIDING, FULL = "sliding_attention", "full_attention"
ATTN_BLOCK = 64    # query rows a step of attention


@dataclass(frozen=True)
class CohereShape(Shape):
    """``flops.Shape`` plus the layer kinds, the window, the shared experts
    and the share of the expert layer held here."""

    pattern: tuple = ()
    window: int = 0
    shared: int = 0          # shared experts, averaged
    routed: int = 0          # the router's width: the published experts
    first_expert: int = 0    # the first of ``experts`` held here

    def kind_layers(self, kind: str) -> int:
        return sum(1 for i in range(self.layers)
                   if self.pattern[i % len(self.pattern)] == kind)

    def layer_matmul_params(self, active: bool = True) -> int:
        """Matmul parameters of one layer; ``active``: what ONE token
        touches in the whole layer (``top_k`` routed experts wherever they
        live), otherwise what is stored here (the experts held)."""
        attn = 2 * self.d * self.hd * (self.heads + self.kv_heads)
        one = 3 * self.d * self.ffn
        n = self.top_k if active else self.experts
        return attn + (n + self.shared) * one + self.d * self.routed

    def attention_flops_per_token(self, context: float) -> float:
        per_key = 2 * 2 * self.heads * self.hd
        return per_key * (
            self.kind_layers(FULL) * context
            + self.kind_layers(SLIDING) * min(context, self.window))


def shape_of(config: dict) -> CohereShape:
    """The published keys of ``cohere2_moe``'s ``config.json``;
    ``num_experts`` is the experts HELD (the file's ``published`` has the
    router's width) and ``deployment_share.first_expert`` their first."""
    if float(config["logit_scale"]) != 1.0:
        raise ValueError("logit_scale %r: neither the program nor this "
                         "reference scales the logits" % config["logit_scale"])
    types = list(config["layer_types"])
    period = next(p for p in range(1, len(types) + 1)
                  if all(t == types[i % p] for i, t in enumerate(types)))
    published = config.get("published", {})
    return CohereShape(
        config["family"], int(config["hidden_size"]),
        int(config["num_hidden_layers"]), int(config["num_attention_heads"]),
        int(config["num_key_value_heads"]), int(config["head_dim"]),
        int(config["intermediate_size"]), int(config["vocab_size"]),
        int(config["num_experts"]), int(config["num_experts_per_tok"]), True,
        bool(config["tie_word_embeddings"]), float(config["layer_norm_eps"]),
        float(config["rope_theta"]),
        pattern=tuple(types[:period]), window=int(config["sliding_window"]),
        shared=int(config["num_shared_experts"]),
        routed=int(published.get("num_experts", config["num_experts"])),
        first_expert=int(config.get("deployment_share", {}).get(
            "first_expert", 0)))


def published_columns(w, hd: int):
    """[..., H x hd] in the program's column order (inside a head the even
    published columns, then the odd) -> the published order."""
    lead = w.shape[:-1]
    halves = w.reshape(*lead, -1, 2, hd // 2)       # [.., H, (even|odd), i]
    return jnp.swapaxes(halves, -1, -2).reshape(*lead, -1)


def _rotate(x, first, theta: float, pairs: str = "interleaved"):
    """x [S, H, hd] at positions first..first+S-1, all ``hd`` values rotated
    by the plain table of ``theta``; pairs (2i, 2i + 1) as published, or
    (i, i + hd/2) under the ``pairs_half_split`` fault."""
    S, _, hd = x.shape
    inv = theta ** -(jnp.arange(0, hd, 2, dtype=ref.F32) / hd)
    ang = (first + jnp.arange(S, dtype=ref.F32))[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if pairs == "interleaved":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames="bits")
def _up(w, bits: int = 0):
    """One matrix in float32; ``bits``: rounded first (symmetric, one scale
    a column: the ``weights_int`` faults)."""
    w = w.astype(ref.F32)
    if not bits:
        return w
    top = 2 ** (bits - 1) - 1
    scale = jnp.abs(w).max(axis=0, keepdims=True) / top
    return jnp.clip(jnp.round(w / scale), -top - 1, top) * scale


def layernorm(x, scale, eps: float, centred: bool = True):
    """Cohere's LayerNorm: mean-centred, a scale, no bias."""
    if centred:
        x = x - x.mean(-1, keepdims=True)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


@partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "hd", "window", "theta", "pairs", "mispaired"))
def _attn(n, wq, wk, wv, wo, *, heads, kv_heads, hd, window, theta, pairs,
          mispaired=False):
    """Steps 2-4 for the normed rows ``n`` [S, d]; the matrices float32 as
    the program holds them, q and k brought to the PUBLISHED column order
    (:func:`published_columns`, on the rows: a permuted copy of ``W_q`` would
    be 268 MB). ``window`` 0 = every key at or before the
    row; ``theta`` 0 = no rotation. In blocks of ``ATTN_BLOCK`` query rows,
    one KV head's group of query heads at a time: the scores of a block are
    [G, block, S]."""
    S, d = n.shape
    G = heads // kv_heads
    k = published_columns(n @ wk, hd).reshape(S, kv_heads, hd)
    v = (n @ wv).reshape(S, kv_heads, hd)
    if theta:
        k = _rotate(k, 0, theta, pairs)
    if mispaired:  # K/V head g is read where g + 1 is computed
        k, v = jnp.roll(k, 1, axis=1), jnp.roll(v, 1, axis=1)
    blocks = -(-S // ATTN_BLOCK)
    rows = jnp.pad(n, ((0, blocks * ATTN_BLOCK - S), (0, 0)))
    kpos = jnp.arange(S)

    def block(i):
        lo = i * ATTN_BLOCK
        nb = jax.lax.dynamic_slice(rows, (lo, 0), (ATTN_BLOCK, d))
        q = published_columns(nb @ wq, hd).reshape(ATTN_BLOCK, heads, hd)
        if theta:
            q = _rotate(q, lo, theta, pairs)
        qpos = lo + jnp.arange(ATTN_BLOCK)
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen &= kpos[None, :] > qpos[:, None] - window

        def group(g):
            qg = jax.lax.dynamic_slice(
                q, (0, g * G, 0), (ATTN_BLOCK, G, hd))
            kg = jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
            vg = jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
            s = jnp.einsum("qhd,kd->hqk", qg, kg) / math.sqrt(hd)
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1), vg)

        o = jax.lax.map(group, jnp.arange(kv_heads))   # [KV, block, G, hd]
        o = jnp.moveaxis(o, 0, 1).reshape(ATTN_BLOCK, heads * hd)
        return o @ wo

    out = jax.lax.map(block, jnp.arange(blocks))
    return out.reshape(blocks * ATTN_BLOCK, d)[:S]


@partial(jax.jit, static_argnames=("top_k", "first", "held", "fault"))
def _route(n, router, *, top_k, first, held, fault=None):
    """Step 5's weights: (w [S, held] float32, the weight of every held
    expert for every row, zero where it is not chosen; margin [S], by how
    much the router's logit of the last expert chosen beat the first left
    out)."""
    logit = n @ router
    score = jax.nn.softmax(logit, -1) if fault == "softmax_router" else (
        jax.nn.sigmoid(logit))
    top, idx = jax.lax.top_k(score, top_k + 1)
    near = jnp.take_along_axis(logit, idx[:, top_k - 1:], axis=-1)
    top, idx = top[:, :top_k], idx[:, :top_k]
    if fault != "weights_unnormalised":
        top = top / top.sum(-1, keepdims=True)
    w = jnp.zeros_like(logit).at[
        jnp.arange(n.shape[0])[:, None], idx].set(top)
    return w[:, first:first + held], near[:, 0] - near[:, 1]


ROW_BLOCK = 2048   # rows an expert's three products take at once


@partial(jax.jit, static_argnames=("bits", "folded"))
def _experts(n, w, wg, wi, wo, i, bits: int = 0, folded: int = 0):
    """``sum_e w[:, e] E_e(n)`` over the bank of layer ``i`` ([L, E, ...],
    as served), one expert after the other and every one on EVERY row: plain
    and wasteful on purpose; one expert's float32 copy and the products of
    a block of rows are the only temporaries. ``folded`` > 0: the bank is the
    program's shared one, that many experts side by side ([L, d, E x f] /
    [L, E x f, d]) with the mean's ``1 / E`` in the down projection
    (``models/cohere.py`` ``averaged_shared_bank``); expert ``e`` is its
    columns / rows and the down projection times ``E``: the published
    experts again."""
    S, d = n.shape
    block = min(ROW_BLOCK, -(-S // 128) * 128)  # a short sample pads little
    blocks = -(-S // block)
    pad = ((0, blocks * block - S), (0, 0))
    nb = jnp.pad(n, pad).reshape(blocks, block, d)
    wb = jnp.pad(w, pad).reshape(blocks, block, -1)
    f = wo.shape[-2] // (folded or 1)

    def mat(bank, e, down=False):
        if not folded:
            m = jax.lax.dynamic_slice(
                bank, (i, e, 0, 0), (1, 1, *bank.shape[2:]))[0, 0]
        elif down:
            m = jax.lax.dynamic_slice(bank, (i, e * f, 0), (1, f, d))[0]
        else:
            m = jax.lax.dynamic_slice(bank, (i, 0, e * f), (1, d, f))[0]
        m = _up(m, bits)
        return m * folded if folded and down else m

    def add(e, y):
        g, u, down = mat(wg, e), mat(wi, e), mat(wo, e, True)

        def rows(t):
            x, weight = t
            return ((jax.nn.silu(x @ g) * (x @ u)) @ down) * (
                jax.lax.dynamic_index_in_dim(weight, e, 1, keepdims=False)
                [:, None])

        return y + jax.lax.map(rows, (nb, wb))

    out = jax.lax.fori_loop(0, folded or wg.shape[1], add, jnp.zeros_like(nb))
    return out.reshape(blocks * block, d)[:S]


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or
    None), as its keywords: every fault is made inside the reference."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault and fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
    return dict(params=params, fault=fault)


def layer_parts(L, i, x, shape: CohereShape, device=None, fault=None,
                bits: int = 0, first=None):
    """One layer's three branches for the stream ``x`` [S, d]: (A, R, Sh,
    margin) of steps 1-6, from the stacked tree ``L`` at index ``i``.
    ``first``: the first held expert's column of the router (the share's
    own by default: the tests sum the members' parts)."""
    first = shape.first_expert if first is None else first
    if fault == "held_offset_off":
        first = (first + shape.experts) % shape.routed
    a, m = L["attn"], L["mlp"]
    at = lambda w: _up(jax.device_put(w[i], device), bits)
    kind = shape.pattern[i % len(shape.pattern)]
    window = shape.window if kind == SLIDING else 0
    if window and fault == "window_off":
        window = 0
    if window and fault == "window_off_by_one":
        window += 1
    rotated = (kind == SLIDING) != (
        fault == ("rope_off_window" if kind == SLIDING else "rope_on_full"))
    scale = at(L["ln1"]["scale"])
    n = layernorm(x, scale, shape.eps, fault != "norm_not_centred")
    A = _attn(
        n, at(a["wq"]), at(a["wk"]), at(a["wv"]), at(a["wo"]),
        heads=shape.heads, kv_heads=shape.kv_heads, hd=shape.hd,
        window=window, theta=shape.rope_theta if rotated else 0.0,
        pairs="half" if fault == "pairs_half_split" else "interleaved",
        mispaired=fault == "gqa_mispaired")
    if fault == "block_sequential":  # the MLP after the attention's sum
        n = layernorm(x + A, scale, shape.eps)
    w, margin = _route(n, at(m["router"]), top_k=shape.top_k, first=first,
                       held=shape.experts,
                       fault=fault if fault in ("softmax_router",
                                                "weights_unnormalised")
                       else None)
    R = _experts(n, w, m["wg"], m["wi"], m["wo"], i, bits=bits)
    sh = m["shared"]
    ones = jnp.ones((n.shape[0], shape.shared), ref.F32)
    Sh = _experts(n, ones, sh["wg"], sh["wi"], sh["wo"], i, bits=bits,
                  folded=shape.shared)
    if fault != "shared_summed":
        Sh = Sh / shape.shared
    return A, R, Sh, margin


def hidden(params, ids, shape: CohereShape, device=None, fault=None,
           bits: int = 0):
    """[S] token ids -> (hidden before the final norm [S, d] float32, the
    smallest routing margin of each position over the layers [S])."""
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        for i in range(shape.layers):
            A, R, Sh, mg = layer_parts(params["layers"], i, x, shape, device,
                                       fault, bits)
            x = x + A + R + Sh
            margin = jnp.minimum(margin, mg)
    return x, margin


def logits(params, ids, shape: CohereShape, device=None,
           last: int | None = None, with_margin: bool = False,
           fault: str | None = None, bits: int = 0):
    """Logits float32 for the last ``last`` positions (all if None) over the
    vocabulary slice the tied embedding holds; with ``with_margin`` also each
    of those positions' smallest routing margin. ``fault`` and ``bits``
    break the reference on purpose: ``faulted`` makes both from a name."""
    x, margin = hidden(params, ids, shape, device, fault, bits)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    with ref.HIGHEST():
        scale = _up(jax.device_put(params["final_norm"]["scale"], device))
        head = _up(jax.device_put(params["embed"]["tok"], device), bits)
        out = layernorm(x, scale, shape.eps,
                        fault != "norm_not_centred") @ head.T
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def window_attention_cost(shape: CohereShape, attended_keys: float,
                          fetched_keys: float, query_rows: float,
                          itemsize: int = 2):
    """The paged attention call of ONE layer (of either kind): (flops,
    bytes) that the work needs. ``attended_keys``: for every real query
    token, the keys it sees, summed. FLOPs: QK^T and PV, 2 x 2 x heads x
    head_dim a (query, key) pair. Bytes: K and V of the ``fetched_keys``,
    the keys of the pages that hold a key some row of the slot sees, each
    ONCE for all the rows and all the 16 query heads a KV head of its slot
    (a kernel that tiles a chunk's rows and reads them once a tile earns no
    share for it), and the queries in and the outputs out for
    ``query_rows`` rows."""
    flops = 2 * 2 * shape.heads * shape.hd * attended_keys
    kv = 2 * shape.kv_heads * shape.hd * itemsize * fetched_keys
    q_out = 2 * shape.heads * shape.hd * itemsize * query_rows
    return flops, kv + q_out


full_attention_cost = window_attention_cost


def shared_expert_cost(shape: CohereShape, real_rows: float, steps: float,
                       itemsize: int = 2):
    """The shared branch of ALL layers over the traced steps: (flops,
    bytes). FLOPs: three products of 2 x d x ffn a shared expert a real row.
    Bytes: the bank once a step a layer (3 x shared x d x ffn), and a row in
    and a row out a real row a layer."""
    one = 3 * shape.d * shape.ffn * shape.shared
    flops = 2 * one * real_rows * shape.layers
    nbytes = (one * steps + 2 * shape.d * real_rows) * itemsize * shape.layers
    return flops, nbytes


def gating_shapes(shape: CohereShape, budget: int) -> str:
    """A regular expression for the instructions of the gate in a step of
    ``budget`` rows (``gating_ms_per_step``): an operand or a result whose
    trailing axes are [budget, routed experts] (scores, the top-k's sort),
    [budget, top_k] (chosen experts, weights, places) or [budget, top_k,
    experts held] (the placement's one-hots), and NO operand of the [budget
    x top_k, hidden] pair rows, which are the combine's (the expert layer's
    own, with the weights as its last operand)."""
    tails = "|".join(str(t) for t in (
        shape.routed, shape.top_k, f"{shape.top_k},{shape.experts}"))
    return r"^(?!.*\[%d,%d\]).*\[(\d+,)*%d,(%s)\]" % (
        budget * shape.top_k, shape.d, budget, tails)
