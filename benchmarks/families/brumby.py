"""The ``brumby`` family: how its configuration files spell their sizes, the
plain reference of what they compute, and what its kernel needs.

Brumby-14B-Base (manifestai/Brumby-14B-Base, config.json; ``model_type``
``brumby``: Qwen3-14B's weights with every attention layer replaced by a
power-retention layer, Manifest AI, arXiv:2507.04239): embedding -> blocks ->
RMSNorm -> untied head. Every block is pre-norm (RMSNorm, eps
``rms_norm_eps``, no biases): ``h <- h + Retention(RMSNorm(h))``, ``h <- h +
SwiGLU(RMSNorm(h))``.

Power retention of degree 2, ``H`` query heads over ``KV`` kv heads of
``hd``: ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; RMSNorm a head on q and
k (Qwen3's QK-norm), rotary (half-split pairs, ``rope_theta``); a log-gate a
kv head a token ``g = log sigmoid(x W_g)``, ``G`` its running sum. Query head
``h`` of kv head ``h // (H / KV)`` attends, in the ATTENTION form (no state,
no feature map: independent of whatever layout a program keeps its state
in)::

    a_ij = exp(G_i - G_j) (q_i . k_j / sqrt(hd)) ** 2      j <= i, else 0
    o_i  = sum_j a_ij v_j / (sum_j a_ij + eps)

computed in query blocks over all keys (the exponent masked before it is
taken, never a quotient). ``y = concat_h(o) W_o``: no output gate or norm.

``FAULTS`` names the ways the reference can be broken on purpose, each what
one fault of a serving engine does to the arithmetic (``token_clear`` and
``token_tied`` are ``run.py``'s own, of any family). Nothing sets one in a
measured run.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks import reference as ref
from benchmarks.flops import Shape

CHUNK = 128   # rows a serving step feeds a slot (the chunk faults' unit)
BLOCK = 512   # query rows a block of the attention form (half past 4 k keys)
VOCAB_BLOCK = 16384  # head rows upcast at a time

FAULTS = (
    "gate_off",             # g = 0: nothing is forgotten
    "normaliser_off",       # o = sum_j a_ij v_j, not divided
    "degree_1",             # a_ij ~ q . k, not its square
    "qk_norm_off",          # q and k not normed a head
    "rope_off",             # q and k not rotated
    "gate_per_query_head",  # head h takes gate h % KV, not its kv head's
    "state_bf16",           # the state held in bf16 between chunks
    "state_not_reset",      # a request starts from what its slot held
    "weights_int8",         # every matrix rounded to 8 bits a column
)


@dataclass(frozen=True)
class BrumbyShape(Shape):
    """``flops.Shape`` plus what retention adds."""

    layer_ids: tuple = ()   # each layer's published index, as run
    ret_eps: float = 1e-6   # the normaliser's

    @property
    def expanded(self) -> int:
        """The fewest numbers a key's square takes: the symmetric square."""
        return self.hd * (self.hd + 1) // 2

    def layer_matmul_params(self, active: bool = True) -> int:
        return super().layer_matmul_params(active) + self.d * self.kv_heads

    def attention_flops_per_token(self, context: float) -> float:
        """The recurrence's count, whatever the context: a row's update of
        ``KV`` states and read-out of ``H`` heads over state and
        normaliser."""
        return self.layers * 2 * (self.heads + self.kv_heads) * (
            self.expanded * (self.hd + 1))


def shape_of(config: dict) -> BrumbyShape:
    """The published keys of Brumby-14B-Base's ``config.json``;
    ``layer_ids`` says which layers of the release are run."""
    ids = tuple(int(i) for i in config["layer_ids"])
    assert len(ids) == int(config["num_hidden_layers"])
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assumed = config["assumed"]
    assert int(assumed["degree"]) == 2
    return BrumbyShape(
        config["family"], int(config["hidden_size"]), len(ids),
        int(config["num_attention_heads"]),
        int(config["num_key_value_heads"]), int(config["head_dim"]),
        int(config["intermediate_size"]), int(config["vocab_size"]), 0, 0,
        True, bool(config["tie_word_embeddings"]),
        float(config["rms_norm_eps"]), float(config["rope_theta"]),
        layer_ids=ids, ret_eps=float(assumed["normaliser_eps"]))


@partial(jax.jit, static_argnames=("bits",))
def _up(w, bits: int = 0):
    """A served matrix (or vector) as the reference reads it: float32 and,
    with ``bits``, a matrix rounded to that many bits (symmetric, to
    nearest, one scale a column; the gate's 8 columns are too few for
    one)."""
    w = w.astype(ref.F32)
    if bits and w.ndim == 2 and min(w.shape) > 8:
        top = 2 ** (bits - 1) - 1
        scale = jnp.abs(w).max(axis=0, keepdims=True) / top
        return jnp.clip(jnp.round(w / scale), -top - 1, top) * scale
    return w


def _head_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


# -------------------------------------------------------------- retention
def _attend(q, k, v, G, Gq, *, eps, fault=None, first: int = 0,
            parts: bool = False):
    """The attention form in query blocks: q [S, H, hd] (row ``i`` at
    position ``first + i`` of the keys), k / v [T, KV, hd], ``G`` [T, KV]
    the keys' running log-gates and ``Gq`` [S, H] the queries' -> [S, H,
    hd]; with ``parts`` the numerator and the denominator [S, H] apart."""
    S, H, hd = q.shape
    T, KV = k.shape[:2]
    kk = jnp.repeat(k, H // KV, axis=1)
    vv = jnp.repeat(v, H // KV, axis=1)
    Gk = jnp.repeat(G, H // KV, axis=1)
    kpos = jnp.arange(T)
    block = BLOCK if T <= 8 * BLOCK else BLOCK // 2  # a block's scores fit
    nums, dens = [], []
    for lo in range(0, S, block):
        qpos = first + jnp.arange(lo, min(lo + block, S))
        seen = (kpos[None, :] <= qpos[:, None])[None]           # [1, b, T]
        s = jnp.einsum("qhd,khd->hqk", q[lo:lo + block], kk) * hd ** -0.5
        diff = Gq[lo:lo + block].T[:, :, None] - Gk.T[:, None, :]
        a = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0) * (
            s if fault == "degree_1" else s * s)
        nums.append(jnp.einsum("hqk,khd->qhd", a, vv))
        dens.append(a.sum(-1).T)
    num, den = jnp.concatenate(nums), jnp.concatenate(dens)
    if parts:
        return num, den
    return num if fault == "normaliser_off" else num / (den[..., None] + eps)


def _chunked_bf16(q, k, v, g, *, eps):
    """``state_bf16``: the same sums as a recurrence over chunks of ``CHUNK``
    rows, the state (the plain outer square of the key, 16,384 numbers at
    128: this reference's own layout) and the normaliser rounded to bf16
    where a chunk ends, as a program that held them so would hand them on."""
    S, H, hd = q.shape
    KV = k.shape[1]
    grp = H // KV
    sq = lambda a: (a[..., :, None] * a[..., None, :]).reshape(
        *a.shape[:-1], hd * hd)
    state = jnp.zeros((KV, hd * hd, hd), ref.F32)
    z = jnp.zeros((KV, hd * hd), ref.F32)
    out = []
    for lo in range(0, S, CHUNK):
        qc, kc, vc, gc = (a[lo:lo + CHUNK] for a in (q, k, v, g))
        n = qc.shape[0]
        c = jnp.cumsum(gc, axis=0)                              # [n, KV]
        num, den = _attend(qc, kc, vc, c, jnp.repeat(c, grp, axis=1),
                           eps=eps, parts=True)
        pq = sq(qc).reshape(n, KV, grp, hd * hd) * jnp.exp(c)[:, :, None, None]
        num = num + jnp.einsum("nckf,cfe->ncke", pq, state).reshape(n, H, hd)
        den = den + jnp.einsum("nckf,cf->nck", pq, z).reshape(n, H)
        out.append(num / (den[..., None] + eps))
        left = jnp.exp(c[-1][None] - c)[..., None] / hd          # s ** 2
        pk = sq(kc) * left
        state = jnp.exp(c[-1])[:, None, None] * state + jnp.einsum(
            "ncf,nce->cfe", pk, vc)
        z = jnp.exp(c[-1])[:, None] * z + pk.sum(0)
        state = state.astype(jnp.bfloat16).astype(ref.F32)
        z = z.astype(jnp.bfloat16).astype(ref.F32)
    return jnp.concatenate(out, axis=0)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps", "theta",
                                   "ret_eps", "fault"))
def _retention(h, a, *, heads, kv_heads, hd, eps, theta, ret_eps, fault=None):
    """One retention mixer over normed inputs ``h`` [S, d]."""
    S = h.shape[0]
    q = (h @ a["wq"]).reshape(S, heads, hd)
    k = (h @ a["wk"]).reshape(S, kv_heads, hd)
    v = (h @ a["wv"]).reshape(S, kv_heads, hd)
    if fault != "qk_norm_off":
        q = _head_norm(q, a["q_norm"]["scale"], eps)
        k = _head_norm(k, a["k_norm"]["scale"], eps)
    if fault != "rope_off":
        q, k = ref.rope(q, theta), ref.rope(k, theta)
    g = jax.nn.log_sigmoid(h @ a["wg"])                          # [S, KV]
    if fault == "gate_off":
        g = jnp.zeros_like(g)
    if fault == "state_bf16":
        o = _chunked_bf16(q, k, v, g, eps=ret_eps)
        return o.reshape(S, heads * hd) @ a["wo"]
    G = jnp.cumsum(g, axis=0)
    grp = heads // kv_heads
    first = 0
    if fault == "state_not_reset":  # what the slot's last request left: the
        # same rows once more, before these
        k, v = jnp.concatenate([k, k]), jnp.concatenate([v, v])
        G = jnp.concatenate([G, G[-1][None] + G])
        first = S
    Gq = jnp.repeat(G[first:], grp, axis=1)
    if fault == "gate_per_query_head":
        Gk = jnp.tile(G, (1, grp))  # head h reads gate h % KV
        o = _attend(q, jnp.repeat(k, grp, axis=1), jnp.repeat(v, grp, axis=1),
                    Gk, Gk[first:], eps=ret_eps, first=first)
    else:
        o = _attend(q, k, v, G, Gq, eps=ret_eps, fault=fault, first=first)
    return o.reshape(S, heads * hd) @ a["wo"]


@partial(jax.jit, static_argnames="bits")
def _gated_served(h, m, bits: int = 0):
    """SwiGLU from the served matrices, each upcast as it is used."""
    up = partial(_up, bits=bits)
    return (jax.nn.silu(h @ up(m["wg"])) * (h @ up(m["wi"]))) @ up(m["wo"])


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords: every fault here is arithmetic of the reference itself,
    but the rounding, which is done as each matrix is upcast (``bits``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault is not None and fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
    return dict(params=params, fault=fault)


def hidden(params, ids, shape: BrumbyShape, device=None, fault=None,
           bits: int = 0):
    """[S] token ids -> hidden before the final norm [S, d] float32, one
    layer's weights upcast at a time (an MLP matrix at a time)."""
    def on_device(tree):
        return jax.tree.map(lambda w: jax.device_put(w, device), tree)

    def load(tree):
        return jax.tree.map(partial(_up, bits=bits), on_device(tree))

    L = params["retention_layers"]
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        for i in range(shape.layers):
            at = lambda sub: ref.layer(L[sub], i)
            h = ref.rmsnorm(x, load(at("ln1")), shape.eps)
            x = x + _retention(
                h, load(at("attn")), heads=shape.heads,
                kv_heads=shape.kv_heads, hd=shape.hd, eps=shape.eps,
                theta=shape.rope_theta, ret_eps=shape.ret_eps, fault=fault)
            x = x + _gated_served(
                ref.rmsnorm(x, load(at("ln2")), shape.eps),
                on_device(at("mlp")), bits)
    return x


def logits(params, ids, shape: BrumbyShape, device=None,
           last: int | None = None, with_margin: bool = False,
           fault: str | None = None, bits: int = 0):
    """Logits float32 for the last ``last`` positions (all if None), the
    head in blocks of ``VOCAB_BLOCK`` vocabulary rows (whole it is 3.1 GB in
    float32); with ``with_margin`` also a margin a position: infinite, this
    family routes nothing. ``fault`` and ``bits`` break the reference on
    purpose: ``faulted`` makes both from a name."""
    x = hidden(params, ids, shape, device, fault, bits)
    if last is not None:
        x = x[-last:]
    up = lambda w: _up(jax.device_put(w, device), bits=bits)
    head = params["lm_head"]
    with ref.HIGHEST():
        y = ref.rmsnorm(x, jax.tree.map(up, params["final_norm"]), shape.eps)
        if bits:  # a column's scale is over all its rows: whole
            out = y @ up(head)
        else:
            out = jnp.concatenate([
                y @ up(head[:, lo:lo + VOCAB_BLOCK])
                for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=1)
    margin = jnp.full((out.shape[0],), jnp.inf, ref.F32)
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def retention_cost(shape: BrumbyShape, rows: float, state_slots: float,
                   itemsize: int = 2):
    """The retention of ONE layer: (flops, bytes) the traced steps needed,
    from the program's counters (``retention_rows`` the real rows,
    ``retention_state_slots`` the live states). For every real row the
    read-out of ``H`` heads over state and normaliser (2 x D x (hd + 1)
    each), the update of ``KV`` states and normalisers (the same each) and
    the chunk's own ``A``, counted at its least, the row's own pair (QK^T
    and PV of every head); ``D`` the symmetric square, the fewest numbers a
    layout can keep (a layout that pads keeps and does more). Bytes: every
    live state and normaliser read and written once a slot a step, float32;
    the real rows' q, k, v and log-gates in and o out."""
    H, KV, hd, D = shape.heads, shape.kv_heads, shape.hd, shape.expanded
    flops = (2 * (H + KV) * D * (hd + 1) + 4 * H * hd) * rows
    state = 2 * KV * D * (hd + 1) * 4 * state_slots
    per_row = (2 * H + 2 * KV) * hd * itemsize + KV * 4
    return flops, state + per_row * rows
