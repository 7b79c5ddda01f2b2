"""Pallas kernel of Kimi delta attention (KDA) over a slot's state.

Parity: Kimi Delta Attention (Kimi Linear, arXiv:2510.26692; the
``bailing_hybrid`` layers of Ling-3.0-flash), for the one ``[max_slots,
token_budget]`` step the serving engine compiles. A head keeps no keys: its
cache is a float32 state ``S`` ``[hd_k, hd_v]`` a slot, and a row erases
before it writes (the gated delta rule, a decay a CHANNEL of the key)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

Over a chunk of ``n`` real rows from ``S_0``, with ``G_i = sum_{j<=i} g_j``
(a vector a row)::

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)          (j < i, else 0)
    (I + A) U = Diag(beta) (V - (K * exp(G)) S_0)
    O_i  = S_0^T (q_i * exp(G_i)) + sum_{j<=i} (sum_c q_ic k_jc exp(G_ic - G_jc)) u_j
    S_n  = Diag(exp(G_n)) S_0 + sum_i (k_i * exp(G_n - G_i)) u_i^T

A decay a channel does not factor into a quotient of powers: over 128 rows
``G`` reaches ``128 x lower_bound`` = -640, and ``exp(-G_j)`` is no float32.
Every ``exp`` here is of a DIFFERENCE ``G_i - G_j`` with ``i >= j`` (at most
0), but inside a sub-block of ``SUB`` rows, where the pair is split at the
sub-block's first row: ``exp(G_i - G_ref) exp(G_ref - G_j)`` with the second
exponent at most ``SUB x |lower_bound|`` = 80 < 88, which float32 holds:
that is what the bounded gate is for (``kda_safe_gate``). The triangular
solve runs a sub-block at a time: what earlier sub-blocks give through one
product, then ``SUB`` steps of forward substitution inside it.

One program a (slot, head), three ways through it: a slot with no real row
gets its state back bit for bit; a slot with ONE real row (decode) runs the
recurrence itself on the vector units (the state never goes through a
matrix product); more rows take the chunk form. The state stack ``[L,
slots, H, hd, hd]`` is read and written in place at the layer's index, a
scalar in SMEM beside the frontiers; a slot that begins at position 0
starts from zeros; padded rows (``i >= n``) add nothing to the state.

:func:`dense_kda` is the recurrence row by row in plain ``jax.numpy``: the
path of an engine without kernel injection and the kernel's oracle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
SUB = 16          # rows of a sub-block: SUB x |lower bound| must stay < 88
EXP_CAP = 80.0    # the largest exponent the split form may take
FIRST_LANES = 4   # columns of the first-row operand: q, k, g and a zero


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=F32, precision=precision)


def _kda_kernel(cl_ref, nn_ref, layer_ref, q_ref, k_ref, v_ref, g_ref,
                beta_ref, first_ref, s_ref, o_ref, s_out, *, scale, sub):
    b = pl.program_id(0)
    cl, nn = cl_ref[b], nn_ref[b]
    W, hd = q_ref.shape[1], q_ref.shape[2]
    held = s_ref[0, 0, 0]
    # a request's first chunk starts from nothing, whatever the slot held
    s0 = jnp.where((cl == 0) & (nn > 0), 0.0, held)

    @pl.when(nn == 0)
    def _idle():
        o_ref[0] = jnp.zeros((W, hd), o_ref.dtype)
        s_out[0, 0, 0] = held

    @pl.when(nn == 1)
    def _decode():
        # the recurrence itself: q, k and the decay of the one real row come
        # as COLUMNS [hd, 1] (the key channel indexes the state's rows), v
        # and the output are rows
        cols = first_ref[0, 0]
        q0, k0, g0 = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
        v0 = v_ref[0, 0:1].astype(F32)
        beta0 = beta_ref[0, 0, 0:1]  # [1, 1]
        s1 = s0 * jnp.exp(g0)
        erased = jnp.sum(s1 * k0, axis=0, keepdims=True)  # k^T S  [1, hd]
        s2 = s1 + k0 * (beta0 * (v0 - erased))
        out = jnp.sum(s2 * q0, axis=0, keepdims=True) * scale
        row = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
        o_ref[0] = jnp.where(row == 0, out, 0.0).astype(o_ref.dtype)
        s_out[0, 0, 0] = s2

    @pl.when(nn > 1)
    def _chunk():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        mm = q.dtype  # the type the chunk's own products run in
        prec = _HI if mm == F32 else None
        row = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
        col = lax.broadcasted_iota(jnp.int32, (1, W), 1)
        live = row < nn
        g = jnp.where(live, g_ref[0], 0.0)
        beta = jnp.where(live, beta_ref[0, 0], 0.0)  # [W, 1]
        kf, qf = k.astype(F32), q.astype(F32)
        # G: the running sum of the log-decays, as one triangular product
        G = _dot((col <= row).astype(F32), g, ((1,), (0,)), _HI)
        whole = jnp.exp(G)
        ks = _dot(kf * whole, s0, ((1,), (0,)), _HI)   # (K * exp G) S_0
        qs = _dot(qf * whole, s0, ((1,), (0,)), _HI)
        rhs = beta * (v.astype(F32) - ks)
        u_rows, a_qk = [], []
        for i in range(W // sub):
            lo = i * sub
            sl = slice(lo, lo + sub)
            ref = G[lo:lo + 1] - g[lo:lo + 1]  # G before the sub-block
            # exp(G_ref - G_j): at most 0 before the sub-block, at most
            # EXP_CAP inside it; rows after it are masked below
            grown = (kf * jnp.exp(jnp.minimum(ref - G, EXP_CAP))).astype(mm)
            shrunk = jnp.exp(G[sl] - ref)
            lhs = jnp.concatenate(
                [kf[sl] * shrunk * beta[sl], qf[sl] * shrunk]).astype(mm)
            pairs = _dot(lhs, grown, ((1,), (1,)), prec)  # [2 sub, W]
            a = jnp.where(col < row[sl], pairs[:sub], 0.0)
            a_qk.append(jnp.where(col <= row[sl], pairs[sub:], 0.0))
            # what the sub-blocks before this one give, in one product
            r = rhs[sl]
            if i:  # (rows of U still to come are zeros: whole operands)
                so_far = jnp.concatenate(
                    u_rows + [jnp.zeros((W - lo, hd), F32)])
                r = r - _dot(a.astype(mm), so_far.astype(mm),
                             ((1,), (0,)), prec)
            # A^T of the sub-block itself: [j, i] = A_ij, so a row's
            # coefficients are a COLUMN, and the substitution needs no
            # transpose
            at = _dot(grown[sl], lhs[:sub], ((1,), (1,)), prec)
            srow = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            scol = lax.broadcasted_iota(jnp.int32, (1, sub), 1)
            at = jnp.where(srow < scol, at, 0.0)
            u = jnp.zeros((sub, hd), F32)
            for t in range(sub):
                u_t = r[t:t + 1] - jnp.sum(at[:, t:t + 1] * u, axis=0,
                                           keepdims=True)
                u = jnp.where(srow == t, u_t, u)
            u_rows.append(u)
        u = jnp.concatenate(u_rows)
        intra = _dot(jnp.concatenate(a_qk).astype(mm), u.astype(mm),
                     ((1,), (0,)), prec)
        o_ref[0] = ((qs + intra) * scale).astype(o_ref.dtype)
        # the state after the chunk's REAL rows: G_n is G's last row (a
        # padded row's log-decay is 0); Diag(exp(G_n)) scales the state's
        # ROWS, so its column comes from a product over the rows of g
        last = G[W - 1:W]
        decay = jnp.exp(_dot(g, jnp.ones((W, hd), F32), ((0,), (0,)), _HI))
        add = _dot(kf * jnp.exp(last - G), u, ((0,), (0,)), _HI)
        s_out[0, 0, 0] = decay * s0 + add


def kda_attention(q, k, v, g, beta, state, cache_len, num_new, *, layer,
                  scale: float, interpret: Optional[bool] = None):
    """q/k/v ``[B, S, H, hd]`` of one chunk a slot (q and k unit vectors a
    head), ``g`` float32 ``[B, S, H, hd]`` the log-decay of every key
    channel (in ``[-EXP_CAP / SUB, 0]``), ``beta`` float32 ``[B, S, H]``;
    ``state`` the stack ``[L, B, H, hd, hd]`` float32 and ``layer`` this
    layer's (traced) index in it; ``cache_len`` [B] each slot's position
    before the chunk, ``num_new`` [B] its real rows. Returns (out ``[B, S,
    H, hd]``, the stack with ``[layer]`` advanced in place)."""
    B, S, H, hd = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sub = min(SUB, S)
    assert S % sub == 0, (S, sub)
    # rows by slot with a head's values side by side: a head is a block of
    # lanes, no transpose
    flat = lambda a: a.reshape(B, S, H * hd)
    head_spec = pl.BlockSpec((1, S, hd), lambda b, h, *_: (b, 0, h))
    # the first row's q, k and g as columns, for the one-row recurrence
    first = jnp.stack(
        [q[:, 0].astype(F32), k[:, 0].astype(F32), g[:, 0]]
        + [jnp.zeros((B, H, hd), F32)] * (FIRST_LANES - 3), axis=-1)
    state_spec = pl.BlockSpec(
        (1, 1, 1, hd, hd), lambda b, h, cl, nn, layer: (layer[0], b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, H),
        in_specs=[
            head_spec, head_spec, head_spec, head_spec,
            pl.BlockSpec((1, 1, S, 1), lambda b, h, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, hd, FIRST_LANES),
                         lambda b, h, *_: (b, h, 0, 0)),
            state_spec,
        ],
        out_specs=[head_spec, state_spec],
    )
    out, state = pl.pallas_call(
        functools.partial(_kda_kernel, scale=float(scale), sub=sub),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, S, H * hd), q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the three scalar-prefetch vectors: the stack is 10th
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="kda_attention",
    )(jnp.asarray(cache_len, jnp.int32), jnp.asarray(num_new, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      flat(q), flat(k), flat(v), flat(g.astype(F32)),
      beta.astype(F32).transpose(0, 2, 1)[..., None], first, state)
    return out.reshape(B, S, H, hd), state


def dense_kda(q, k, v, g, beta, state, cache_len, num_new, *, scale: float):
    """The chunk of :func:`kda_attention` as the recurrence itself, a row at
    a time under a scan, float32: ``state`` is ONE layer's ``[B, H, hd,
    hd]``. Returns (out float32 ``[B, S, H, hd]``, the layer's state after
    the real rows)."""
    S = q.shape[1]
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    nn = jnp.asarray(num_new, jnp.int32)
    fresh = ((jnp.asarray(cache_len) == 0) & (nn > 0))[:, None, None, None]
    s0 = jnp.where(fresh, 0.0, state)

    def row(s, t):
        i, qt, kt, vt, gt, bt = t  # [B, H, hd] each, bt [B, H]
        with jax.default_matmul_precision("highest"):
            decayed = s * jnp.exp(gt)[..., None]
            erased = jnp.einsum("bhc,bhce->bhe", kt, decayed)
            after = decayed + kt[..., None] * (
                bt[..., None] * (vt - erased))[:, :, None, :]
            out = jnp.einsum("bhc,bhce->bhe", qt, after) * scale
        real = (i < nn)[:, None, None, None]
        return jnp.where(real, after, s), out

    rows_first = lambda a: jnp.moveaxis(a, 1, 0)
    after, out = lax.scan(row, s0, (jnp.arange(S), *map(
        rows_first, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1), after
