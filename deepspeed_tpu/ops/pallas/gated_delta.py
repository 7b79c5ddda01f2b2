"""Pallas kernel of the gated delta rule with a SCALAR decay a head (Gated
DeltaNet) over a slot's state.

Parity: Gated Delta Networks (arXiv:2412.06464; the ``linear_attention``
layers of Qwen3-Next), for the one ``[max_slots, token_budget]`` step the
serving engine compiles. ``Hv`` value heads read ``Hk`` key heads, value
heads ``r j .. r j + r - 1`` key head ``j`` (``r = Hv / Hk``); a value head
keeps a float32 state ``S`` ``[dk, dv]`` a slot and a row erases before it
writes, after ONE decay for the whole state::

    S_t = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S_t^T k_t)
    S_t = S_t + k_t u_t^T;   o_t = S_t^T q_t * scale

``g`` is a log-decay a value head a row, at most 0 and NOT bounded below
(``-exp(A_log) softplus(a + dt_bias)``): one row may read -30. Over a chunk
of ``n`` real rows from ``S_0``, with ``gamma_i = sum_{j<=i} g_j`` (a scalar
a row) and ``D_ij = exp(gamma_i - gamma_j)`` for ``j <= i``, else 0::

    A = Diag(beta) (K K^T * D)                      (strictly lower)
    (I + A) U = Diag(beta) (V - Diag(exp gamma) K S_0)
    O   = Diag(exp gamma) Q S_0 + (Q K^T * D) U     (diagonal included)
    S_n = exp(gamma_n) S_0 + sum_i exp(gamma_n - gamma_i) k_i u_i^T

A scalar a row needs no split of the pair (ops/pallas/kda_attention.py
splits a decay a CHANNEL at a sub-block's first row and holds only under
its bound): the decay between two rows is one ``[n, n]`` matrix, every
exponent a difference formed BEFORE ``exp`` and at most 0 (a running sum of
numbers at most 0 never grows), so no magnitude of ``g`` overflows and none
is clamped. ``K K^T`` and ``Q K^T`` are plain products taken ONCE a key
head for the value heads that share it. The triangular solve runs a
sub-block of ``SUB`` rows at a time: what earlier sub-blocks give through
one product, then ``SUB`` steps of forward substitution inside it, the
value heads of a key head side by side. A chunk of more than ``CHUNK`` rows
runs as sub-chunks of ``CHUNK``, the state carried from one to the next
(the wrapper's running sums begin again at each).

One program a (slot, ``kb`` key heads and their value heads), three ways
through it as kda_attention's: a slot with no real row gets its state back
bit for bit; a slot with ONE real row (decode) runs the recurrence itself on
the vector units; more rows take the chunk form. The state stack ``[L,
slots, Hv, dk, dv]`` is read and written in place at the layer's index; a
slot that begins at position 0 starts from zeros; padded rows add nothing.
The parking of a decoding slot's big blocks and the output in two pieces
(``whole``, ``first``: ``ChunkRows.pack_split``) are kda_attention's.

:func:`dense_gated_delta` is the recurrence row by row in plain
``jax.numpy``: the path of an engine without kernel injection and the
kernel's oracle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .expert_bank import VMEM_CAP
from .kda_attention import (BLOCK_VMEM_BYTES, F32, FIRST_ROWS, LANES,
                            ROW_TILE, _HI, _dot, _each_head, parking)

SUB = 16      # rows of a sub-block of the triangular solve
CHUNK = 256   # rows the chunk form takes at once: its [n, n] matrices
NEG = -1e30   # an exponent that reads 0: the pairs a row does not see


def _key_head_bytes(r: int, dk: int, dv: int, rows: int, itemsize: int) -> int:
    """A key head's blocks, two buffers each: its ``r`` value heads' states
    in and out, ``rows`` rows of q, k and of its value heads' v, o."""
    return 2 * (2 * r * dk * dv * 4 + rows * (2 * dk + 2 * r * dv) * itemsize)


def key_heads_per_program(Hk: int, r: int, dk: int, dv: int, S: int,
                          itemsize: int) -> int:
    """``kb``: the most key heads a program takes, from static shapes alone
    (``kda_attention.heads_per_program``'s rule): the largest divisor of
    ``Hk`` whose blocks fit ``BLOCK_VMEM_BYTES`` and whose lanes the chip's
    tiling takes; the narrowest such block where none fits."""
    head = _key_head_bytes(r, dk, dv, S, itemsize)
    takes = [kb for kb in range(1, Hk + 1) if Hk % kb == 0 and (
        kb == Hk or (kb * dk % LANES == 0 and kb * r * dv % LANES == 0))]
    fits = [kb for kb in takes if kb * head <= BLOCK_VMEM_BYTES]
    return max(fits) if fits else min(takes)


def _gdn_kernel(cl_ref, nn_ref, layer_ref, park_ref, parkj_ref, q_ref, k_ref,
                v_ref, q0_ref, k0_ref, v0_ref, gam_ref, gamt_ref, beta_ref,
                betat_ref, s_ref, o_ref, o0_ref, s_out, cols_ref, *, scale,
                r, dk, dv, chunk):
    b, j = pl.program_id(0), pl.program_id(1)
    cl, nn = cl_ref[b], nn_ref[b]
    S, kb = q_ref.shape[1], q_ref.shape[2] // dk
    hb = kb * r  # the program's value heads
    # a request's first chunk starts from nothing, whatever the slot held
    fresh = (cl == 0) & (nn > 0)
    head_lane = lax.broadcasted_iota(jnp.int32, (1, beta_ref.shape[2]), 1)
    head_sub = lax.broadcasted_iota(jnp.int32, (betat_ref.shape[1], 1), 0)
    key_lanes = lambda h: pl.ds(pl.multiple_of(h * dk, dk), dk)
    val_lanes = lambda h: pl.ds(pl.multiple_of(h * dv, dv), dv)
    # value head h's column of a rows' [.., Hv] block / row of a [Hv, ..] one
    col_of = lambda rows, h: jnp.sum(
        jnp.where(head_lane == j * hb + h, rows, 0.0), axis=1, keepdims=True)
    row_of = lambda cols, h: jnp.sum(
        jnp.where(head_sub == j * hb + h, cols, 0.0), axis=0, keepdims=True)

    @pl.when(nn != 1)
    def _no_first_row():
        o0_ref[0] = jnp.zeros(o0_ref.shape[1:], o0_ref.dtype)

    @pl.when(nn == 0)
    def _idle():
        s_out[0, 0] = s_ref[0, 0]

    @pl.when(nn == 1)
    def _decode():
        # the recurrence itself: q and k of the one real row as COLUMNS [dk,
        # 1] (the key channel indexes the state's rows), turned from the row
        # tile for every key head of the program at once; v and the output
        # are rows, the decay and the step size scalars
        cols_ref[...] = jnp.concatenate(
            [q0_ref[0, 0:1].astype(F32), k0_ref[0, 0:1].astype(F32),
             jnp.zeros((FIRST_ROWS - 2, kb * dk), F32)]).T
        row = lax.broadcasted_iota(jnp.int32, (o0_ref.shape[1], 1), 0)

        def head(h, carry):
            cols = cols_ref[key_lanes(h)]
            q0, k0 = cols[:, 0:1], cols[:, 1:2]
            for e in range(r):  # the value heads of key head h, side by side
                hv = h * r + e
                at = val_lanes(hv)
                v0 = v0_ref[0, 0:1, at].astype(F32)
                g0 = col_of(gam_ref[0, 0:1], hv)      # [1, 1]
                beta0 = col_of(beta_ref[0, 0:1], hv)
                s0 = jnp.where(fresh, 0.0, s_ref[0, 0, hv])
                s1 = s0 * jnp.exp(g0)
                erased = jnp.sum(s1 * k0, axis=0, keepdims=True)  # k^T S
                s2 = s1 + k0 * (beta0 * (v0 - erased))
                out = jnp.sum(s2 * q0, axis=0, keepdims=True) * scale
                o0_ref[0, :, at] = jnp.where(row == 0, out, 0.0).astype(
                    o0_ref.dtype)
                s_out[0, 0, hv] = s2
            return carry

        _each_head(kb, head)

    @pl.when(nn > 1)
    def _chunk():
        W = chunk
        sub = min(SUB, W)
        row = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
        col = lax.broadcasted_iota(jnp.int32, (1, W), 1)
        srow = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        scol = lax.broadcasted_iota(jnp.int32, (1, sub), 1)

        def head(h, carry):
            mm = q_ref.dtype  # the type the chunk's own products run in
            prec = _HI if mm == F32 else None
            states = [jnp.where(fresh, 0.0, s_ref[0, 0, h * r + e])
                      for e in range(r)]
            for c in range(S // W):  # sub-chunks, the state carried on
                rows = slice(c * W, (c + 1) * W)
                q, k = q_ref[0, rows, key_lanes(h)], k_ref[0, rows,
                                                           key_lanes(h)]
                kf, qf = k.astype(F32), q.astype(F32)
                # once a key head, for every value head that reads it
                kk = _dot(k, k, ((1,), (1,)), prec)   # [W, W], symmetric
                qk = _dot(q, k, ((1,), (1,)), prec)
                per = []  # a value head's operands of the solve
                for e in range(r):
                    hv = h * r + e
                    s0 = states[e]
                    gcol = col_of(gam_ref[0, rows], hv)          # [W, 1]
                    grow = row_of(gamt_ref[0, :, rows], hv)      # [1, W]
                    bcol = col_of(beta_ref[0, rows], hv)
                    brow = row_of(betat_ref[0, :, rows], hv)
                    # D: every exponent a difference at most 0
                    decay = jnp.exp(jnp.where(col <= row, gcol - grow, NEG))
                    a = jnp.where(col < row, bcol * kk * decay, 0.0)
                    whole = jnp.exp(gcol)
                    ks = _dot(kf, s0, ((1,), (0,)), _HI)         # K S_0
                    qs = _dot(qf, s0, ((1,), (0,)), _HI)
                    v = v_ref[0, rows, val_lanes(hv)].astype(F32)
                    per.append(dict(
                        s0=s0, gcol=gcol, grow=grow, brow=brow, a=a,
                        aqk=qk * decay, out0=whole * qs,
                        rhs=bcol * (v - whole * ks), u=[]))
                for i in range(W // sub):
                    lo = i * sub
                    sl = slice(lo, lo + sub)
                    solving = []
                    for p in per:
                        x = p["rhs"][sl]
                        if i:  # what the sub-blocks before this one give
                            so_far = jnp.concatenate(
                                p["u"] + [jnp.zeros((W - lo, dv), F32)])
                            x = x - _dot(p["a"][sl].astype(mm),
                                         so_far.astype(mm), ((1,), (0,)),
                                         prec)
                        # A^T of the sub-block itself ([j, i] = A_ij: K K^T
                        # is symmetric), so a row's coefficients are a
                        # COLUMN and the substitution needs no transpose
                        at_ = jnp.where(
                            srow < scol,
                            p["brow"][:, sl] * kk[sl, sl] * jnp.exp(
                                jnp.minimum(p["grow"][:, sl] - p["gcol"][sl],
                                            0.0)), 0.0)
                        solving.append((x, at_, jnp.zeros((sub, dv), F32)))
                    for t in range(sub):  # the value heads' chains together
                        solving = [
                            (x, at_, jnp.where(
                                srow == t,
                                x[t:t + 1] - jnp.sum(at_[:, t:t + 1] * u,
                                                     axis=0, keepdims=True),
                                u))
                            for x, at_, u in solving]
                    for p, (_, _, u) in zip(per, solving):
                        p["u"].append(u)
                for e, p in enumerate(per):
                    hv = h * r + e
                    u = jnp.concatenate(p["u"])
                    intra = _dot(p["aqk"].astype(mm), u.astype(mm),
                                 ((1,), (0,)), prec)
                    o_ref[0, rows, val_lanes(hv)] = (
                        (p["out0"] + intra) * scale).astype(o_ref.dtype)
                    # the state after the sub-chunk's REAL rows: gamma_n is
                    # gamma's last row (a padded row's log-decay is 0)
                    last = p["gcol"][W - 1:W]
                    add = _dot(kf * jnp.exp(last - p["gcol"]), u,
                               ((0,), (0,)), _HI)
                    states[e] = jnp.exp(last) * p["s0"] + add
            for e in range(r):
                s_out[0, 0, h * r + e] = states[e]
            return carry

        _each_head(kb, head)


def gated_delta_attention(q, k, v, g, beta, state, cache_len, num_new, *,
                          layer, scale: float,
                          interpret: Optional[bool] = None):
    """q / k ``[B, S, Hk, dk]`` of one chunk a slot (unit vectors a head), v
    ``[B, S, Hv, dv]``, ``g`` float32 ``[B, S, Hv]`` the log-decay of every
    value head (at most 0, no lower bound), ``beta`` float32 ``[B, S, Hv]``;
    ``state`` the stack ``[L, B, Hv, dk, dv]`` float32 and ``layer`` this
    layer's (traced) index in it; ``cache_len`` [B] each slot's position
    before the chunk, ``num_new`` [B] its real rows. Returns the output in
    the two pieces the programs write, a head's values side by side, and
    the stack with ``[layer]`` advanced in place: (``whole`` ``[B + 1, S, Hv
    x dv]``, slot ``b``'s rows where ``num_new[b] > 1`` and nothing anyone
    may read elsewhere, ``first`` ``[B, min(S, ROW_TILE), Hv x dv]``, row 0
    and zeros where ``num_new[b] <= 1``, the stack);
    ``ChunkRows.pack_split`` puts the pieces together."""
    B, S, Hk, dk = q.shape
    Hv, dv = v.shape[2:]
    r = Hv // Hk
    assert Hv == Hk * r and state.shape[1:] == (B, Hv, dk, dv), (
        q.shape, v.shape, state.shape)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    W = min(S, CHUNK)
    assert S % W == 0 and W % min(SUB, W) == 0, (S, W)
    kb = key_heads_per_program(Hk, r, dk, dv, S, q.dtype.itemsize)
    tiles, R = Hk // kb, min(S, ROW_TILE)
    kwide, vwide = kb * dk, kb * r * dv
    nn = jnp.asarray(num_new, jnp.int32)
    park, park_tile = parking(nn, tiles)
    # a padded row decays nothing and writes nothing; the running sum of the
    # log-decays begins again at every sub-chunk (a sum of numbers at most 0
    # never grows, so every later difference is at most 0)
    live = (jnp.arange(S, dtype=jnp.int32)[None, :] < nn[:, None])[..., None]
    gamma = jnp.cumsum(
        jnp.where(live, g.astype(F32), 0.0).reshape(B, S // W, W, Hv),
        axis=2).reshape(B, S, Hv)
    beta = jnp.where(live, beta.astype(F32), 0.0)
    turned = lambda a: jnp.swapaxes(a, 1, 2)  # [B, Hv, S]: a head's ROW
    # rows by slot with a head's values side by side: a head is a block of
    # lanes, no transpose
    q, k, v = (a.reshape(B, S, -1) for a in (q, k, v))

    def parked(b, j, cl, nn, layer, park, park_tile):
        return park[b], 0, jnp.where(nn[b] > 1, j, park_tile[b])

    def spare(b, j, cl, nn, *_):
        return jnp.where(nn[b] > 1, b, B), 0, jnp.where(nn[b] > 1, j, 0)

    tile = lambda wide: pl.BlockSpec((1, R, wide), lambda b, j, *_: (b, 0, j))
    by_row = pl.BlockSpec((1, S, Hv), lambda b, j, *_: (b, 0, 0))
    by_head = pl.BlockSpec((1, Hv, S), lambda b, j, *_: (b, 0, 0))
    state_spec = pl.BlockSpec(
        (1, 1, kb * r, dk, dv),
        lambda b, j, cl, nn, layer, *_: (layer[0], b, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B, tiles),
        in_specs=[pl.BlockSpec((1, S, kwide), parked)] * 2
        + [pl.BlockSpec((1, S, vwide), parked)]
        + [tile(kwide)] * 2 + [tile(vwide)]
        + [by_row, by_head, by_row, by_head, state_spec],
        out_specs=[pl.BlockSpec((1, S, vwide), spare), tile(vwide),
                   state_spec],
        scratch_shapes=[pltpu.VMEM((kwide, FIRST_ROWS), F32)],
    )
    blocks = kb * _key_head_bytes(r, dk, dv, S + R, q.dtype.itemsize)
    temps = 16 * W * W * 4  # the chunk form's [W, W] matrices, two heads'
    whole, first, state = pl.pallas_call(
        functools.partial(_gdn_kernel, scale=float(scale), r=r, dk=dk, dv=dv,
                          chunk=W),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B + 1, S, Hv * dv), q.dtype),
                   jax.ShapeDtypeStruct((B, R, Hv * dv), q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the five scalar-prefetch vectors: the stack is 16th
        input_output_aliases={15: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(VMEM_CAP,
                                 max(32 << 20, 2 * blocks + temps))),
        interpret=interpret, name="gated_delta_attention",
    )(jnp.asarray(cache_len, jnp.int32), nn,
      jnp.asarray(layer, jnp.int32).reshape(1), park, park_tile,
      q, k, v, q, k, v, gamma, turned(gamma), beta, turned(beta), state)
    return whole, first, state


def dense_gated_delta(q, k, v, g, beta, state, cache_len, num_new, *,
                      scale: float):
    """The chunk of :func:`gated_delta_attention` as the recurrence itself,
    a row at a time under a scan, float32: ``state`` is ONE layer's ``[B,
    Hv, dk, dv]``. Returns (out float32 ``[B, S, Hv, dv]``, the layer's
    state after the real rows)."""
    S, r = q.shape[1], v.shape[2] // q.shape[2]
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    # value heads r j .. r j + r - 1 read key head j
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    nn = jnp.asarray(num_new, jnp.int32)
    fresh = ((jnp.asarray(cache_len) == 0) & (nn > 0))[:, None, None, None]
    s0 = jnp.where(fresh, 0.0, state)

    def row(s, t):
        i, qt, kt, vt, gt, bt = t  # [B, Hv, d] each, gt / bt [B, Hv]
        with jax.default_matmul_precision("highest"):
            decayed = s * jnp.exp(gt)[..., None, None]
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
