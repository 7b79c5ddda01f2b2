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

One program a (slot, ``hb`` heads), three ways through it, a head at a time
under one loop: a slot with no real row gets its state back bit for bit; a
slot with ONE real row (decode) runs the recurrence itself on the vector
units (the state never goes through a matrix product); more rows take the
chunk form. ``hb`` is the most heads whose buffers fit ``BLOCK_VMEM_BYTES``
(:func:`heads_per_program`), so a program moves ``hb`` states each way and
the grid is ``(slots, H / hb)``. The state stack ``[L, slots, H, hd, hd]``
is read and written in place at the layer's index, a scalar in SMEM beside
the frontiers; a slot that begins at position 0 starts from zeros; padded
rows (``i >= n``) add nothing to the state.

The call moves what a slot holds. Its row operands come twice: as the
``[S, hb x hd]`` blocks the chunk form reads, and as the tile of
``ROW_TILE`` rows that holds row 0, which is all the one-row path reads. A
program of a slot with at most one real row PARKS the big blocks: their
index map reads ``num_new`` and points at the block the nearest slot with a
chunk fetched last (or will fetch first), so consecutive programs ask for
the block that is there and the pipeline copies nothing. The output is two
pieces the same way: ``whole`` ``[slots + 1, S, ...]`` written by a slot with
a chunk (the others park on the spare block ``[slots]``, which nobody
reads), and ``first`` ``[slots, ROW_TILE, ...]``, row 0 and zeros, written by
the others (``ChunkRows.pack_split`` puts them together, by computed row
or by slot).

:func:`dense_kda` is the recurrence row by row in plain ``jax.numpy``: the
path of an engine without kernel injection and the kernel's oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .expert_bank import VMEM_CAP

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
SUB = 16          # rows of a sub-block: SUB x |lower bound| must stay < 88
EXP_CAP = 80.0    # the largest exponent the split form may take
LANES = 128       # a register's lanes: a block of heads is a multiple wide
ROW_TILE = 16     # rows of the tile that holds row 0: a bf16 register's
FIRST_ROWS = 8    # rows of the one-row path's q, k, g before they turn
# what a program's blocks may take, two buffers each: an eighth of its
# siblings' VMEM cap, 16 heads of 128 at a chunk of 128 rows
BLOCK_VMEM_BYTES = VMEM_CAP // 8
# heads of the one-row path laid out side by side: a head's recurrence is one
# chain (decay, erase, write, read), and alone it is longer than its copy
HEADS_TOGETHER = 2


def _head_bytes(hd: int, rows: int, itemsize: int) -> int:
    """A head's blocks, two buffers each: its state in and out, ``rows`` rows
    of q, k, v, o at ``itemsize`` and of g float32."""
    return 2 * (2 * hd * hd * 4 + rows * hd * (4 * itemsize + 4))


def heads_per_program(H: int, hd: int, S: int, itemsize: int) -> int:
    """``hb``: the most heads a program takes, from static shapes alone: the
    largest divisor of ``H`` whose blocks (:func:`_head_bytes` at ``S``
    rows) fit ``BLOCK_VMEM_BYTES`` and whose lanes ``hb x hd`` the chip's
    tiling takes (a lane multiple, or all of them); the narrowest such block
    where none fits."""
    head = _head_bytes(hd, S, itemsize)
    takes = [hb for hb in range(1, H + 1)
             if H % hb == 0 and (hb == H or hb * hd % LANES == 0)]
    fits = [hb for hb in takes if hb * head <= BLOCK_VMEM_BYTES]
    return max(fits) if fits else min(takes)


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=F32, precision=precision)


def _each_head(hb: int, head, together: int = 1):
    """``head(h, carry)`` for the program's ``hb`` heads under ONE loop traced
    once, ``together`` heads a trip laid out side by side where the kernel is
    lowered, so that one head's chain runs under another's."""
    t = math.gcd(together, hb)

    def trip(i, carry):
        return lax.fori_loop(0, t, lambda u, c: head(i * t + u, c), carry,
                             unroll=True)

    lax.fori_loop(0, hb // t, trip, 0)


def _kda_kernel(cl_ref, nn_ref, layer_ref, park_ref, parkj_ref, q_ref, k_ref,
                v_ref, g_ref, q0_ref, k0_ref, v0_ref, g0_ref, beta_ref, s_ref,
                o_ref, o0_ref, s_out, cols_ref, *, scale, sub, hd):
    b, j = pl.program_id(0), pl.program_id(1)
    cl, nn = cl_ref[b], nn_ref[b]
    W, hb = q_ref.shape[1], s_ref.shape[2]
    # a request's first chunk starts from nothing, whatever the slot held
    fresh = (cl == 0) & (nn > 0)
    head_lane = lax.broadcasted_iota(jnp.int32, (1, beta_ref.shape[2]), 1)
    lanes_of = lambda h: pl.ds(pl.multiple_of(h * hd, hd), hd)
    # head h's beta: a column of the rows' [.., H] block, one lane of it
    beta_of = lambda rows, h: jnp.sum(
        jnp.where(head_lane == j * hb + h, rows, 0.0), axis=1, keepdims=True)

    @pl.when(nn != 1)
    def _no_first_row():
        o0_ref[0] = jnp.zeros(o0_ref.shape[1:], o0_ref.dtype)

    @pl.when(nn == 0)
    def _idle():
        s_out[0, 0] = s_ref[0, 0]

    @pl.when(nn == 1)
    def _decode():
        # the recurrence itself: q, k and the decay of the one real row as
        # COLUMNS [hd, 1] (the key channel indexes the state's rows), turned
        # from the row tile for every head of the program at once; v and the
        # output are rows
        cols_ref[...] = jnp.concatenate(
            [q0_ref[0, 0:1].astype(F32), k0_ref[0, 0:1].astype(F32),
             g0_ref[0, 0:1],
             jnp.zeros((FIRST_ROWS - 3, hb * hd), F32)]).T
        row = lax.broadcasted_iota(jnp.int32, (o0_ref.shape[1], 1), 0)

        def head(h, carry):
            at = lanes_of(h)
            cols = cols_ref[at]
            q0, k0, g0 = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
            v0 = v0_ref[0, 0:1, at].astype(F32)
            beta0 = beta_of(beta_ref[0, 0:1], h)  # [1, 1]
            s0 = jnp.where(fresh, 0.0, s_ref[0, 0, h])
            s1 = s0 * jnp.exp(g0)
            erased = jnp.sum(s1 * k0, axis=0, keepdims=True)  # k^T S [1, hd]
            s2 = s1 + k0 * (beta0 * (v0 - erased))
            out = jnp.sum(s2 * q0, axis=0, keepdims=True) * scale
            o0_ref[0, :, at] = jnp.where(row == 0, out, 0.0).astype(
                o0_ref.dtype)
            s_out[0, 0, h] = s2
            return carry

        _each_head(hb, head, HEADS_TOGETHER)

    @pl.when(nn > 1)
    def _chunk():
        row = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
        col = lax.broadcasted_iota(jnp.int32, (1, W), 1)
        live = row < nn

        def head(h, carry):
            at = lanes_of(h)
            q, k, v = q_ref[0, :, at], k_ref[0, :, at], v_ref[0, :, at]
            s0 = jnp.where(fresh, 0.0, s_ref[0, 0, h])
            mm = q.dtype  # the type the chunk's own products run in
            prec = _HI if mm == F32 else None
            g = jnp.where(live, g_ref[0, :, at], 0.0)
            beta = jnp.where(live, beta_of(beta_ref[0], h), 0.0)  # [W, 1]
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
                grown = (kf * jnp.exp(jnp.minimum(ref - G, EXP_CAP))).astype(
                    mm)
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
                at_ = _dot(grown[sl], lhs[:sub], ((1,), (1,)), prec)
                srow = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
                scol = lax.broadcasted_iota(jnp.int32, (1, sub), 1)
                at_ = jnp.where(srow < scol, at_, 0.0)
                u = jnp.zeros((sub, hd), F32)
                for t in range(sub):
                    u_t = r[t:t + 1] - jnp.sum(at_[:, t:t + 1] * u, axis=0,
                                               keepdims=True)
                    u = jnp.where(srow == t, u_t, u)
                u_rows.append(u)
            u = jnp.concatenate(u_rows)
            intra = _dot(jnp.concatenate(a_qk).astype(mm), u.astype(mm),
                         ((1,), (0,)), prec)
            o_ref[0, :, at] = ((qs + intra) * scale).astype(o_ref.dtype)
            # the state after the chunk's REAL rows: G_n is G's last row (a
            # padded row's log-decay is 0); Diag(exp(G_n)) scales the state's
            # ROWS, so its column comes from a product over the rows of g
            last = G[W - 1:W]
            decay = jnp.exp(
                _dot(g, jnp.ones((W, hd), F32), ((0,), (0,)), _HI))
            add = _dot(kf * jnp.exp(last - G), u, ((0,), (0,)), _HI)
            s_out[0, 0, h] = decay * s0 + add
            return carry

        _each_head(hb, head)


def parking(nn, tiles: int):
    """Where a slot without a chunk (``nn <= 1``) parks a call's big blocks:
    (slot, head tile) [B] each: on the last block the nearest slot with a
    chunk before it fetched, else on the first the next one will, so the
    pipeline finds the block it holds or needs next."""
    B = nn.shape[0]
    slot = jnp.arange(B, dtype=jnp.int32)
    chunk = nn > 1
    before = lax.cummax(jnp.where(chunk, slot, -1))
    after = lax.cummin(jnp.where(chunk, slot, B), reverse=True)
    park = jnp.where(before >= 0, before, jnp.where(after < B, after, 0))
    return park, jnp.where(before >= 0, tiles - 1, 0)


def kda_attention(q, k, v, g, beta, state, cache_len, num_new, *, layer,
                  scale: float, interpret: Optional[bool] = None):
    """q/k/v ``[B, S, H, hd]`` of one chunk a slot (q and k unit vectors a
    head), ``g`` float32 ``[B, S, H, hd]`` the log-decay of every key
    channel (in ``[-EXP_CAP / SUB, 0]``), ``beta`` float32 ``[B, S, H]``;
    ``state`` the stack ``[L, B, H, hd, hd]`` float32 and ``layer`` this
    layer's (traced) index in it; ``cache_len`` [B] each slot's position
    before the chunk, ``num_new`` [B] its real rows. Returns the output in
    the two pieces the programs write, a head's values side by side as the
    call holds them, and the stack with ``[layer]`` advanced in place:
    (``whole`` ``[B + 1, S, H x hd]``, slot ``b``'s rows where ``num_new[b]
    > 1`` and nothing anyone may read elsewhere, ``first`` ``[B, min(S,
    ROW_TILE), H x hd]``, row 0 and zeros where ``num_new[b] <= 1``, the
    stack); ``ChunkRows.pack_split`` puts the pieces together."""
    B, S, H, hd = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sub = min(SUB, S)
    assert S % sub == 0, (S, sub)
    hb = heads_per_program(H, hd, S, q.dtype.itemsize)
    tiles, R, wide = H // hb, min(S, ROW_TILE), hb * hd
    nn = jnp.asarray(num_new, jnp.int32)
    park, park_tile = parking(nn, tiles)
    # rows by slot with a head's values side by side: a head is a block of
    # lanes, no transpose
    flat = lambda a: a.reshape(B, S, H * hd)
    q, k, v, g = flat(q), flat(k), flat(v), flat(g.astype(F32))

    def parked(b, j, cl, nn, layer, park, park_tile):
        return park[b], 0, jnp.where(nn[b] > 1, j, park_tile[b])

    def spare(b, j, cl, nn, *_):
        return jnp.where(nn[b] > 1, b, B), 0, jnp.where(nn[b] > 1, j, 0)

    tile_spec = pl.BlockSpec((1, R, wide), lambda b, j, *_: (b, 0, j))
    state_spec = pl.BlockSpec(
        (1, 1, hb, hd, hd),
        lambda b, j, cl, nn, layer, *_: (layer[0], b, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B, tiles),
        in_specs=[pl.BlockSpec((1, S, wide), parked)] * 4
        + [tile_spec] * 4
        + [pl.BlockSpec((1, S, H), lambda b, j, *_: (b, 0, 0)), state_spec],
        out_specs=[pl.BlockSpec((1, S, wide), spare), tile_spec, state_spec],
        scratch_shapes=[pltpu.VMEM((wide, FIRST_ROWS), F32)],
    )
    blocks = hb * _head_bytes(hd, S + R, q.dtype.itemsize)  # the tiles too
    whole, first, state = pl.pallas_call(
        functools.partial(_kda_kernel, scale=float(scale), sub=sub, hd=hd),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B + 1, S, H * hd), q.dtype),
                   jax.ShapeDtypeStruct((B, R, H * hd), q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the five scalar-prefetch vectors: the stack is 15th
        input_output_aliases={14: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(VMEM_CAP, max(32 << 20, 2 * blocks))),
        interpret=interpret, name="kda_attention",
    )(jnp.asarray(cache_len, jnp.int32), nn,
      jnp.asarray(layer, jnp.int32).reshape(1), park, park_tile,
      q, k, v, g, q, k, v, g, beta.astype(F32), state)
    return whole, first, state


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
