"""Pallas kernel of power retention (degree 2) over a slot's state.

Parity: power retention (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239; the layers of Brumby-14B-Base), for the one
``[max_slots, token_budget]`` step the serving engine compiles. A kv head
keeps no keys: with ``G_i`` the running sum of its log-gates ``g <= 0`` a row
attends ``a_ij = exp(G_i - G_j) (s q_i . k_j)^2`` and ``o_i = sum_j a_ij v_j /
(sum_j a_ij + eps)``, which is a recurrence over an EXPANDED state because
``(a . b)^2 = phi_q(a) . phi_k(b)``::

    S_i = e^{g_i} S_{i-1} + v_i phi_k(s k_i)^T     [hd, D] a kv head a slot
    z_i = e^{g_i} z_{i-1} + phi_k(s k_i)           [D]
    o_i = S_i phi_q(q_i) / (z_i . phi_q(q_i) + eps)

``phi`` is the symmetric square, the ``hd (hd + 1) / 2`` products ``a_i a_j``
(``i <= j``) packed into ``hd / 2 + 1`` rows of ``hd`` lanes (:func:`phi`):
row ``r`` holds ``a_r a_j`` at lanes ``j >= r`` and ``a_{hd-r} a_{hd-r+j}``
at lanes ``j < r`` (a lane rotation of ``a`` by ``r``), so ``D = (hd / 2 + 1)
hd`` = 8,320 at 128 with 64 lanes of zeros, never the 16,384 of the plain
outer product. The key's side carries the weights (1 on the squares, 2 on
the mixed products), exact powers of two.

Over a chunk of ``n`` real rows from ``(S, z)``, ``c_i`` the running sum of
``g`` inside the chunk (inclusive)::

    A_ij = exp(c_i - c_j) (s q_i . k_j)^2 [j <= i]
    num  = A V + exp(c_i) phi_q(Q) S^T       den = A 1 + exp(c_i) phi_q(Q) z
    S'   = exp(c_{n-1}) S + sum_j exp(c_{n-1} - c_j) v_j phi_k(s k_j)^T

Every exponent is a difference that is at most 0, taken of the difference.

One program a (slot, kv head, tile of ``D``): a kv head's state is 4.3 MB
at 128 and goes through VMEM WHOLE, two buffers each way, where that fits
``STATE_VMEM_BYTES`` (:func:`tile_rows`: else the most packed rows that divide
``R`` and fit), and ``phi`` of the rows is formed a packed row at a time in
VMEM and never written out (loops over the tile's rows whose index is a
scalar: two lane rotations by it, so the program is a few packed rows long,
not sixty-five). The leaves keep the packed rows as an axis: the state stack
``[L, slots, KV, R, hd, hd]`` (packed row, value channel, lane of the packed
row) and the normaliser ``[L, slots, KV, R, 1, hd]``, ``R = hd / 2 + 1``,
float32, read and written in place at the layer's index, a scalar in SMEM
beside the frontiers. The query heads of a group are stacked along the rows,
so they read the state ONCE. Three ways through a program: a slot with no
real row gets its leaves back bit for bit; ONE real row (decode) runs the
recurrence itself on the vector units, the state never going through a
matrix product; more rows take the chunk form. A slot that begins at
position 0 starts from zeros; padded rows add nothing.

The call is as fast as the chip copies: a stream read and written back
through VMEM runs at 658 GB/s on a v5e whatever the tile and however many
copies are in flight (PERF.md, PR 49), and the decode path keeps its vector
work under that. Its one long chain, a packed row of ``phi`` (a rotation, a
lane broadcast, a product, a select, each waiting for the last), is formed
``PHI_BLOCK`` packed rows side by side before the state is touched; the
state is then walked ``WALK_CHANNELS`` value channels at a time over all the
tile's packed rows, the heads' read-outs carried in registers.

:func:`dense_power_retention` is the same chunk in plain ``jax.numpy``: the
path of an engine without kernel injection and the kernel's oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .expert_bank import VMEM_CAP

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
FIRST_ROWS = 8        # rows of the one-row operand: a group's q heads, k, v
SUBLANES = 8          # rows of a float32 register
PHI_BLOCK = 8         # packed rows of the one row's phi formed side by side
WALK_CHANNELS = 32    # value channels whose read-outs a walk keeps in registers
# the most of its siblings' VMEM cap a tile's buffers take, two in and two out
STATE_VMEM_BYTES = VMEM_CAP // 3


def expanded_dim(hd: int) -> int:
    """``D``: the packed symmetric square of a head of ``hd``."""
    return (hd // 2 + 1) * hd


def _tables(hd: int):
    """(lead, other, weight) ``[hd / 2 + 1, hd]``: packed entry ``[r, j]`` is
    ``a[lead] a[other]``, and the key's side times ``weight`` (0 on the 64
    lanes that hold nothing)."""
    r = np.arange(hd // 2 + 1)[:, None]
    j = np.arange(hd)[None, :]
    upper = j >= r
    lead = np.where(upper, r, (hd - r) % hd)
    other = np.where(upper, j, (hd - r + j) % hd)
    weight = np.where(lead == other, 1.0, 2.0)
    weight = np.where((2 * r == hd) & ~upper, 0.0, weight)
    return lead, other, weight.astype(np.float32)


def phi(a, key: bool = False):
    """``[..., hd]`` -> ``[..., R, hd]`` float32, the packed symmetric
    square; ``sum(phi(a) * phi(b, key=True)) = (a . b)^2``."""
    lead, other, weight = _tables(a.shape[-1])
    a = a.astype(F32)
    return a[..., lead] * a[..., other] * jnp.asarray(
        weight if key else (weight > 0).astype(np.float32))


def tile_rows(hd: int) -> int:
    """Packed rows a program takes: the whole ``hd / 2 + 1`` where a tile's
    four buffers fit ``STATE_VMEM_BYTES``, else the most that divide it and
    fit."""
    rows = hd // 2 + 1
    return max(p for p in range(1, rows + 1) if rows % p == 0 and (
        p == 1 or 4 * p * hd * hd * 4 <= STATE_VMEM_BYTES))


def vmem_limit(per_tile: int, hd: int, group: int, S: int, itemsize: int):
    """The VMEM the call asks for: twice what its buffers take (two of each
    operand's block, the scratches, the chunk form's temporaries the size of
    the query block), at least the 32 MiB its siblings start from and at most
    ``VMEM_CAP``."""
    state = 4 * per_tile * hd * hd * 4
    norm = 4 * per_tile * SUBLANES * hd * 4  # a [1, hd] row pads to a register
    rows = 2 * (2 * group * S + 2 * S + FIRST_ROWS) * hd * itemsize
    scratch = (4 * group * S + group * hd + FIRST_ROWS
               + per_tile * (group + 1) * SUBLANES) * hd * 4
    return min(VMEM_CAP, max(32 << 20, 2 * (state + norm + rows + scratch)))


def _phi_row(a, r, roll):
    """Packed row ``r`` (a traced scalar) of ``phi`` for every row of ``a``
    [rows, hd] float32, the query's side (no weights): two lane rotations,
    each bringing its leading channel to lane 0."""
    hd = a.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (1, hd), 1)
    up = roll(a, hd - r)   # up[:, 0] = a[:, r]
    dn = roll(a, r)        # dn[:, j] = a[:, j - r], dn[:, 0] = a[:, hd - r]
    return jnp.where(lane >= r, up[:, 0:1] * a, jnp.where(
        2 * r != hd, dn[:, 0:1] * dn, 0.0))


def _key_weight(hd: int, r):
    lane = lax.broadcasted_iota(jnp.int32, (1, hd), 1)
    return jnp.where((lane == r) | ((lane == 0) & (r > 0)), 1.0, 2.0)


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=F32, precision=precision)


def _retention_kernel(cl_ref, nn_ref, layer_ref, q_ref, k_ref, v_ref,
                      ccol_ref, crow_ref, first_ref, vcol_ref, s_ref, z_ref,
                      o_ref, s_out, z_out, num_ref, den_ref, acc_ref,
                      dacc_ref, phi_ref, *, scale, eps, group, per_tile,
                      tiles, roll):
    b, t = pl.program_id(0), pl.program_id(2)
    cl, nn = cl_ref[b], nn_ref[b]
    S, hd = k_ref.shape[2], k_ref.shape[3]
    G = group
    scale2 = scale * scale
    base = t * per_tile  # the tile's first packed row
    # a request's first chunk starts from nothing, whatever the slot held
    fresh = (cl == 0) & (nn > 0)
    row = lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    # packed row ``rr`` of the tile, [hd, hd] and [1, hd], as the chunk
    # finds it
    s_at = lambda rr: jnp.where(fresh, 0.0, s_ref[0, 0, 0, rr])
    z_at = lambda rr: jnp.where(fresh, 0.0, z_ref[0, 0, 0, rr])

    @pl.when(nn == 0)
    def _idle():
        def packed_row(rr, carry):
            s_out[0, 0, 0, rr] = s_ref[0, 0, 0, rr]
            return carry

        lax.fori_loop(0, per_tile, packed_row, 0)
        z_out[0, 0, 0] = z_ref[0, 0, 0]
        o_ref[0, 0] = jnp.zeros((G * S, hd), o_ref.dtype)

    @pl.when(nn == 1)
    def _decode():
        # the recurrence itself, the group's q rows, the k row and the v row
        # of the one real token in one tile of FIRST_ROWS rows; v as a COLUMN
        # too (the value channel indexes the state's rows)
        rows = first_ref[0, 0]
        # c_0 = g_0, along the lanes first: a [1, 1] does not broadcast both
        # ways at once
        decay = jnp.exp(jnp.broadcast_to(ccol_ref[0, 0, 0:1], (1, hd)))

        @pl.when(t == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, F32)
            dacc_ref[...] = jnp.zeros(dacc_ref.shape, F32)

        def phi_of(rr, dacc):
            # packed row ``rr`` of the tile: every head's phi and the key's,
            # each broadcast down a register for the walks below, and the
            # normaliser's row, which is one register, while it is here
            r = base + rr
            ph = _phi_row(rows, r, roll)                      # [8, hd]
            pk = ph[G:G + 1] * _key_weight(hd, r)             # [1, hd]
            z0 = z_at(rr)
            z_out[0, 0, 0, rr] = decay * z0 + pk * scale2
            for h in range(G):
                phi_ref[rr, h] = jnp.broadcast_to(ph[h:h + 1], (SUBLANES, hd))
            phi_ref[rr, G] = jnp.broadcast_to(pk, (SUBLANES, hd))
            return dacc + ph * z0

        def phi_block(i, dacc):
            # PHI_BLOCK rows a trip: a row's chain is some 180 cycles long
            # and a trip of one row has nothing to put between its links
            # (traced once; laid out side by side where the kernel is lowered)
            return lax.fori_loop(
                0, PHI_BLOCK, lambda u, d: phi_of(i * PHI_BLOCK + u, d), dacc,
                unroll=True)

        dacc = lax.fori_loop(0, per_tile // PHI_BLOCK, phi_block,
                             dacc_ref[...])
        for rr in range(per_tile - per_tile % PHI_BLOCK, per_tile):
            dacc = phi_of(rr, dacc)
        dacc_ref[...] = dacc

        # the state, ``walk`` value channels at a time over the tile's packed
        # rows: a register of it is read once, gives each head its products
        # (what the state BEFORE the row gives: the row's own share is taken
        # below from q . k itself, as the chunk form takes it) and is written
        # once
        walk = math.gcd(WALK_CHANNELS, hd)
        blocks = walk // SUBLANES
        decay8 = jnp.broadcast_to(decay, (SUBLANES, hd))

        def walk_from(w, carry):
            at = [pl.ds(pl.multiple_of(w * walk + SUBLANES * i, SUBLANES),
                        SUBLANES) for i in range(blocks)]
            vlanes = [jnp.broadcast_to(vcol_ref[0, 0, a] * scale2,
                                       (SUBLANES, hd)) for a in at]

            def packed_row(rr, accs):
                ph = [phi_ref[rr, h] for h in range(G + 1)]
                out = []
                for i, (a, vl) in enumerate(zip(at, vlanes)):
                    s0 = jnp.where(fresh, 0.0, s_ref[0, 0, 0, rr, a, :])
                    s_out[0, 0, 0, rr, a, :] = decay8 * s0 + vl * ph[G]
                    out += [accs[i * G + h] + s0 * ph[h] for h in range(G)]
                return tuple(out)

            accs = lax.fori_loop(0, per_tile, packed_row, tuple(
                jnp.zeros((SUBLANES, hd), F32) for _ in range(G * blocks)))
            for i in range(blocks):
                for h in range(G):
                    acc_ref[pl.ds(pl.multiple_of(
                        h * hd + w * walk + SUBLANES * i, SUBLANES),
                        SUBLANES)] += accs[i * G + h]
            return carry

        # traced once, a walk after the other where the kernel is lowered
        lax.fori_loop(0, hd // walk, walk_from, 0, unroll=True)

        @pl.when(t == tiles - 1)
        def _():
            # row h of num: the sum over the key channels of head h's
            # products, brought from a column to a row by one product
            head = lax.broadcasted_iota(jnp.int32, (FIRST_ROWS, hd), 0)
            num = jnp.zeros((FIRST_ROWS, hd), F32)
            for h in range(G):
                num = num + _dot((head == h).astype(F32),
                                 acc_ref[h * hd:(h + 1) * hd],
                                 ((1,), (1,)), _HI)
            qk = jnp.sum(rows * rows[G:G + 1], axis=1, keepdims=True) * scale
            own = qk * qk                                     # a_ii, no gate
            num = own * rows[G + 1:G + 2] + decay * num
            den = own + decay * jnp.sum(dacc_ref[...], axis=1, keepdims=True)
            out = num / (den + eps)
            for h in range(G):
                o_ref[0, 0, h * S:(h + 1) * S] = jnp.where(
                    row == 0, out[h:h + 1], 0.0).astype(o_ref.dtype)

    @pl.when(nn > 1)
    def _chunk():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        mm = q.dtype  # the type the chunk's own products run in
        prec = _HI if mm == F32 else None
        cc, cr = ccol_ref[0, 0], crow_ref[0, 0]  # [S, 1], [1, S]

        @pl.when(t == 0)
        def _():
            col = lax.broadcasted_iota(jnp.int32, (1, S), 1)
            seen = (col <= row) & (col < nn)
            gate = jnp.where(seen, jnp.exp(jnp.where(seen, cc - cr, 0.0)),
                             0.0)
            for h in range(G):
                qk = _dot(q[h * S:(h + 1) * S], k, ((1,), (1,)), prec) * scale
                a = gate * qk * qk
                num_ref[h * S:(h + 1) * S] = _dot(
                    a.astype(mm), v, ((1,), (0,)), prec)
                den_ref[h * S:(h + 1) * S] = jnp.sum(a, axis=1, keepdims=True)

        qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
        lead = jnp.concatenate([jnp.exp(cc)] * G)  # [G S, 1]: exp(c_i)
        last = cc[S - 1:S]  # c_{n-1}: a padded row's log-gate is 0
        left = jnp.where(row < nn, jnp.exp(last - cc), 0.0) * scale2
        keep = jnp.exp(jnp.broadcast_to(last, (1, hd)))

        def packed_row(rr, carry):
            r = base + rr
            pq = _phi_row(qf, r, roll) * lead                      # [G S, hd]
            pk = _phi_row(kf, r, roll) * _key_weight(hd, r) * left  # [S, hd]
            s0, z0 = s_at(rr), z_at(rr)
            num_ref[...] += _dot(pq, s0, ((1,), (1,)), _HI)
            den_ref[...] += jnp.sum(pq * z0, axis=1, keepdims=True)
            s_out[0, 0, 0, rr] = keep * s0 + _dot(vf, pk, ((0,), (0,)), _HI)
            z_out[0, 0, 0, rr] = keep * z0 + jnp.sum(
                pk, axis=0, keepdims=True)
            return carry

        lax.fori_loop(0, per_tile, packed_row, 0)

        @pl.when(t == tiles - 1)
        def _():
            o_ref[0, 0] = (num_ref[...] / (den_ref[...] + eps)).astype(
                o_ref.dtype)


def kernel_reasons(q, k, interpret: bool) -> Tuple[str, ...]:
    """Why the kernel would decline these operands (() = it takes them)."""
    (_, S, H, hd), KV = q.shape, k.shape[2]
    why = []
    if H % KV or H // KV + 2 > FIRST_ROWS:
        why.append(f"{H} query heads over {KV} kv heads: a group, its key and "
                   f"its value fill the one-row tile of {FIRST_ROWS} rows")
    if hd % SUBLANES:
        why.append(f"head_dim {hd} is no multiple of {SUBLANES}: the state "
                   "is walked a register of value channels at a time")
    if not interpret and (hd % 128 or S % 16):
        why.append(f"head_dim {hd} is no lane multiple or the chunk of {S} "
                   "rows no sublane multiple")
    return tuple(why)


def power_retention(q, k, v, g, state, norm, cache_len, num_new, *, layer,
                    scale: float, eps: float,
                    interpret: Optional[bool] = None):
    """q ``[B, S, H, hd]``, k / v ``[B, S, KV, hd]`` of one chunk a slot
    (``H`` a multiple of ``KV``: query head ``h`` reads kv head ``h // (H /
    KV)``), ``g`` float32 ``[B, S, KV]`` the log-gate of every row (at most
    0); ``state`` the stack ``[L, B, KV, R, hd, hd]`` and ``norm`` ``[L, B,
    KV, R, 1, hd]`` float32 (``R = hd / 2 + 1`` packed rows), ``layer`` this
    layer's (traced) index in them; ``cache_len`` [B] each slot's position
    before the chunk, ``num_new`` [B] its real rows. Returns (out ``[B, S,
    H, hd]``, the two stacks with ``[layer]`` advanced in place)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    why = kernel_reasons(q, k, interpret)
    assert not why, why
    per_tile = tile_rows(hd)
    tiles = (hd // 2 + 1) // per_tile
    nn = jnp.asarray(num_new, jnp.int32)
    # the running sum of the real rows' log-gates, by kv head, as a column
    # and as a row
    c = jnp.cumsum(jnp.where(
        (jnp.arange(S)[None, :] < nn[:, None])[..., None], g.astype(F32), 0.0),
        axis=1).transpose(0, 2, 1)
    # a group's heads stacked along the rows
    qh = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4).reshape(
        B, KV, G * S, hd)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)
    first = jnp.concatenate([
        q[:, 0].reshape(B, KV, G, hd), k[:, 0][:, :, None],
        v[:, 0][:, :, None].astype(q.dtype),
        jnp.zeros((B, KV, FIRST_ROWS - G - 2, hd), q.dtype)],
        axis=2).astype(F32)
    vcol = v[:, 0].astype(F32)[..., None]
    at = lambda *tail: (lambda b, c, t, *_: (b, c, *tail))
    leaf = lambda b, c, t, cl, nn, layer: (layer[0], b, c, t, 0, 0)
    state_spec = pl.BlockSpec((1, 1, 1, per_tile, hd, hd), leaf)
    norm_spec = pl.BlockSpec((1, 1, 1, per_tile, 1, hd), leaf)
    q_spec = pl.BlockSpec((1, 1, G * S, hd), at(0, 0))
    row_spec = pl.BlockSpec((1, 1, S, hd), at(0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, KV, tiles),
        in_specs=[
            q_spec, row_spec, row_spec,
            pl.BlockSpec((1, 1, S, 1), at(0, 0)),
            pl.BlockSpec((1, 1, 1, S), at(0, 0)),
            pl.BlockSpec((1, 1, FIRST_ROWS, hd), at(0, 0)),
            pl.BlockSpec((1, 1, hd, 1), at(0, 0)),
            state_spec, norm_spec,
        ],
        out_specs=[q_spec, state_spec, norm_spec],
        scratch_shapes=[
            pltpu.VMEM((G * S, hd), F32), pltpu.VMEM((G * S, 1), F32),
            pltpu.VMEM((G * hd, hd), F32), pltpu.VMEM((FIRST_ROWS, hd), F32),
            pltpu.VMEM((per_tile, G + 1, SUBLANES, hd), F32),
        ],
    )
    roll = (lambda a, r: jnp.roll(a, r, axis=1)) if interpret else (
        lambda a, r: pltpu.roll(a, r, 1))  # the same rotation, on the chip
    out, state, norm = pl.pallas_call(
        functools.partial(_retention_kernel, scale=float(scale),
                          eps=float(eps), group=G, per_tile=per_tile,
                          tiles=tiles, roll=roll),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, G * S, hd), q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        # operands count the three scalar-prefetch vectors: the stacks are
        # the 11th and the 12th
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(per_tile, hd, G, S,
                                        q.dtype.itemsize)),
        interpret=interpret, name="power_retention",
    )(jnp.asarray(cache_len, jnp.int32), nn,
      jnp.asarray(layer, jnp.int32).reshape(1),
      qh, heads_first(k), heads_first(v), c[..., None], c[:, :, None, :],
      first, vcol, state, norm)
    out = out.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, H, hd), state, norm


def dense_power_retention(q, k, v, g, state, norm, cache_len, num_new, *,
                          scale: float, eps: float):
    """The chunk of :func:`power_retention` by plain lines, float32:
    ``state`` ``[B, KV, R, hd, hd]`` and ``norm`` ``[B, KV, R, 1, hd]`` are
    ONE layer's. Returns (out float32 ``[B, S, H, hd]``, the layer's state and
    normaliser after the real rows)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.astype(F32).reshape(B, S, KV, G, hd)
    k, v, g = (a.astype(F32) for a in (k, v, g))
    nn = jnp.asarray(num_new, jnp.int32)
    live = (nn > 0)[:, None, None, None, None]
    fresh = (jnp.asarray(cache_len) == 0)[:, None, None, None, None] & live
    s0, z0 = jnp.where(fresh, 0.0, state), jnp.where(fresh, 0.0, norm)
    real = jnp.arange(S)[None, :] < nn[:, None]          # [B, S]
    c = jnp.cumsum(jnp.where(real[..., None], g, 0.0), axis=1)  # [B, S, KV]
    row, col = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = ((col <= row)[None] & real[:, None, :])[:, None]  # [B, 1, S, S]
    diff = (c[:, :, None, :] - c[:, None, :, :]).transpose(0, 3, 1, 2)
    gate = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0)
    last = c[:, -1]                                       # [B, KV]
    left = jnp.where(real[..., None], jnp.exp(last[:, None] - c), 0.0)
    with jax.default_matmul_precision("highest"):
        qk = jnp.einsum("bickd,bjcd->bckij", q, k) * scale
        a = gate[:, :, None] * qk * qk                    # [B, KV, G, S, S]
        pq = phi(q) * jnp.exp(c)[..., None, None, None]  # [B, S, KV, G, R, hd]
        pk = phi(k, key=True) * (left * scale * scale)[..., None, None]
        num = (jnp.einsum("bckij,bjce->bicke", a, v)
               + jnp.einsum("bickrd,bcred->bicke", pq, s0))
        den = (jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)
               + jnp.einsum("bickrd,bcrd->bick", pq, z0[:, :, :, 0]))
        keep = jnp.exp(last)[..., None, None, None]
        after = keep * s0 + jnp.einsum("bjce,bjcrd->bcred", v, pk)
        z = keep * z0 + jnp.sum(pk, axis=1)[:, :, :, None]
    out = (num / (den[..., None] + eps)).reshape(B, S, H, hd)
    return out, jnp.where(live, after, state), jnp.where(live, z, norm)
