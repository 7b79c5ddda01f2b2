"""Pallas kernels of block-sparse attention over a paged GQA cache.

Parity: MiniCPM4's / MiniCPM-SALA's ``minicpm4`` layers (InfLLM-v2: a query
scores mean-pooled "compressed" keys, its kv group keeps the ``topk`` best
``block_size``-token blocks, and every head of the group attends them), for
the one ``[max_slots, token_budget]`` step the serving engine compiles.

What a sparse layer caches: K and V pages ``[L, P+1, page_size, KV, hd]``
exactly as a dense GQA layer does, and one compressed key a page a kv head,
``kc [L, P+1, KV, hd]`` on the same page table: ``Kc_j = mean(K[s j : s j +
kernel_size])`` with stride ``s`` = the page size, written once the last of
its tokens is (:func:`write_compressed_keys`, XLA lines).

Two calls a layer, each named in a device trace:

``block_select``  For a tile of query rows of one kv group: ``p^h =
    softmax_j(q_h . Kc_j)`` over the compressed keys whose tokens all lie at
    or before the row, summed over the group's heads, a block's score the
    max over the kernels that overlap it; the first ``init_blocks`` blocks
    and those of the last ``window_size`` tokens forced; the ``topk`` best
    kept (a threshold found bit by bit over order-preserving integers; blocks
    that share a compressed key tie, and the lower block wins). A row at a position inside ``dense_len`` keeps every block. Two
    passes over the slot's compressed keys (the softmax's sum, then the
    probabilities), their trip counts following the row's position. Out:
    ``[B, KV, chunks, S, 128]`` float32, 1.0 at a kept block.
``block_sparse_attention``  Softmax attention of the group's heads over the
    kept blocks, causal inside them. Like ``sparse_latent_attention`` it
    walks every key block of the slot's context and masks what was not
    kept: its work follows the context, not ``topk`` (PERF.md says what
    that costs). A page comes in whole (both kv heads), as the paged
    kernel fetches it.

The ``dense_*`` functions are the same steps in plain ``jax.numpy`` over a
gathered per-slot view: the path of an engine without kernel injection and
the oracle of the kernels' tests.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import LANES, NEG_INF, _normalized, _tile_update
from .flash_attention import _lanes_to
from .paged_attention import (DEFAULT_BLOCK_K, SMEM_TABLE_BYTES,
                              VMEM_LIMIT_BYTES, _block_pages, _frontiers,
                              _head_tiles)
from .sparse_latent_attention import INT_MIN, _sort_key

F32 = jnp.float32
SELECT_ROWS = 16     # query rows a program of the selection kernel
SELECT_BLOCKS = 256  # blocks a loop trip of its two passes
ATTN_ROWS = 16       # query rows a program of the attention kernel
FORCED = 3.0e38      # the score of a block that is always kept


@dataclass(frozen=True)
class BlockSparse:
    """The sizes of a block selection (MiniCPM4's ``sparse_config``)."""

    kernel_size: int = 32     # tokens a compressed key is the mean of
    kernel_stride: int = 16   # tokens between two compressed keys
    block_size: int = 64      # tokens a selected block holds
    topk: int = 64            # blocks kept, the forced ones among them
    init_blocks: int = 1      # leading blocks always kept
    window_size: int = 2048   # trailing tokens whose blocks are always kept
    dense_len: int = 8192     # a query at a position inside it keeps all

    def __post_init__(self):
        s = self.kernel_stride
        if self.kernel_size % s or self.block_size % s or s < 1:
            raise ValueError(
                f"kernel_size {self.kernel_size} and block_size "
                f"{self.block_size} are whole strides of {s}")

    @property
    def per_block(self) -> int:
        """Compressed keys that start inside a block."""
        return self.block_size // self.kernel_stride

    @property
    def reach(self) -> int:
        """Compressed keys before a block's own that still overlap it."""
        return self.kernel_size // self.kernel_stride - 1

    @property
    def planes(self) -> int:
        """Compressed keys that overlap a block."""
        return self.per_block + self.reach

    def keys_before(self, pos):
        """Compressed keys whose tokens all lie at or before ``pos``."""
        whole = pos + 1 - self.kernel_size
        return jnp.where(
            whole >= 0, jnp.maximum(whole, 0) // self.kernel_stride + 1, 0)

    def blocks(self, capacity: int) -> int:
        return -(-capacity // self.block_size)


# ------------------------------------------------------- compressed keys
def write_compressed_keys(kc_pool, k_pool, layer, cache_len, num_new,
                          page_table, geom: BlockSparse, chunk: int):
    """The compressed keys a chunk of ``chunk`` rows completed, written in
    place into ``kc_pool`` [L, P+1, KV, hd] at ``[layer, page of their first
    token]``: key ``j`` is complete when token ``s j + kernel_size - 1`` is
    a real row of this chunk; its tokens (this chunk's already scattered)
    are read back from ``k_pool`` through the table and the mean is taken in
    float32. Candidates that are not complete land in the NULL page."""
    ps, mp = k_pool.shape[2], page_table.shape[1]
    span = geom.kernel_size // geom.kernel_stride  # pages a key is the mean of
    cl = jnp.asarray(cache_len, jnp.int32)[:, None]
    nn = jnp.asarray(num_new, jnp.int32)[:, None]
    # from the first key whose last token is at or past the chunk's first row
    first = jnp.maximum(cl - geom.kernel_size + ps, 0) // ps
    j = first + jnp.arange(chunk // ps + 1, dtype=jnp.int32)[None, :]
    last = j * ps + geom.kernel_size - 1
    done = (last >= cl) & (last < cl + nn)
    pages = jnp.clip(j[:, :, None] + jnp.arange(span)[None, None, :], 0,
                     mp - 1)
    B = page_table.shape[0]
    phys = jnp.take_along_axis(
        page_table, pages.reshape(B, -1), axis=1).reshape(pages.shape)
    # (one gather out of the stack: a layer sliced out first is a copy)
    mean = k_pool[layer, phys].astype(F32).mean(axis=(2, 3))  # [B,n,KV,hd]
    dst = jnp.where(done, phys[:, :, 0], kc_pool.shape[1] - 1)
    return kc_pool.at[layer, dst].set(mean.astype(kc_pool.dtype))


def plane_view(kc_pool, layer, page_table, geom: BlockSparse, blocks: int):
    """The slot's compressed keys as the selection reads them: ``[B, KV,
    planes, blocks, hd]``, plane ``r`` of block ``m`` the key ``per_block m
    - reach + r`` (a gather through the table; a key before the first or
    past the table reads some page and is masked by its index)."""
    mp = page_table.shape[1]
    j = (geom.per_block * jnp.arange(blocks)[None, :] - geom.reach
         + jnp.arange(geom.planes)[:, None])  # [planes, blocks]
    phys = page_table[:, jnp.clip(j, 0, mp - 1)]  # [B, planes, blocks]
    # (one gather out of the stack: a layer sliced out first is a copy)
    return kc_pool[layer, phys].transpose(0, 3, 1, 2, 4)


def _padded_blocks(geom: BlockSparse, capacity: int) -> int:
    """Blocks of a slot's capacity, in whole trips of the selection."""
    nb = geom.blocks(capacity)
    trip = min(SELECT_BLOCKS, -(-nb // LANES) * LANES)
    return -(-nb // trip) * trip


# ------------------------------------------------------------- selection
def _block_select_kernel(cl_ref, nn_ref, q_ref, kc_ref, sel_ref, m_scr,
                         l_scr, key_scr, *, geom, group, rows, trip, scale,
                         pos_bits):
    b, t = pl.program_id(0), pl.program_id(2)
    cl, nn = cl_ref[b], nn_ref[b]
    r0 = t * rows
    trips = key_scr.shape[0]
    GR = group * rows
    sel_ref[...] = jnp.zeros_like(sel_ref)

    @pl.when(r0 < nn)
    def _select():
        B_, R, reach = geom.block_size, geom.per_block, geom.reach
        # the tile's last real row decides how far the passes go
        last = cl + jnp.minimum(nn, r0 + rows) - 1
        n_trips = jnp.minimum(pl.cdiv(last // B_ + 1, trip), trips)
        # head-major stack: row r of [group * rows] is query r % rows
        qrow = lax.rem(lax.broadcasted_iota(jnp.int32, (GR, 1), 0), rows)
        nk = geom.keys_before(cl + r0 + qrow)  # [GR, 1]
        q = q_ref[0, 0, 0]

        def scores(i, r):
            kc = kc_ref[0, 0, r, pl.ds(pl.multiple_of(i * trip, trip), trip), :]
            s = lax.dot_general(q, kc.astype(q.dtype),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
            m = i * trip + lax.broadcasted_iota(jnp.int32, (1, trip), 1)
            j = R * m - reach + r
            return s, (j >= 0) & (j < nk)

        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

        def sums(i, c):  # every key once: a block's own planes
            for r in range(reach, geom.planes):
                s, ok = scores(i, r)
                s = jnp.where(ok, s, NEG_INF)
                m_prev = m_scr[...]  # [GR, LANES], lane-replicated
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
                p = jnp.where(ok, jnp.exp(s - _lanes_to(m_safe, trip)), 0.0)
                l_scr[...] = (l_scr[...] * jnp.exp(m_prev - m_safe)
                              + jnp.sum(p, axis=1, keepdims=True))
                m_scr[...] = m_new
            return c

        lax.fori_loop(0, n_trips, sums, 0)
        m_fin = m_scr[...]
        m_fin = _lanes_to(jnp.where(m_fin <= NEG_INF, 0.0, m_fin), trip)
        l_fin = l_scr[...]
        inv = _lanes_to(1.0 / jnp.where(l_fin == 0.0, 1.0, l_fin), trip)
        pos = cl + r0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        first_kept = jnp.maximum(pos + 1 - geom.window_size, 0) // B_

        def keys(i, c):
            best = jnp.zeros((rows, trip), F32)
            for r in range(geom.planes):
                s, ok = scores(i, r)
                p = jnp.where(ok, jnp.exp(s - m_fin) * inv, 0.0)
                both = p[0:rows]
                for h in range(1, group):  # the group's heads, summed
                    both = both + p[h * rows:(h + 1) * rows]
                best = jnp.maximum(best, both)
            m = i * trip + lax.broadcasted_iota(jnp.int32, (1, trip), 1)
            forced = (m < geom.init_blocks) | (m >= first_kept)
            key_scr[i] = jnp.where(
                m <= pos // B_,
                _sort_key(jnp.where(forced, FORCED, best)), INT_MIN)
            return c

        lax.fori_loop(0, n_trips, keys, 0)

        def count(pred):
            def one(i, acc):
                m = i * trip + lax.broadcasted_iota(jnp.int32, (1, trip), 1)
                return acc + jnp.sum(pred(key_scr[i], m).astype(jnp.int32),
                                     axis=1, keepdims=True)

            return lax.fori_loop(0, n_trips, one,
                                 jnp.zeros((rows, 1), jnp.int32))

        # the topk-th largest key: the largest T with count(key >= T) >= topk
        topk = geom.topk
        lo = jnp.where(count(lambda k, m: k >= 0) >= topk, 0, INT_MIN)

        def bit(i, lo):
            cand = lo + jnp.left_shift(jnp.int32(1), 30 - i)
            return jnp.where(count(lambda k, m: k >= cand) >= topk, cand, lo)

        thr = lax.fori_loop(0, 31, bit, lo)
        # blocks that share a compressed key tie: the lower blocks win, up
        # to the largest p with count(tie, block < p) < what is left to keep
        need = topk - count(lambda k, m: k > thr)

        def pos_bit(i, at):
            cand = at + jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
            n = count(lambda k, m: (k == thr) & (m < cand))
            return jnp.where(n < need, cand, at)

        tie = lax.fori_loop(0, pos_bits, pos_bit,
                            jnp.zeros((rows, 1), jnp.int32))
        # every block at or before the row (a block past it has INT_MIN):
        # inside dense_len, or where no more than topk are
        every = (pos + 1 <= geom.dense_len) | (
            count(lambda k, m: k > INT_MIN) <= topk)
        real = r0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < nn
        per = trip // LANES

        def keep(i, c):
            key = key_scr[i]
            m = i * trip + lax.broadcasted_iota(jnp.int32, (1, trip), 1)
            best = (key > thr) | ((key == thr) & (m <= tie))
            # (no select between masks: the chip's compiler has none)
            best = (every & (key > INT_MIN)) | (~every & best)
            kept = jnp.where(real & best, 1.0, 0.0)
            for c_ in range(per):
                sel_ref[0, 0, i * per + c_] = kept[:, c_ * LANES:
                                                   (c_ + 1) * LANES]
            return c

        lax.fori_loop(0, n_trips, keep, 0)


def block_select(q, kc_planes, cache_len, num_new, geom: BlockSparse, *,
                 interpret: Optional[bool] = None):
    """The kept blocks of every real row: ``q`` [B,S,H,hd] (normed, as
    attention takes them), ``kc_planes`` [B,KV,planes,blocks,hd]
    (:func:`plane_view`, ``blocks`` in whole trips). Returns float32
    ``[B, KV, blocks / 128, S, 128]``: 1.0 where row ``i``'s group keeps
    block ``128 c + l``; rows past ``num_new`` keep nothing."""
    B, S, H, hd = q.shape
    KV, NBp = kc_planes.shape[1], kc_planes.shape[3]
    G = H // KV
    rows = min(SELECT_ROWS, S)
    trip = min(SELECT_BLOCKS, NBp)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cl, nn = _frontiers(B, S, cache_len, num_new)
    # a tile's queries head-major: [KV, tiles, G * rows, hd]
    qt = q.reshape(B, S // rows, rows, KV, G, hd).transpose(
        0, 3, 1, 4, 2, 5).reshape(B, KV, S // rows, G * rows, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, KV, S // rows),
        in_specs=[
            pl.BlockSpec((1, 1, 1, G * rows, hd),
                         lambda b, g, t, *_: (b, g, t, 0, 0)),
            pl.BlockSpec((1, 1, geom.planes, NBp, hd),
                         lambda b, g, t, *_: (b, g, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, NBp // LANES, rows, LANES),
                               lambda b, g, t, *_: (b, g, 0, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((G * rows, LANES), F32),
            pltpu.VMEM((G * rows, LANES), F32),
            pltpu.VMEM((NBp // trip, rows, trip), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_block_select_kernel, geom=geom, group=G,
                          rows=rows, trip=trip, scale=hd ** -0.5,
                          pos_bits=NBp.bit_length()),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, NBp // LANES, S, LANES), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="block_select",
    )(cl, nn, qt, kc_planes)


# ------------------------------------------------------------- attention
def _attention_trip(page_size: int, max_pages: int, geom: BlockSparse) -> int:
    """Pages a loop trip of the attention kernel fetches: the paged
    kernel's, in whole selected blocks."""
    per = geom.block_size // page_size
    return _block_pages(DEFAULT_BLOCK_K, page_size, max_pages) // per * per


def _block_sparse_kernel(pt_ref, cl_ref, nn_ref, layer_ref, q_ref, sel_ref,
                         k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, kh_scr,
                         vh_scr, m_scr, l_scr, acc_scr, *, scale, page_size,
                         pages_per_block, group, rows, block_size):
    KV = q_ref.shape[1]
    ps, ppb = page_size, pages_per_block
    bk = ps * ppb
    per_trip = bk // block_size  # selected blocks a trip spans
    mp = pt_ref.shape[1]
    b, t = pl.program_id(0), pl.program_id(1)
    cl, nn, layer = cl_ref[b], nn_ref[b], layer_ref[0]
    r0 = t * rows
    RG = rows * group

    def page_copies(slot, j, page):
        dst = pl.ds(j * ps, ps)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[slot, dst], sems.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[slot, dst], sems.at[1, slot]),
        )

    def start_fetch(blk, slot):
        def one(j, carry):
            page = pt_ref[b, jnp.minimum(blk * ppb + j, mp - 1)]
            for c in page_copies(slot, j, page):
                c.start()
            return carry

        lax.fori_loop(0, ppb, one, 0)

    def wait_fetch(slot):
        def one(j, carry):
            for c in page_copies(slot, j, 0):
                c.wait()
            return carry

        lax.fori_loop(0, ppb, one, 0)

    @pl.when(r0 >= nn)
    def _padding():  # rows no token stands in: keep them finite
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(r0 < nn)
    def _attend():
        n_blocks = jnp.minimum(
            pl.cdiv(cl + jnp.minimum(nn, r0 + rows), bk),
            pl.cdiv(mp * ps, bk))
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # row r of the [rows * group, hd] stack is query r // group
        frontier = cl + r0 + lax.broadcasted_iota(
            jnp.int32, (RG, bk), 0) // group
        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        in_trip = lax.broadcasted_iota(jnp.int32, (1, bk), 1) // block_size
        start_fetch(0, 0)

        def block(i, carry):
            slot = lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_fetch(i + 1, 1 - slot)

            wait_fetch(slot)
            for kv, tile in _head_tiles(k_buf.at[slot], KV):
                kh_scr[kv] = tile
            for kv, tile in _head_tiles(v_buf.at[slot], KV):
                vh_scr[kv] = tile
            first = i * per_trip  # this trip's first selected block
            at = lax.rem(first, LANES)

            def head(kv, c):
                # the trip's ``per_trip`` flags of every row, one lane each
                # of the chunk that holds them, spread over their keys
                chunk = sel_ref[0, kv, first // LANES]  # [rows, 128]
                kept = jnp.zeros((rows, bk), F32)
                for m in range(per_trip):
                    flag = jnp.sum(jnp.where(lane == at + m, chunk, 0.0),
                                   axis=1, keepdims=True)
                    kept = jnp.where(in_trip == m, flag, kept)
                allowed = jnp.broadcast_to(
                    (kept > 0.5)[:, None, :], (rows, group, bk)
                ).reshape(RG, bk)
                _tile_update(
                    q_ref[0, kv], kh_scr[kv], vh_scr[kv], None, None, i * bk,
                    frontier, scale, m_scr.at[kv], l_scr.at[kv],
                    acc_scr.at[kv], allowed=allowed)
                return c

            lax.fori_loop(0, KV, head, 0)
            return carry

        lax.fori_loop(0, n_blocks, block, 0)

        def finish(kv, c):
            o_ref[0, kv] = _normalized(
                l_scr.at[kv], acc_scr.at[kv]).astype(o_ref.dtype)
            return c

        lax.fori_loop(0, KV, finish, 0)


def block_sparse_attention(q, k_pool, v_pool, kept, cache_len, page_table, *,
                           layer, geom: BlockSparse, num_new=None,
                           interpret: Optional[bool] = None):
    """``q`` [B,S,H,hd] against layer ``layer`` of the page pools
    ``[L, P+1, page_size, KV, hd]`` through ``page_table``, each row over
    the blocks ``kept`` (:func:`block_select`) gives its kv group, causal
    inside them. Returns [B,S,H,hd]; a tile of rows wholly past ``num_new``
    is zeros."""
    B, S, H, hd = q.shape
    ps, KV = k_pool.shape[2], k_pool.shape[3]
    mp = page_table.shape[1]
    G = H // KV
    rows = min(ATTN_ROWS, S)
    ppb = _attention_trip(ps, mp, geom)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cl, nn = _frontiers(B, S, cache_len, num_new)
    qg = q.reshape(B, S, KV, G, hd).swapaxes(1, 2).reshape(B, KV, S * G, hd)
    chunks = kept.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B, S // rows),
        in_specs=[
            pl.BlockSpec((1, KV, rows * G, hd), lambda b, t, *_: (b, 0, t, 0)),
            pl.BlockSpec((1, KV, chunks, rows, LANES),
                         lambda b, t, *_: (b, 0, 0, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KV, rows * G, hd),
                               lambda b, t, *_: (b, 0, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * ps, KV, hd), k_pool.dtype),
            pltpu.VMEM((2, ppb * ps, KV, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, ppb * ps, hd), k_pool.dtype),
            pltpu.VMEM((KV, ppb * ps, hd), v_pool.dtype),
            pltpu.VMEM((KV, rows * G, LANES), F32),
            pltpu.VMEM((KV, rows * G, LANES), F32),
            pltpu.VMEM((KV, rows * G, hd), F32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _block_sparse_kernel, scale=hd ** -0.5, page_size=ps,
            pages_per_block=ppb, group=G, rows=rows,
            block_size=geom.block_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, S * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="block_sparse_attention",
    )(jnp.asarray(page_table, jnp.int32), cl, nn,
      jnp.asarray(layer, jnp.int32).reshape(1), qg, kept, k_pool, v_pool)
    return out.reshape(B, KV, S, G, hd).swapaxes(1, 2).reshape(B, S, H, hd)


def kernel_reasons(q, k_pool, page_table, geom: BlockSparse,
                   interpret: bool) -> List[str]:
    """Why the two kernels cannot take these operands ([] = they can)."""
    from ...models.sharding import current_topology

    B, S, H, hd = q.shape
    ps, KV = k_pool.shape[2], k_pool.shape[3]
    mp = page_table.shape[1]
    reasons = []
    topo = current_topology()
    if topo is not None and topo.world_size > 1:
        reasons.append("a mesh of several devices (the selection is a kv "
                       "group's: the kernels are written for one device)")
    if k_pool.dtype not in (jnp.bfloat16, jnp.float32):
        reasons.append(f"{jnp.dtype(k_pool.dtype).name} KV pool")
    elif k_pool.dtype == jnp.bfloat16 and KV > 1 and KV % 2:
        reasons.append(f"{KV} KV heads do not pair in bf16")
    if ps != geom.kernel_stride:
        reasons.append(f"pages of {ps} tokens are not the kernel stride "
                       f"{geom.kernel_stride}")
    if S % SELECT_ROWS or S % ATTN_ROWS:
        reasons.append(f"a chunk of {S} rows is not whole 16-row tiles")
    ppb = _attention_trip(ps, mp, geom)
    per_trip = ppb * ps // geom.block_size
    if ppb == 0 or LANES % max(per_trip, 1):
        reasons.append(f"a trip of {ppb} pages does not hold whole blocks of "
                       f"{geom.block_size} that divide a {LANES}-lane chunk")
    if not interpret:
        if hd % LANES:
            reasons.append(f"head_dim {hd} not {LANES}-aligned")
        if B * mp * 4 > SMEM_TABLE_BYTES:
            reasons.append(
                f"a [{B}, {mp}] page table is over the "
                f"{SMEM_TABLE_BYTES >> 10} KiB of SMEM it may take")
    return reasons


def block_sparse(q, k_pool, v_pool, kc_pool, cache_len, page_table, *, layer,
                 geom: BlockSparse, num_new=None,
                 interpret: Optional[bool] = None
                 ) -> Tuple[Optional[jax.Array], List[str]]:
    """Selection and attention of one layer through the kernels. Returns
    ``(out [B,S,H,hd], [])``, or ``(None, reasons)`` when the operands are
    not theirs (the caller takes the dense lines)."""
    interp = interpret if interpret is not None else (
        jax.default_backend() != "tpu")
    reasons = kernel_reasons(q, k_pool, page_table, geom, interp)
    if reasons:
        from ...utils.logging import log_fallback_once

        log_fallback_once("block_sparse_attention", reasons)
        return None, reasons
    ps, mp = k_pool.shape[2], page_table.shape[1]
    planes = plane_view(kc_pool, layer, page_table, geom,
                        _padded_blocks(geom, mp * ps))
    kept = block_select(q, planes, cache_len, num_new, geom, interpret=interp)
    return block_sparse_attention(
        q, k_pool, v_pool, kept, cache_len, page_table, layer=layer,
        geom=geom, num_new=num_new, interpret=interp), reasons


# ----------------------------------------------------------- dense lines
def unchunked(kept):
    """The kernels' ``[B, KV, chunks, S, 128]`` flags as bool
    ``[B, KV, S, blocks]``."""
    B, KV, C, S, L = kept.shape
    return kept.swapaxes(2, 3).reshape(B, KV, S, C * L) > 0.5


def dense_block_selection(q, kc_planes, positions, geom: BlockSparse):
    """bool [B,KV,S,blocks]: the blocks each row's kv group keeps, by plain
    lines over the plane view (``positions`` [B,S] the rows' own)."""
    B, S, H, hd = q.shape
    KV, NB = kc_planes.shape[1], kc_planes.shape[3]
    R, reach = geom.per_block, geom.reach
    qg = q.astype(F32).reshape(B, S, KV, H // KV, hd)
    j = (R * jnp.arange(NB)[None, :] - reach
         + jnp.arange(geom.planes)[:, None])  # [planes, blocks]
    nk = geom.keys_before(positions)  # [B, S]
    ok = (j >= 0)[None, None] & (j[None, None] < nk[:, :, None, None])
    ok = ok[:, None, :, None]  # [B, 1, S, 1, planes, blocks]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bsghd,bgrmd->bgshrm", qg,
                       kc_planes.astype(F32)) * hd ** -0.5
    own = ok & (jnp.arange(geom.planes) >= reach)[:, None]
    top = jnp.max(jnp.where(own, s, -jnp.inf), axis=(-2, -1), keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    total = jnp.sum(jnp.where(own, jnp.exp(s - top), 0.0), axis=(-2, -1),
                    keepdims=True)
    p = jnp.where(ok, jnp.exp(s - top) / jnp.where(total == 0, 1.0, total),
                  0.0)
    score = p.sum(axis=3).max(axis=3)  # heads summed, planes' max: [B,KV,S,NB]
    m = jnp.arange(NB)[None, None, None, :]
    pos = positions[:, None, :, None]
    causal = m <= pos // geom.block_size
    forced = (m < geom.init_blocks) | (
        m >= jnp.maximum(pos + 1 - geom.window_size, 0) // geom.block_size)
    score = jnp.where(causal, jnp.where(forced, FORCED, score), -jnp.inf)
    # lax.top_k is stable: of tied blocks (they share a compressed key) the
    # lower wins
    _, idx = lax.top_k(score, min(geom.topk, NB))
    kept = causal & (jnp.arange(NB) == idx[..., None]).any(axis=-2)
    return jnp.where(pos + 1 <= geom.dense_len, causal, kept)


def dense_block_attention(q, k_view, v_view, kept, positions,
                          geom: BlockSparse):
    """q [B,S,H,hd] over a gathered per-slot view [B,N,KV,hd] under
    ``kept`` [B,KV,S,blocks]: float32 [B,S,H,hd]."""
    B, S, H, hd = q.shape
    N, KV = k_view.shape[1], k_view.shape[2]
    G = H // KV
    qg = q.astype(F32).reshape(B, S, KV, G, hd)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bsghd,bngd->bgshn", qg,
                       k_view.astype(F32)) * hd ** -0.5
        kpos = jnp.arange(N)
        allowed = jnp.take_along_axis(
            kept, jnp.broadcast_to(
                (kpos // geom.block_size)[None, None, None, :],
                (B, KV, S, N)), axis=3)
        allowed &= kpos[None, None, None, :] <= positions[:, None, :, None]
        p = jax.nn.softmax(jnp.where(allowed[:, :, :, None, :], s, -1e30),
                           axis=-1)
        out = jnp.einsum("bgshn,bngd->bsghd", p, v_view.astype(F32))
    return out.reshape(B, S, H, hd)
