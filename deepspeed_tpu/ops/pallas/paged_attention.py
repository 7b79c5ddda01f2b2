"""Pallas paged attention for the serving step's [slots, chunk] block.

Parity: the blocked-KV attention of DeepSpeed-FastGen / vLLM's block-table
kernels, for the one ``[max_slots, token_budget]`` step the serving engine
compiles. The XLA fallback (models/decoding.py) gathers a per-slot
``[B, capacity]`` view, repeats K/V to every query head and builds a
float32 ``[B, H, S, capacity]`` score tensor whatever a slot holds; this
kernel reads each slot's own pages through the table and its work —
loop trips and DMA included — follows the slot's length.

Shape of the kernel: one program per slot (grid ``(B,)``), or, where a
slot's whole ``[KV, S * G, hd]`` query block with its float32 accumulators
is over the VMEM budget (16 query heads a KV head at a 256-row chunk), one
per slot and ROW TILE (grid ``(B, S / rows)``, :func:`row_tile`): a tile is
a chunk of its own whose frontier is the slot's plus the rows before it, so
a tile past the slot's real rows is idle and a decoding slot computes one
tile, not the chunk. Where the grid is one program a slot, the program
reads the slot's real rows and runs the same block loops over the first
``SMALL_ROWS`` rows of the stack alone when they hold every real row (row
``r`` of the stack is query ``r // G``, so a slot's real rows are its first:
a decoding slot's ``G`` rows, a verify window's), the whole stack otherwise;
the rows past the small tile are padding and read zeros. ``SMALL_ROWS`` is
one constant, taken from the call alone on the v5e at three cells' shapes
(16 and 32 rows cost the same, 64 more; PERF.md section 6, PR 63);
:func:`small_tile_slots` is the predicate over host vectors. The pools stay
in HBM; a loop whose trip count is read from the slot's frontier fetches
``pages_per_block`` pages a trip (whole pages, in the pool's own
``[page_size, KV, hd]`` layout: all KV heads of a page are contiguous and
nothing is relaid out in HBM) by async copy into double-buffered VMEM, the
next block in flight while this one is computed; each head's ``[block_k,
hd]`` tile is then a strided read of that buffer. The G query heads of
one KV head stack as ``[S * G, hd]`` rows against one ``[block_k, hd]`` K
tile (no head is repeated); fp32 online softmax in VMEM via
decode_attention's ``_tile_update``. Blocks wholly below the chunk's first
row skip the causal mask. With ``window`` (a window layer: row ``i`` sees
only its last ``window`` keys) the loop starts at the block of row 0's
oldest visible key, and the blocks that a window's lower edge crosses are
masked there too.

Layouts: q ``[B, S, H, hd]``, pools ``[L, P+1, page_size, KV, hd]``: the
whole stack of init_paged_cache, as stored, with the layer's index a scalar
in SMEM beside page_table ``[B, max_pages]`` and the per-row frontiers (a
layer sliced out of the stack would be a copy of the pool every call). Row
``i`` of slot ``b`` attends ``kpos <= cache_len[b] + i``; the chunk's own
keys are already in the pool (the caller scatters first).

Heads of 64 (:func:`lane_pairs`): the chip keeps no array whose rows are 64
wide as it is written (such a pool is stored with its lanes padded to 128,
twice the bytes, or with another axis innermost, which every call would
re-lay), so a pool of ``KV`` (even) 64-wide heads is HELD with two heads a
128-lane row, ``[L, P+1, page_size, KV / 2, 128]``: the bytes of a row-major
``[page_size, KV, 64]`` page in the same order, and what the kernel is handed
as a pool of ``KV / 2`` heads of 128. The ``2 G`` query heads of a lane pair
``(a, b)`` stack a query as one group: a's ``G`` with zeros in lanes 64-127
over b's ``G`` with zeros in lanes 0-63, so ``Q K^T`` over the 128 lanes is
each row's own head's and ``P V`` carries both heads' values, of which a row
keeps its own half. The products are twice the useful ones; the bytes of a
walk are not, and a page is fetched once a slot. The kernel below is the one
program at either width.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import LANES, NEG_INF, _normalized, _tile_update

# keys a loop trip: 512 beat 256 at every length tried on the v5e (by 15 %
# with 16 slots at 300 tokens, by 27 % with 16 at 8k; PERF.md, PR 28)
DEFAULT_BLOCK_K = 512
# the chip's VMEM is 128 MiB; the scoped default (16 MiB) is below what the
# per-head (m, l, acc) scratches of a 128-row chunk take
VMEM_LIMIT_BYTES = 96 * 1024 * 1024
VMEM_BUDGET_BYTES = 64 * 1024 * 1024
# the page table rides in SMEM (1 MiB on the v5e; 512 KiB compiles, 1 MiB
# does not)
SMEM_TABLE_BYTES = 512 * 1024
# rows of the [S * G, hd] query stack a program of the row-tiled grid takes:
# what a slot with one real row (a decoding one) computes a key block, and
# the inverse of how often a prompt chunk's programs re-read its K and V. At
# 16 query heads a KV head under [8, 256] (seven slots decoding beside one
# prompt chunk at contexts of 4-65 k; my chip runs, PR 56, PERF.md section 6)
# 1,024 / 512 / 256 served 7,679 / 7,597 / 7,811 tokens/s and the two calls
# took 15.4 / 14.5 / 10.7 ms of a traced step
ROW_TILE_ROWS = 256
# rows of the [S * G, hd] stack a program of the one-program-a-slot grid
# takes where the slot's real rows fit them (a decoding slot's G rows, a
# verify window's): a multiple of a bf16 sublane tile, chosen once from the
# call alone on the v5e at three cells' shapes (PERF.md section 6, PR 63)
SMALL_ROWS = 32


def lane_pairs(hd: int, KV: int) -> int:
    """How many KV heads of ``hd`` a 128-lane row of the pool holds: 2 where
    heads of 64 pair (``KV`` even), else 1 (the head is the row)."""
    return 2 if 2 * hd == LANES and KV % 2 == 0 else 1


def kernel_heads(H: int, KV: int, hd: int) -> Tuple[int, int, int]:
    """(query heads a group, KV heads, head width) as the kernel sees ``H``
    query heads on ``KV`` heads of ``hd``: a lane pair is one KV head of 128
    whose group is both heads' queries."""
    n = lane_pairs(hd, KV)
    return n * (H // KV), KV // n, n * hd


def paired_pool_row(KV: int, hd: int) -> Tuple[int, int]:
    """The ``[heads, lanes]`` of a token's row in a K / V pool as the chip
    holds it: ``[KV / 2, 128]`` for heads of 64 that pair, else ``[KV,
    hd]``."""
    return kernel_heads(KV, KV, hd)[1:]


def _second_of_pair(H: int, KV: int):
    """[H, 1] bool: query head ``h`` reads KV head ``h // G``, the SECOND of
    its lane pair where that is odd."""
    return ((jnp.arange(H) // (H // KV)) % 2 == 1)[:, None]


def _pair_queries(q, KV: int):
    """q [B, S, H, 64] -> [B, S, H, 128]: the queries of the FIRST head of a
    lane pair in lanes 0-63 (zeros in 64-127), those of the second in lanes
    64-127."""
    second = _second_of_pair(q.shape[2], KV)
    zero = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(second, zero, q),
                            jnp.where(second, q, zero)], axis=-1)


def _unpair_outputs(out, KV: int):
    """[B, S, H, 128] -> [B, S, H, 64]: each query head's own half of its
    lane pair's values (:func:`_pair_queries`)."""
    hd = out.shape[3] // 2
    return jnp.where(_second_of_pair(out.shape[2], KV),
                     out[..., hd:], out[..., :hd])


def _head_tiles(buf, KV: int):
    """The per-head [block_k, hd] tiles of one fetched block: ``buf`` is a
    VMEM ref [block_k, KV, hd] in the pool's layout, so head ``kv`` is
    every KV-th row of its [block_k * KV, hd] view. Yields (kv, tile) in
    head order. A bf16 buffer packs two rows to a 32-bit sublane: one
    strided read of the uint32 view brings a pair of heads, split by shift
    and mask (exact: a bf16 is the top half of its float32)."""
    bk, _, hd = buf.shape
    flat = buf.reshape(bk * KV, hd)
    if KV == 1:
        yield 0, flat[...]
    elif buf.dtype == jnp.bfloat16:
        words = flat.bitcast(jnp.uint32)  # [bk * KV / 2, hd]
        for pair in range(KV // 2):
            w = words[pair::KV // 2, :]
            yield 2 * pair, pltpu.bitcast(
                w << 16, jnp.float32).astype(jnp.bfloat16)
            yield 2 * pair + 1, pltpu.bitcast(
                w & jnp.uint32(0xFFFF0000), jnp.float32).astype(jnp.bfloat16)
    else:
        for kv in range(KV):
            yield kv, flat[kv::KV, :]


def _paged_attention_kernel(pt_ref, cl_ref, nn_ref, layer_ref, q_ref, k_hbm,
                            v_hbm, o_ref, k_buf, v_buf, sems, kh_scr,
                            vh_scr, m_scr, l_scr, acc_scr,
                            *, scale, page_size, pages_per_block, group,
                            window=None, tiled=False):
    KV, SG, hd = q_ref.shape[1:]
    ps, ppb = page_size, pages_per_block
    bk = ps * ppb
    mp = pt_ref.shape[1]
    b = pl.program_id(0)
    cl = cl_ref[b]
    nn = nn_ref[b]
    if tiled:  # this program's rows are a chunk that starts ``before`` in
        before = pl.program_id(1) * (SG // group)
        cl, nn = cl + before, jnp.clip(nn - before, 0, SG // group)
    layer = layer_ref[0]

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
            # logical pages past the table (a last block that overhangs
            # it) re-read the last entry: those keys lie past every row's
            # frontier
            page = pt_ref[b, jnp.minimum(blk * ppb + j, mp - 1)]
            for c in page_copies(slot, j, page):
                c.start()
            return carry

        lax.fori_loop(0, ppb, one, 0)

    def wait_fetch(slot):
        def one(j, carry):
            # a wait needs the copy's shape and semaphore, not its source
            for c in page_copies(slot, j, 0):
                c.wait()
            return carry

        lax.fori_loop(0, ppb, one, 0)

    @pl.when(nn == 0)
    def _idle():
        # a slot with nothing scheduled: its rows are padding (the frontier
        # invariant in models/decoding._cached_attention); keep them finite
        o_ref[...] = jnp.zeros_like(o_ref)

    def attend(rows):
        """The slot's three block loops over the first ``rows`` (static)
        rows of its stack: all ``SG`` of them, or the small tile that holds
        every real row (the rows past it are padding and read zeros, as an
        idle slot's do)."""
        # this body's rows of a head's [SG, ...] block (the whole stack
        # takes its refs unsliced, as it always did)
        rs = () if rows == SG else (slice(rows),)
        # keys needed: 0 .. cl + nn - 1 (rows past nn are padding and may
        # see less than their frontier)
        n_blocks = jnp.minimum(pl.cdiv(cl + nn, bk), pl.cdiv(mp * ps, bk))
        # blocks wholly at or below row 0's frontier need no mask
        n_full = jnp.minimum((cl + 1) // bk, n_blocks)
        if window is None:
            first = lo_end = 0
        else:
            # row 0 sees keys from cl - window + 1 on; the last real row's
            # lower edge, cl + nn - window, is the highest: a block that
            # starts at or past it is below no real row's window
            first = jnp.maximum(cl - (window - 1), 0) // bk
            lo_end = jnp.clip(
                pl.cdiv(jnp.maximum(cl + nn - window, 0), bk), first,
                n_blocks)
            n_full = jnp.clip(n_full, lo_end, n_blocks)

        m_scr[(slice(None), *rs)] = jnp.full(
            (KV, rows, LANES), NEG_INF, m_scr.dtype)
        l_scr[(slice(None), *rs)] = jnp.zeros((KV, rows, LANES), l_scr.dtype)
        acc_scr[(slice(None), *rs)] = jnp.zeros((KV, rows, hd), acc_scr.dtype)
        start_fetch(first, 0 if window is None else lax.rem(first, 2))

        def block(i, carry, *, masked):
            slot = lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_fetch(i + 1, 1 - slot)

            wait_fetch(slot)
            # head-major copies of the block, so that one rolled loop over
            # the KV heads serves them all: a body a head runs the long
            # contexts a third faster (3.8 against 5.6 ms with 16 slots at
            # 8k) but octuples the kernel's code, and the engine's first
            # step then takes 4 s longer even from a warm compile cache
            # (PERF.md, PR 28)
            for kv, tile in _head_tiles(k_buf.at[slot], KV):
                kh_scr[kv] = tile
            for kv, tile in _head_tiles(v_buf.at[slot], KV):
                vh_scr[kv] = tile
            start = i * bk

            def head(kv, c):
                frontier = None
                if masked:
                    # row r of the [S * G, hd] stack is query (r // G)
                    frontier = cl + lax.broadcasted_iota(
                        jnp.int32, (rows, bk), 0
                    ) // group
                _tile_update(
                    q_ref[(0, kv, *rs)], kh_scr[kv], vh_scr[kv], None, None,
                    start, frontier, scale, m_scr.at[(kv, *rs)],
                    l_scr.at[(kv, *rs)], acc_scr.at[(kv, *rs)],
                    lo=frontier - window
                    if masked and window is not None else None,
                )
                return c

            lax.fori_loop(0, KV, head, 0)
            return carry

        if window is not None:
            lax.fori_loop(first, lo_end,
                          functools.partial(block, masked=True), 0)
        lax.fori_loop(lo_end, n_full,
                      functools.partial(block, masked=False), 0)
        lax.fori_loop(n_full, n_blocks,
                      functools.partial(block, masked=True), 0)

        if rs:
            o_ref[...] = jnp.zeros_like(o_ref)

        def finish(kv, c):
            o_ref[(0, kv, *rs)] = _normalized(
                l_scr.at[(kv, *rs)], acc_scr.at[(kv, *rs)]
            ).astype(o_ref.dtype)
            return c

        lax.fori_loop(0, KV, finish, 0)

    if tiled or SG <= SMALL_ROWS:
        pl.when(nn > 0)(functools.partial(attend, SG))
    else:
        # one program a slot: a slot whose real rows (its first nn * group
        # stacked rows) fit the small tile computes that tile alone
        # (a decoding slot, a verify window, a cached prompt's last token)
        small = nn * group <= SMALL_ROWS
        pl.when((nn > 0) & small)(functools.partial(attend, SMALL_ROWS))
        pl.when(jnp.logical_not(small))(functools.partial(attend, SG))


def _block_pages(block_k: int, page_size: int, max_pages: int) -> int:
    return max(1, min(block_k // page_size, max_pages))


def _vmem_bytes(S, G, KV, hd, page_size, pages_per_block, q_bytes, kv_bytes):
    """What one program keeps in VMEM: the (m, l, acc) scratches of every
    KV head, the double-buffered K and V blocks and their head-major
    copies, the pipelined q and out
    blocks, and the [S * G, block_k] fp32 score/probability temporaries."""
    SG, bk = S * G, page_size * pages_per_block
    scratch = KV * SG * (2 * LANES + hd) * 4
    kv_bufs = 3 * 2 * bk * KV * hd * kv_bytes  # two fetch slots + head-major
    q_out = 2 * 2 * KV * SG * hd * q_bytes
    temps = 4 * SG * bk * 4
    return scratch + kv_bufs + q_out + temps


def row_tile(S: int, G: int, KV: int, hd: int, page_size: int,
             pages_per_block: int, q_bytes: int, kv_bytes: int
             ) -> Optional[int]:
    """Query rows of a slot's chunk one program takes: all ``S`` where the
    whole block fits the VMEM budget (one program a slot, as ever); else the
    most rows that divide ``S`` into sublane-whole tiles of at most
    ``ROW_TILE_ROWS`` stacked rows that fit; None if none does."""
    def fits(rows):
        return _vmem_bytes(rows, G, KV, hd, page_size, pages_per_block,
                           q_bytes, kv_bytes) <= VMEM_BUDGET_BYTES

    if fits(S):
        return S
    return next((rows for rows in range(min(S, ROW_TILE_ROWS // G), 7, -1)
                 if S % rows == 0 and rows % 8 == 0 and fits(rows)), None)


def key_counts(cache_len, num_new, page_size: int, max_pages: int,
               window: Optional[int] = None,
               block_k: int = DEFAULT_BLOCK_K) -> Tuple[int, int]:
    """What one call of the kernel has to do for host vectors ``cache_len``
    and ``num_new`` [B], by the same arithmetic as its loop: (attended, the
    keys visible to every real query row, summed; fetched, the keys of the
    blocks the slots' loops read). With ``block_k`` the page size, fetched
    is the keys of the pages that hold a visible key: what any paged read
    needs, which is what the engine books."""
    cl = np.asarray(cache_len, np.int64)
    nn = np.asarray(num_new, np.int64)
    bk = page_size * _block_pages(block_k, page_size, max_pages)
    attended = nn * cl + nn * (nn + 1) // 2
    first = np.zeros_like(cl)
    if window is not None:
        # rows whose frontier passes the window see ``window`` keys
        over = np.clip(cl + nn - window, 0, nn)
        attended -= over * (cl + nn - window) - over * (over - 1) // 2
        first = np.maximum(cl - (window - 1), 0) // bk
    n_blocks = np.minimum(-(-(cl + nn) // bk), -(-(max_pages * page_size) // bk))
    fetched = np.where(nn > 0, (n_blocks - first) * bk, 0)
    return int(attended.sum()), int(fetched.sum())


def small_tile_slots(num_new, G: int, S: int,
                     rows: Optional[int] = None) -> int:
    """How many slots of host vector ``num_new`` [B] take the kernel's small
    tile, by the kernel's own predicate: ``0 < nn * G <= SMALL_ROWS`` where
    the grid is one program a slot whose ``[S * G, hd]`` stack is larger.
    ``rows`` is the call's :func:`row_tile` (None: the whole chunk); a
    row-tiled grid has no small tile and reads 0."""
    if (rows is not None and rows < S) or S * G <= SMALL_ROWS:
        return 0
    nn = np.asarray(num_new, np.int64)
    return int(((nn > 0) & (nn * G <= SMALL_ROWS)).sum())


def _frontiers(B: int, S: int, cache_len, num_new):
    """Per-slot int32 [B] (frontier, real rows): a scalar frontier
    broadcasts, no ``num_new`` means every row is real."""
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    nn = (
        jnp.full((B,), S, jnp.int32) if num_new is None
        else jnp.clip(jnp.asarray(num_new, jnp.int32).reshape(-1), 0, S)
    )
    return cl, nn


def paged_attention_kernel(q, k_pool, v_pool, cache_len, page_table, *,
                           layer, num_new=None,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None,
                           name: Optional[str] = None):
    """q [B,S,H,hd] chunk queries vs layer ``layer`` (a scalar, traced or
    not) of a block-paged KV pool stack k/v_pool [L, P+1, page_size, KV, hd]
    addressed through per-slot page tables [B, max_pages], the stack read
    as stored. ``cache_len`` [B] is each slot's frontier BEFORE
    the chunk (row i attends kpos <= cache_len[b] + i; the caller has
    already scattered the chunk's keys). ``num_new`` [B] (optional) is the
    count of real rows: the loop stops at the last key a real row needs,
    and a slot with none is skipped (its output rows are zeros). ``window``
    (static) bounds row i to ``kpos > cache_len[b] + i - window`` as well;
    ``name`` is the call's name in a device trace. Returns [B,S,H,hd].
    Heads of 64 over a pool held two a row (``[.., KV / 2, 128]``,
    :func:`lane_pairs`; a ``[.., KV, 64]`` pool is read as one, which only
    the interpreter does for free) run as ``KV / 2`` heads of 128."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    # the model's KV heads, however the pool's rows hold them
    heads = k_pool.shape[3] * k_pool.shape[4] // q.shape[-1]
    paired = lane_pairs(q.shape[-1], heads) > 1
    if paired:
        row = (*k_pool.shape[:3], *paired_pool_row(heads, q.shape[-1]))
        k_pool, v_pool = k_pool.reshape(row), v_pool.reshape(row)
        q = _pair_queries(q, heads)
    B, S, H, hd = q.shape
    ps, KV = k_pool.shape[2], k_pool.shape[3]
    mp = page_table.shape[1]
    G = H // KV
    ppb = _block_pages(block_k, ps, mp)
    rows = row_tile(S, G, KV, hd, ps, ppb, jnp.dtype(q.dtype).itemsize,
                    jnp.dtype(k_pool.dtype).itemsize) or S
    tiled = rows < S
    SG = rows * G
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pt = jnp.asarray(page_table, jnp.int32)
    cl, nn = _frontiers(B, S, cache_len, num_new)
    # the G query heads of a KV head stack beside each query: [S * G, hd]
    qg = q.reshape(B, S, KV, G, hd).swapaxes(1, 2).reshape(B, KV, S * G, hd)
    if tiled:  # a program a slot and row tile
        grid, q_map = (B, S // rows), lambda b, t, *_: (b, 0, t, 0)
    else:
        grid, q_map = (B,), lambda b, *_: (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # page_table, cache_len, num_new, layer
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, KV, SG, hd), q_map),
            # the pool stacks stay in HBM; whole pages ([ps, KV, hd], all
            # heads contiguous) come in by async copy
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KV, SG, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * ps, KV, hd), k_pool.dtype),
            pltpu.VMEM((2, ppb * ps, KV, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, ppb * ps, hd), k_pool.dtype),
            pltpu.VMEM((KV, ppb * ps, hd), v_pool.dtype),
            pltpu.VMEM((KV, SG, LANES), jnp.float32),
            pltpu.VMEM((KV, SG, LANES), jnp.float32),
            pltpu.VMEM((KV, SG, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attention_kernel, scale=scale, page_size=ps,
            pages_per_block=ppb, group=G, window=window, tiled=tiled,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, S * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name or "paged_attention",
    )(pt, cl, nn, jnp.asarray(layer, jnp.int32).reshape(1), qg, k_pool,
      v_pool)
    out = out.reshape(B, KV, S, G, hd).swapaxes(1, 2).reshape(B, S, H, hd)
    return _unpair_outputs(out, heads) if paired else out


def paged_attention(q, k_pool, v_pool, cache_len, page_table, *, layer,
                    num_new=None, interpret: Optional[bool] = None,
                    window: Optional[int] = None, name: Optional[str] = None
                    ) -> Tuple[Optional[jax.Array], List[str]]:
    """Shard-map-aware wrapper (heads over tp, slots over dp/fsdp — the
    layout of decode_attention's). Returns ``(out, reasons)``: ``out`` is
    None when the shapes don't fit the kernel, ``reasons`` says why (logged
    once) and the caller falls back to the dense XLA lines."""
    from ...models.sharding import current_topology

    B, S, H, hd = q.shape
    ps = k_pool.shape[2]
    # the model's KV heads, however the pool's rows hold them: a pool of
    # 64-wide heads held two a 128-lane row is [.., KV / 2, 128]
    held_paired = k_pool.shape[4] != hd
    KV = k_pool.shape[3] * k_pool.shape[4] // hd
    mp = page_table.shape[1]
    topo = current_topology()
    distributed = topo is not None and topo.world_size > 1
    tp = topo.tp_size if distributed else 1
    interp = interpret if interpret is not None else (
        jax.default_backend() != "tpu"
    )
    reasons = []
    if H % KV != 0:
        reasons.append(f"H={H} not a multiple of KV={KV}")
    if distributed and (H % tp != 0 or KV % tp != 0):
        reasons.append(f"H={H}/KV={KV} not divisible by tp={tp}")
    kv_local = KV // tp if KV % tp == 0 else KV
    if held_paired and (
            paired_pool_row(KV, hd) != k_pool.shape[3:] or kv_local % 2):
        reasons.append(
            f"a pool row {list(k_pool.shape[3:])} is not {kv_local} local "
            f"KV heads of {hd} two a {LANES}-lane row")
    # what the kernel runs: a lane pair is one KV head of 128
    G, kv_local, lanes = kernel_heads(
        H // KV * kv_local, kv_local, hd) if held_paired else (
        H // KV, kv_local, hd)
    if k_pool.dtype not in (jnp.bfloat16, jnp.float32):
        reasons.append(f"{jnp.dtype(k_pool.dtype).name} KV pool")
    elif k_pool.dtype == jnp.bfloat16 and kv_local > 1 and kv_local % 2:
        reasons.append(f"{kv_local} local KV heads do not pair in bf16")
    if not interp:
        # what the chip's compiler asks of the tiles (interpret mode has no
        # such constraint): head_dim on the lanes, and a page's [KV, hd]
        # rows filling whole sublane tiles (as jax's
        # ragged_paged_attention asks of its combined heads)
        sublanes = kv_local * jnp.dtype(k_pool.dtype).itemsize // 4
        if lanes % LANES != 0:
            reasons.append(
                f"head_dim {hd} not {LANES}-aligned" + (
                    f" ({KV} KV heads a row: an even number of 64-wide "
                    f"heads is taken where the pool holds two a {LANES}-lane "
                    "row)" if hd < LANES else ""))
        if sublanes not in (1, 2, 4) and (sublanes == 0 or sublanes % 8):
            reasons.append(
                f"{kv_local} local KV heads in "
                f"{jnp.dtype(k_pool.dtype).name} do not fill a sublane tile"
            )
    slots = B
    if distributed:
        for a in ("dp", "fsdp"):
            slots //= max(topo.sizes[a], 1)
    if not interp and slots * mp * 4 > SMEM_TABLE_BYTES:
        reasons.append(
            f"a [{slots}, {mp}] page table is over the "
            f"{SMEM_TABLE_BYTES >> 10} KiB of SMEM it may take"
        )
    if not reasons:
        shape = (G, kv_local, lanes, ps,
                 _block_pages(DEFAULT_BLOCK_K, ps, mp),
                 jnp.dtype(q.dtype).itemsize, jnp.dtype(k_pool.dtype).itemsize)
        if row_tile(S, *shape) is None:
            reasons.append(
                f"a [{S} x {G}]-row chunk of {kv_local} KV heads needs "
                f"{_vmem_bytes(S, *shape) >> 20} MiB of VMEM (budget "
                f"{VMEM_BUDGET_BYTES >> 20} MiB) and no tile of 8 or more of "
                f"its rows (at most {ROW_TILE_ROWS} stacked) fits either"
            )
    if reasons:
        from ...utils.logging import log_fallback_once

        log_fallback_once("paged_attention", reasons)
        return None, reasons

    if not distributed:
        return paged_attention_kernel(
            q, k_pool, v_pool, cache_len, page_table, layer=layer,
            num_new=num_new, interpret=interp, window=window, name=name,
        ), reasons

    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("dp", "fsdp") if topo.sizes[a] > 1)
    b_ax = batch_axes if batch_axes else None
    h_ax = "tp" if tp > 1 else None
    # page pools are slot-agnostic: heads over tp, pages replicated; the
    # table and the frontiers ride with the (slot) batch
    q_spec = P(b_ax, None, h_ax, None)
    kv_spec = P(None, None, None, h_ax, None)

    def body(q, kc, vc, cl, nn, pt, layer):
        return paged_attention_kernel(
            q, kc, vc, cl, pt, layer=layer, num_new=nn, interpret=interp,
            window=window, name=name,
        )

    return jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(b_ax), P(b_ax), P(b_ax, None),
                  P()),
        out_specs=q_spec,
        check_vma=False,
    )(q, k_pool, v_pool, *_frontiers(B, S, cache_len, num_new),
      jnp.asarray(page_table, jnp.int32), jnp.asarray(layer, jnp.int32)
      ), reasons
