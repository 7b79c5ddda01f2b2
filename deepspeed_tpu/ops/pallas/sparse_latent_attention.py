"""Pallas kernels of learned sparse attention over a paged latent cache.

Parity: DeepSeek-V3.2's lightning indexer and its sparse latent attention
(``inference/model.py`` of the release: ``Indexer`` and the absorbed branch
of ``MLA``), for the one ``[max_slots, token_budget]`` step the serving
engine compiles. Three calls a layer, each named in a device trace:

``indexer_scores``  For every real query row of a slot, the index score of
    every cached token at or before it: ``sum_j w_j relu(q_j . k)`` over
    the indexer's heads, the keys read page by page through the slot's
    table from the indexer's own pool. One program a slot; a loop over key
    blocks whose trip count follows the slot's length, and inside it a loop
    over row tiles that follows the slot's real rows (:func:`score_tiles`
    counts both). A tile is some queries with their heads stacked
    query-major: one product against the key block, then ``relu``, the
    weights and the sum over heads. A slot's rows go in LARGE tiles of
    ``SCORE_STACK`` stacked rows (the key block enters the matrix unit once
    for all of them) where at least half of one is real, and in tiles of
    ``SCORE_ROWS`` queries for the rest, so a decoding slot pays for 16
    rows. A block's pages (32 copies of 4 KiB at 16-token pages) are issued
    as straight-line code and ``SCORE_RING`` blocks are in flight, keys
    coming and scores going: a decoding slot's trip is one small product,
    so it runs at the pace the copies are issued and land at. A block's
    ``[S, block_k]`` scores go out whole; the rows of tiles that were not
    computed hold whatever the buffer held.
``selection_topk``  The exact ``topk`` largest scores of each row, as a
    threshold: 32 counting passes find the ``topk``-th largest value bit by
    bit (the scores as order-preserving integers), one more counts the keys
    above it, its ties and the last of them. Where every row of a tile
    needs all its ties (the rule: float32 sums seldom meet at the
    threshold) that last position is the answer; else 17 passes more find
    the position up to which the ties belong (ties go to the lower
    position). A pass adds lane by lane over the key blocks the context
    reaches and sums across the lanes once. A program a tile of 8 rows;
    one that searches nothing (no real row, or a context within ``topk``,
    which keeps it all) parks its block of scores on a neighbour's, so it
    fetches none. No sort, no index list.
``sparse_latent_attention``  Softmax attention of the absorbed queries over
    the chosen tokens of the latent pool. One latent row is key AND value of
    all heads, so the heads of a query stack as the rows of one matmul
    against one ``[block_k, latent]`` tile. The program walks every block of
    the slot's context and masks what the selection left out: its work
    follows the context, not ``topk`` (PERF.md says what that costs; a walk
    over a per-row list of chosen rows is the open follow-up).
``latent_attention``  The same walk with nothing masked, for a latent layer
    without an indexer (:func:`latent_attention`): every key at or before a
    query is attended; no scores, no threshold.

Layouts: pools ``[L, P+1, page_size, width]`` as stored, the layer's index a
scalar in SMEM beside ``page_table [B, max_pages]`` and the per-slot
frontiers; ``width`` is a multiple of 128 lanes (a latent row is padded to
it with zeros). Scores are float32 ``[B, blocks, S, block_k]``, key block
major (a block of every row is one contiguous write of the scoring kernel
and one leading index of the two that read it); only blocks a slot's loop
reaches are written, and only columns at or before a row's own position are
ever read.

POOLED index keys (``kpool`` > 1: one key a block of ``kpool`` tokens, the
pool ``[L, P+1, page_size / kpool, Di]`` on the same table). A page's few
pooled keys are no tile of a DMA, so :func:`pooled_view` gathers each slot's
into one contiguous run first (a sixteenth of the latent rows' bytes at
``kpool`` 4) and the scoring kernel reads that through a table that counts;
scores, threshold and tie are then by BLOCK (``POOLED_BLOCK_K`` a score
block, the blocks of one ``BLOCK_K`` of tokens), a row sees the blocks whose
last token is at or before it, and the walk spreads a block's verdict over
its tokens with one small product and always attends the tokens after the
row's last whole block.

The ``dense_*`` functions compute the same three steps in plain
``jax.numpy`` over a gathered per-slot view: the path of a CPU engine and
the oracle of the kernels' tests.
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
from .paged_attention import (SMEM_TABLE_BYTES, VMEM_LIMIT_BYTES,
                              _block_pages, _frontiers)

BLOCK_K = 512        # keys a loop trip of the scoring and attention kernels
SCORE_ROWS = 16      # query rows a tile of the scoring kernel
SCORE_STACK = 2048   # stacked rows (queries x heads) of its large tile
SCORE_RING = 4       # key blocks (and score blocks) it keeps in flight
SELECT_ROWS = 8      # query rows a program of the selection kernel
SELECT_BLOCKS = 8    # key blocks a counting step of the selection reads
ATTN_ROWS = 8        # query rows a program of the attention kernel
INT_MIN = -(2 ** 31)


def score_blocks(max_pages: int, page_size: int,
                 block_k: int = BLOCK_K) -> Tuple[int, int]:
    """(key blocks of the score matrix, keys a block): the slot's mapped
    tokens in whole blocks, the blocks in whole counting steps."""
    bk = page_size * _block_pages(block_k, page_size, max_pages)
    nb = -(-(max_pages * page_size) // bk)
    if nb > SELECT_BLOCKS:
        nb = -(-nb // SELECT_BLOCKS) * SELECT_BLOCKS
    return nb, bk


# --------------------------------------------------------------- paging
def _page_fetch(pt_ref, hbm, buf, sems, b, layer, ps, ppb, mp,
                unroll: bool = False):
    """(start, wait) of the block fetches of one slot: ``ppb`` whole pages
    of ``hbm[layer]`` through row ``b`` of the table into ``buf[slot]``.
    ``unroll``: the ``ppb`` copies of a block are issued (and awaited) as
    straight-line code, not as a loop of scalar trips."""
    def copy(slot, j, page):
        return pltpu.make_async_copy(
            hbm.at[layer, page], buf.at[slot, pl.ds(j * ps, ps)],
            sems.at[slot])

    def start(blk, slot):
        def one(j, c):
            # pages past the table re-read its last entry: those keys lie
            # past every row's frontier
            copy(slot, j, pt_ref[b, jnp.minimum(blk * ppb + j, mp - 1)]
                 ).start()
            return c

        lax.fori_loop(0, ppb, one, 0, unroll=unroll)

    def wait(slot):
        def one(j, c):
            copy(slot, j, 0).wait()
            return c

        lax.fori_loop(0, ppb, one, 0, unroll=unroll)

    return start, wait


# -------------------------------------------------------------- indexer
def _index_scores_kernel(pt_ref, cl_ref, nn_ref, layer_ref, q_ref, w_ref,
                         k_hbm, o_hbm, k_buf, o_buf, ksems, osems,
                         *, page_size, pages_per_block, heads, rows, large,
                         kpool: int = 1):
    """A ring of ``k_buf.shape[0]`` key blocks: block ``i`` is computed
    while the pages of the next ``ring - 1`` are on their way and the
    scores of the last ``ring`` are on their way out. A slot's real rows
    are covered by tiles of ``large`` rows where it has (most of) that many
    and tiles of ``rows`` for the rest (:func:`_tiles`)."""
    ps, ppb = page_size, pages_per_block
    bk = ps * ppb
    mp = pt_ref.shape[1]
    ring = k_buf.shape[0]
    b = pl.program_id(0)
    cl, nn, layer = cl_ref[b], nn_ref[b], layer_ref[0]
    # a block of 16-token pages is 32 copies: issued by a loop, a decoding
    # slot's trip waits for the scalar trips, not for the keys (PERF.md,
    # PR 64)
    start_fetch, wait_fetch = _page_fetch(pt_ref, k_hbm, k_buf, ksems, b,
                                          layer, ps, ppb, mp, unroll=True)

    def out_copy(slot, blk):
        return pltpu.make_async_copy(
            o_buf.at[slot], o_hbm.at[b, blk], osems.at[slot])

    @pl.when(nn > 0)
    def _score():
        keys = cl + nn  # a key a token, or with kpool a whole block of them
        if kpool > 1:
            keys = keys // kpool
        n_blocks = jnp.minimum(pl.cdiv(keys, bk), pl.cdiv(mp * ps, bk))
        n_large, n_small = _tiles(nn, o_buf.shape[1], rows, large)

        def first(i, c):
            start_fetch(i, i)
            return c

        lax.fori_loop(0, jnp.minimum(ring - 1, n_blocks), first, 0)

        def block(i, carry):
            slot = lax.rem(i, ring)

            @pl.when(i + ring - 1 < n_blocks)
            def _prefetch():  # into the slot block i - 1 was computed from
                start_fetch(i + ring - 1, lax.rem(i + ring - 1, ring))

            wait_fetch(slot)

            @pl.when(i >= ring)
            def _drain():  # the write that last used this slot
                out_copy(slot, i - ring).wait()

            k = k_buf[slot]

            def tile_of(size):
                def tile(t, c):
                    r0 = pl.multiple_of(t * size * heads, size * heads)
                    q = q_ref[0, pl.ds(r0, size * heads), :]
                    s = lax.dot_general(
                        q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [size*heads, bk]
                    s = jnp.maximum(s, 0.0) * w_ref[
                        0, pl.ds(r0, size * heads), :1]
                    o_buf[slot, pl.ds(pl.multiple_of(t * size, size), size),
                          :] = jnp.sum(s.reshape(size, heads, bk), axis=1)
                    return c

                return tile

            if large > rows:
                lax.fori_loop(0, n_large, tile_of(large), 0)
            first_small = n_large * (large // rows)
            lax.fori_loop(first_small, first_small + n_small, tile_of(rows), 0)
            out_copy(slot, i).start()
            return carry

        lax.fori_loop(0, n_blocks, block, 0)

        def last(i, c):  # the writes still on their way
            out_copy(lax.rem(i, ring), i).wait()
            return c

        lax.fori_loop(jnp.maximum(n_blocks - ring, 0), n_blocks, last, 0)


def score_rows(S: int, heads: int) -> Tuple[int, int]:
    """(rows of a small tile, rows of a large one) of the scoring kernel
    over chunks of ``S`` queries of ``heads`` index heads: the large tile
    stacks ``SCORE_STACK`` rows (or the chunk), which loads a key block
    into the matrix unit once for 8 (Keye), 4 or 2 small tiles' rows; a
    small tile is what a decoding slot's one row pays for."""
    rows = min(SCORE_ROWS, S)
    return rows, min(S, max(SCORE_STACK // heads // rows, 1) * rows)


def _at_most(x, cap: int):
    """``min(x, cap)`` of a kernel scalar or a host vector alike."""
    return x - (x - cap) * (x > cap)


def _tiles(nn, S: int, rows: int, large: int):
    """(large tiles, small tiles) that cover ``nn`` real rows of ``S`` (a
    scalar of the kernel or a host vector): a large tile where at least
    half of it is real, small tiles from where the large ones end to the
    last real row."""
    n_large = 0 * nn
    if large > rows:
        n_large = _at_most((nn + large // 2) // large, S // large)
    rest = (nn + rows - 1) // rows - n_large * (large // rows)
    return n_large, rest * (rest > 0)


def score_grid(max_pages: int, page_size: int, kpool: int = 1
               ) -> Tuple[int, int]:
    """(key blocks a slot's table maps, keys a block) of the scoring
    kernel's loop over a ``[B, max_pages]`` table of ``page_size``-token
    pages; ``kpool`` > 1: over :func:`pooled_view`'s run of pooled keys."""
    if kpool > 1:
        pages = POOLED_BLOCK_K // (page_size // kpool)
        return -(-max_pages // pages), POOLED_BLOCK_K
    bk = page_size * _block_pages(BLOCK_K, page_size, max_pages)
    return -(-(max_pages * page_size) // bk), bk


def score_tiles(cache_len, num_new, S: int, heads: int, max_pages: int,
                page_size: int, kpool: int = 1):
    """(int [B], int) (numpy or jax, as the frontiers are): the (row tile,
    key block) trips of :func:`index_scores`'s loops for each slot, in
    small tiles (a large tile counts the small ones it covers), and the
    trips a slot over a full grid. A tile is computed where it holds a real
    row (or lies in a large tile that is half real), a block where it holds
    a key at or before the slot's last real row; an idle slot runs none."""
    blocks, bk = score_grid(max_pages, page_size, kpool)
    rows, large = score_rows(S, heads)
    n_large, n_small = _tiles(num_new, S, rows, large)
    tiles = n_large * (large // rows) + n_small
    n_blocks = _at_most((_keys(cache_len + num_new, kpool) + bk - 1) // bk,
                        blocks)
    return tiles * n_blocks, S // rows * blocks


def index_scores(q_idx, w_idx, ki_pool, cache_len, page_table, *, layer,
                 num_new=None, interpret: Optional[bool] = None,
                 kpool: int = 1, block_k: int = BLOCK_K):
    """Index scores float32 ``[B, blocks, S, block_k]`` (token ``s`` of row
    ``i`` at ``[b, s // block_k, i, s % block_k]``). ``q_idx`` [B,S,Hi,Di]
    rotated indexer queries, ``w_idx`` [B,S,Hi] float32 head weights (every
    constant factor folded in), ``ki_pool`` [L,P+1,ps,Di] the indexer keys,
    the chunk's own already written. An entry is defined for
    ``s < cache_len[b] + num_new[b]`` and ``i < num_new[b]``: the row tiles
    past a slot's real rows are not computed (what is written there is
    whatever the buffer held) and the blocks past its context are not
    written (:func:`score_tiles` counts what is). ``kpool`` > 1: a key is a
    block of that many tokens (``ki_pool`` and ``page_table`` in keys,
    :func:`pooled_view`'s), defined for ``s < (cache_len[b] + num_new[b])
    // kpool``."""
    B, S, Hi, Di = q_idx.shape
    ps, mp = ki_pool.shape[2], page_table.shape[1]
    ppb = _block_pages(block_k, ps, mp)
    NB, bk = score_blocks(mp, ps, block_k)
    rows, large = score_rows(S, Hi)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cl, nn = _frontiers(B, S, cache_len, num_new)
    q2 = q_idx.reshape(B, S * Hi, Di)
    w2 = jnp.broadcast_to(
        w_idx.astype(jnp.float32).reshape(B, S * Hi, 1), (B, S * Hi, LANES))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S * Hi, Di), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, S * Hi, LANES), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((SCORE_RING, bk, Di), ki_pool.dtype),
            pltpu.VMEM((SCORE_RING, S, bk), jnp.float32),
            pltpu.SemaphoreType.DMA((SCORE_RING,)),
            pltpu.SemaphoreType.DMA((SCORE_RING,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, page_size=ps,
                          pages_per_block=ppb, heads=Hi, rows=rows,
                          large=large, kpool=kpool),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NB, S, bk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="indexer_scores",
    )(jnp.asarray(page_table, jnp.int32), cl, nn,
      jnp.asarray(layer, jnp.int32).reshape(1), q2, w2, ki_pool)


# ------------------------------------------------------------ selection
def _sort_key(x):
    """float32 -> int32 of the same order (-0.0 counted as 0.0)."""
    i = lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.int32)
    return i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))


def _keys(tokens, kpool: int):
    """tokens -> keys (with ``kpool``: the whole blocks of that many)."""
    return tokens // kpool if kpool > 1 else tokens


def _searches(cl, nn, r0, rows: int, topk: int, kpool: int):
    """Whether the row tile at ``r0`` of a slot holds a real row whose
    context passes ``topk``, that of the tile's last real row ``cl +
    min(nn, r0 + rows)``: the tiles that have something to select."""
    return (r0 < nn) & (_keys(cl + nn, kpool) > topk) & (
        _keys(cl + r0 + rows, kpool) > topk)


def _selection_kernel(cl_ref, nn_ref, at_ref, s_ref, thr_ref, tie_ref,
                      key_scr, *, topk, rows, group, pos_bits, kpool: int = 1):
    b, t = pl.program_id(0), pl.program_id(1)
    cl, nn = cl_ref[b], nn_ref[b]
    bk = s_ref.shape[-1]
    r0 = t * rows
    # every key allowed: what a row inside ``topk`` (or a padded row) gets
    thr_ref[0] = jnp.full(thr_ref.shape[1:], INT_MIN, jnp.int32)
    tie_ref[0] = jnp.full(tie_ref.shape[1:], 2 ** 31 - 1, jnp.int32)

    @pl.when(_searches(cl, nn, r0, rows, topk, kpool))
    def _search():
        row = r0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        qpos = cl + row
        if kpool > 1:  # the last block whose last token is at or before it
            qpos = (qpos + 1) // kpool - 1
        n_groups = pl.cdiv(_keys(cl + jnp.minimum(nn, r0 + rows), kpool),
                           group * bk)  # counting steps the context reaches
        shape = (group, rows, bk)
        in_group = lax.broadcasted_iota(jnp.int32, shape, 0) * bk + (
            lax.broadcasted_iota(jnp.int32, shape, 2))

        def fill(g, carry):
            at = pl.ds(g * group, group)
            pos = g * group * bk + in_group
            key_scr[at] = jnp.where(
                pos <= qpos[None], _sort_key(s_ref[0, at]), INT_MIN)
            return carry

        lax.fori_loop(0, n_groups, fill, 0)

        def wide(v):  # a value a row, over a group's lanes
            return jnp.broadcast_to(v, (rows, bk))[None]

        def over_groups(step, init):
            """``step(acc, keys, positions)`` folded over the groups the
            context reaches, lane by lane: the lanes meet once a pass."""
            def one(g, acc):
                return step(acc, key_scr[pl.ds(g * group, group)],
                            g * group * bk + in_group)

            return lax.fori_loop(0, n_groups, one, init)

        zeros = jnp.zeros((rows, bk), jnp.int32)

        def count(pred):
            return jnp.sum(over_groups(
                lambda acc, k, p: acc + jnp.sum(
                    pred(k, p).astype(jnp.int32), axis=0), zeros),
                axis=1, keepdims=True)

        # the topk-th largest key, from its sign down: the largest T with
        # count(key >= T) >= topk
        lo = jnp.where(count(lambda k, p: k >= 0) >= topk, 0, INT_MIN)

        def value_bit(i, lo):
            cand = lo + jnp.left_shift(jnp.int32(1), 30 - i)
            at = wide(cand)
            return jnp.where(count(lambda k, p: k >= at) >= topk, cand, lo)

        lo = lax.fori_loop(0, 31, value_bit, lo)
        thr = wide(lo)

        def ties(acc, k, p):  # keys above it, its ties, the last of them
            above, equal, last = acc
            tied = k == thr
            return (above + jnp.sum((k > thr).astype(jnp.int32), axis=0),
                    equal + jnp.sum(tied.astype(jnp.int32), axis=0),
                    jnp.maximum(last, jnp.max(jnp.where(tied, p, -1), axis=0)))

        above, equal, last = over_groups(
            ties, (zeros, zeros, jnp.full((rows, bk), -1, jnp.int32)))
        need = topk - jnp.sum(above, axis=1, keepdims=True)  # of its ties
        equal = jnp.sum(equal, axis=1, keepdims=True)
        last = jnp.max(last, axis=1, keepdims=True)
        # such a row keeps its whole context (a padded one has no scores)
        free = (qpos < topk) | (row >= nn)

        def write(tie):
            thr_ref[0] = jnp.broadcast_to(
                jnp.where(free, INT_MIN, lo), thr_ref.shape[1:])
            tie_ref[0] = jnp.broadcast_to(
                jnp.where(free, 2 ** 31 - 1, tie), tie_ref.shape[1:])

        # a row all of whose ties are needed takes them up to the last one
        write(last)

        @pl.when(jnp.sum(jnp.where((equal == need) | free, 0, 1)) > 0)
        def _tie_search():  # some row's ties exceed its need
            def pos_bit(i, at):  # the largest p: count(tie, pos < p) < need
                cand = at + jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
                end = wide(cand)
                n = count(lambda k, p: (k == thr) & (p < end))
                return jnp.where(n < need, cand, at)

            write(lax.fori_loop(0, pos_bits, pos_bit,
                                jnp.zeros((rows, 1), jnp.int32)))


def selection_tiles(cache_len, num_new, S: int, topk: int, kpool: int = 1):
    """bool [B, S / SELECT_ROWS] (numpy or jax, as the frontiers are): the
    programs of :func:`select_topk`'s grid that search; the others write
    "everything" and fetch no score."""
    rows = min(SELECT_ROWS, S)
    return _searches(cache_len[:, None], num_new[:, None],
                     np.arange(0, S, rows)[None], rows, topk, kpool)


def select_topk(scores, cache_len, num_new, topk: int,
                interpret: Optional[bool] = None, kpool: int = 1):
    """The selection of every row of ``scores`` (as :func:`index_scores`
    lays them out) as (threshold key, tie position), int32 [B,S] each: row
    ``i`` of slot ``b`` (at position ``cache_len[b] + i``) sees token ``s``
    at or before it iff ``key(score) > thr or (key(score) == thr and
    s <= tie)``, which are its ``topk`` best, ties to the lower position (all
    of them inside ``topk`` tokens). See :func:`_sort_key` for ``key``.
    ``kpool`` > 1: ``scores`` are of blocks of that many tokens, ``s`` and
    ``tie`` count blocks, and the row sees block ``s`` iff its last token
    ``kpool s + kpool - 1`` is at or before the row. A row past ``num_new``
    keeps everything, as a row inside ``topk`` does."""
    B, NB, S, bk = scores.shape
    rows = min(SELECT_ROWS, S)
    tiles = S // rows
    group = min(SELECT_BLOCKS, NB)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cl, nn = _frontiers(B, S, cache_len, num_new)
    # where a program that searches nothing parks its block of scores: on
    # the one the last searching program before it fetched, else on the one
    # the next will, so the pipeline finds the block it holds or needs next
    # and an idle tile moves no score
    prog = jnp.arange(B * tiles, dtype=jnp.int32)
    live = selection_tiles(cl, nn, S, int(topk), kpool).reshape(-1)
    upto = prog[None, :] <= prog[:, None]
    before = jnp.max(jnp.where(upto & live[None, :], prog[None, :], -1), 1)
    after = jnp.min(jnp.where(~upto & live[None, :], prog[None, :],
                              B * tiles), 1)
    at = jnp.where(before >= 0, before, after % (B * tiles))  # (none: 0)

    def parked(b, t, cl, nn, at):
        p = at[b * tiles + t]
        return p // tiles, 0, p % tiles, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, tiles),
        in_specs=[pl.BlockSpec((1, NB, rows, bk), parked)],
        out_specs=[pl.BlockSpec((1, rows, LANES), lambda b, t, *_: (b, t, 0)),
                   pl.BlockSpec((1, rows, LANES), lambda b, t, *_: (b, t, 0))],
        scratch_shapes=[pltpu.VMEM((NB, rows, bk), jnp.int32)],
    )
    thr, tie = pl.pallas_call(
        functools.partial(_selection_kernel, topk=int(topk), rows=rows,
                          group=group, pos_bits=(NB * bk).bit_length(),
                          kpool=kpool),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, S, LANES), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="selection_topk",
    )(cl, nn, at, scores)
    return thr[:, :, 0], tie[:, :, 0]


# ------------------------------------------------------------ attention
def _sparse_attention_kernel(pt_ref, cl_ref, nn_ref, layer_ref, q_ref, *refs,
                             scale, page_size, pages_per_block, heads,
                             rows, v_width, selected: bool = True,
                             kpool: int = 1):
    """``selected``: the operands hold a selection (scores, threshold, tie)
    between the queries and the pool; without one every key at or before a
    query is attended. ``kpool`` > 1: the selection is of blocks of that
    many tokens (a score block holds the blocks of one block of keys), and
    the tokens after a row's last whole block are always attended."""
    s_ref = thr_ref = tie_ref = None
    if selected:
        s_ref, thr_ref, tie_ref, *refs = refs
    kv_hbm, o_ref, kv_buf, sems, m_scr, l_scr, acc_scr = refs
    ps, ppb = page_size, pages_per_block
    bk = ps * ppb
    mp = pt_ref.shape[1]
    b, t = pl.program_id(0), pl.program_id(1)
    cl, nn, layer = cl_ref[b], nn_ref[b], layer_ref[0]
    r0 = t * rows
    start_fetch, wait_fetch = _page_fetch(pt_ref, kv_hbm, kv_buf, sems, b,
                                          layer, ps, ppb, mp)

    @pl.when(r0 >= nn)
    def _padding():  # rows no token stands in: keep them finite
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(r0 < nn)
    def _attend():
        # keys this tile's last real row needs: 0 .. cl + min(nn, r0+rows)-1
        n_blocks = jnp.minimum(
            pl.cdiv(cl + jnp.minimum(nn, r0 + rows), bk),
            pl.cdiv(mp * ps, bk))
        qpos = cl + r0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        if selected:
            thr, tie = thr_ref[0, :, :1], tie_ref[0, :, :1]
        if kpool > 1:
            whole = (qpos + 1) // kpool  # blocks at or before the row
            # block c of a key block -> its kpool tokens
            spread = (lax.broadcasted_iota(jnp.int32, (bk // kpool, bk), 1)
                      // kpool == lax.broadcasted_iota(
                          jnp.int32, (bk // kpool, bk), 0)
                      ).astype(jnp.float32)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        start_fetch(0, 0)

        def block(i, carry):
            slot = lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start_fetch(i + 1, 1 - slot)

            wait_fetch(slot)
            q = q_ref[0]
            kv = kv_buf[slot].astype(q.dtype)
            pos = i * bk + lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
            chosen = pos <= qpos
            if kpool > 1:
                key = _sort_key(s_ref[0, i])
                blk = i * (bk // kpool) + lax.broadcasted_iota(
                    jnp.int32, (rows, bk // kpool), 1)
                best = (blk < whole) & (
                    (key > thr) | ((key == thr) & (blk <= tie)))
                chosen &= (jnp.dot(best.astype(jnp.float32), spread,
                                   preferred_element_type=jnp.float32) > 0.5
                           ) | (pos >= whole * kpool)
            elif selected:
                key = _sort_key(s_ref[0, i])
                chosen &= (key > thr) | ((key == thr) & (pos <= tie))
            # the heads of a query share its row of the selection
            _tile_update(
                q, kv, kv[:, :v_width], None, None, i * bk, None, scale,
                m_scr, l_scr, acc_scr,
                allowed=jnp.broadcast_to(
                    chosen[:, None, :], (rows, heads, bk)
                ).reshape(rows * heads, bk))
            return carry

        lax.fori_loop(0, n_blocks, block, 0)
        o_ref[0] = _normalized(l_scr, acc_scr).astype(o_ref.dtype)


def sparse_attention(q_abs, kv_pool, scores, thr, tie, cache_len, page_table,
                     *, layer, scale: float, v_width: int, num_new=None,
                     interpret: Optional[bool] = None, kpool: int = 1):
    """Absorbed queries ``q_abs`` [B,S,H,W] against the chosen latent rows of
    ``kv_pool`` [L,P+1,ps,W] (key: the whole row; value: its first
    ``v_width`` lanes). ``scores``/``thr``/``tie`` as :func:`select_topk`
    gives them; all three None (:func:`latent_attention`): no selection,
    every key at or before a query. Returns [B,S,H,v_width]; a tile of rows
    wholly past ``num_new`` is zeros, padded rows beside real ones are
    finite. ``kpool`` > 1: the selection is by block of that many tokens
    (``scores`` [B, blocks, S, block_k / kpool])."""
    B, S, H, W = q_abs.shape
    ps, mp = kv_pool.shape[2], page_table.shape[1]
    ppb = _block_pages(BLOCK_K, ps, mp)
    selected = scores is not None
    bk = scores.shape[3] * kpool if selected else ps * ppb
    rows = min(ATTN_ROWS, S)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cl, nn = _frontiers(B, S, cache_len, num_new)

    def lanes(a):
        return jnp.broadcast_to(a[:, :, None], (B, S, LANES))

    selection, selection_specs = (), []
    if selected:
        selection = (scores, lanes(thr), lanes(tie))
        selection_specs = [
            pl.BlockSpec((1, scores.shape[1], rows, scores.shape[3]),
                         lambda b, t, *_: (b, 0, t, 0)),
            pl.BlockSpec((1, rows, LANES), lambda b, t, *_: (b, t, 0)),
            pl.BlockSpec((1, rows, LANES), lambda b, t, *_: (b, t, 0)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B, S // rows),
        in_specs=[
            pl.BlockSpec((1, rows * H, W), lambda b, t, *_: (b, t, 0)),
            *selection_specs,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows * H, v_width),
                               lambda b, t, *_: (b, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bk, W), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows * H, LANES), jnp.float32),
            pltpu.VMEM((rows * H, LANES), jnp.float32),
            pltpu.VMEM((rows * H, v_width), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _sparse_attention_kernel, scale=float(scale), page_size=ps,
            pages_per_block=ppb, heads=H, rows=rows, v_width=v_width,
            selected=selected, kpool=kpool),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S * H, v_width), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sparse_latent_attention" if selected else "latent_attention",
    )(jnp.asarray(page_table, jnp.int32), cl, nn,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_abs.reshape(B, S * H, W), *selection, kv_pool)
    return out.reshape(B, S, H, v_width)


def latent_attention(q_abs, kv_pool, cache_len, page_table, *, layer,
                     scale: float, v_width: int, num_new=None,
                     interpret: Optional[bool] = None
                     ) -> Tuple[Optional[jax.Array], List[str]]:
    """Latent attention WITHOUT a selection: the walk of
    :func:`sparse_attention` over every key of each slot's context, prompt
    chunks and decode rows alike (the device call is named
    ``latent_attention``). Returns ``(out [B,S,H,v_width], [])``, or
    ``(None, reasons)`` when the operands are not the kernel's (the caller
    takes the dense lines)."""
    interp = interpret if interpret is not None else (
        jax.default_backend() != "tpu")
    reasons = kernel_reasons(q_abs, None, kv_pool, None, page_table, interp)
    if reasons:
        from ...utils.logging import log_fallback_once

        log_fallback_once("latent_attention", reasons)
        return None, reasons
    return sparse_attention(
        q_abs, kv_pool, None, None, None, cache_len, page_table, layer=layer,
        scale=scale, v_width=v_width, num_new=num_new,
        interpret=interp), reasons


def kernel_reasons(q_abs, q_idx, kv_pool, ki_pool, page_table,
                   interpret: bool, kpool: int = 1) -> List[str]:
    """Why the kernels cannot take these operands ([] = they can);
    ``q_idx`` / ``ki_pool`` None: no indexer."""
    from ...models.sharding import current_topology

    B, S = q_abs.shape[:2]
    reasons = []
    topo = current_topology()
    if topo is not None and topo.world_size > 1:
        reasons.append("a mesh of several devices (one latent serves every "
                       "head: the kernels are written for one device)")
    if kv_pool.dtype not in (jnp.bfloat16, jnp.float32):
        reasons.append(f"{jnp.dtype(kv_pool.dtype).name} latent pool")
    if S % SELECT_ROWS or S % ATTN_ROWS:
        reasons.append(f"a chunk of {S} rows is not whole 8-row tiles")
    if kpool > 1:
        bk = kv_pool.shape[2] * _block_pages(BLOCK_K, kv_pool.shape[2],
                                             page_table.shape[1])
        if bk != POOLED_BLOCK_K * kpool or POOLED_BLOCK_K % ki_pool.shape[2]:
            reasons.append(
                f"a block of {bk} keys is not {POOLED_BLOCK_K} blocks of "
                f"{kpool} tokens in whole pages of {ki_pool.shape[2]}")
    if not interpret:
        for what, pool in (("latent row", kv_pool), ("indexer key", ki_pool)):
            width = 0 if pool is None else pool.shape[-1]
            if width % LANES:
                reasons.append(f"{what} of {width} is not {LANES}-aligned")
        if B * page_table.shape[1] * 4 > SMEM_TABLE_BYTES:
            reasons.append(
                f"a [{B}, {page_table.shape[1]}] page table is over the "
                f"{SMEM_TABLE_BYTES >> 10} KiB of SMEM it may take")
    return reasons


def latent_sparse_attention(q_abs, q_idx, w_idx, kv_pool, ki_pool, cache_len,
                            page_table, *, layer, topk: int, scale: float,
                            v_width: int, num_new=None,
                            interpret: Optional[bool] = None, kpool: int = 1
                            ) -> Tuple[Optional[jax.Array], List[str]]:
    """Scores, selection and attention of one layer through the kernels.
    Returns ``(out [B,S,H,v_width], [])``, or ``(None, reasons)`` when the
    operands are not theirs (the caller takes the dense lines). ``kpool`` >
    1: ``ki_pool`` holds one key a block of that many tokens and ``topk``
    counts blocks."""
    interp = interpret if interpret is not None else (
        jax.default_backend() != "tpu")
    reasons = kernel_reasons(q_abs, q_idx, kv_pool, ki_pool, page_table,
                             interp, kpool)
    if reasons:
        from ...utils.logging import log_fallback_once

        log_fallback_once("latent_sparse_attention", reasons)
        return None, reasons
    kw = dict(num_new=num_new, interpret=interp)
    if kpool > 1:
        view, counting = pooled_view(ki_pool, page_table, layer)
        scores = index_scores(q_idx, w_idx, view, cache_len, counting,
                              layer=0, block_k=POOLED_BLOCK_K, kpool=kpool,
                              **kw)
    else:
        scores = index_scores(q_idx, w_idx, ki_pool, cache_len, page_table,
                              layer=layer, **kw)
    thr, tie = select_topk(scores, cache_len, num_new, topk, interpret=interp,
                           kpool=kpool)
    return sparse_attention(
        q_abs, kv_pool, scores, thr, tie, cache_len, page_table, layer=layer,
        scale=scale, v_width=v_width, kpool=kpool, **kw), reasons


POOLED_BLOCK_K = 128  # pooled keys a score block (and a "page" of the view)


def pooled_view(ki_pool, page_table, layer):
    """The pooled index keys of every slot as the scoring kernel reads them:
    (a pool ``[1, B x blocks, POOLED_BLOCK_K, Di]`` in which slot ``b``'s
    keys lie one after the other, gathered through ``page_table`` from
    ``ki_pool[layer]`` ``[P+1, keys a page, Di]``; the table ``[B, blocks]``
    that counts its pages). The table is padded to whole blocks by its last
    entry: those keys lie past every row's frontier."""
    B, mp = page_table.shape
    per = ki_pool.shape[2]
    pages = POOLED_BLOCK_K // per  # pages a block of the view
    table = jnp.pad(page_table, ((0, 0), (0, -mp % pages)), mode="edge")
    view = lax.dynamic_index_in_dim(ki_pool, layer, 0, False)[table]
    blocks = table.shape[1] // pages
    return (view.reshape(1, B * blocks, POOLED_BLOCK_K, ki_pool.shape[-1]),
            jnp.arange(B * blocks, dtype=jnp.int32).reshape(B, blocks))


# ---------------------------------------------------------- dense lines
def unblocked(scores):
    """The kernels' ``[B, blocks, S, block_k]`` scores as ``[B, S, N]``."""
    B, NB, S, bk = scores.shape
    return scores.swapaxes(1, 2).reshape(B, S, NB * bk)


def dense_index_scores(q_idx, w_idx, ki_view):
    """[B,S,Hi,Di] x [B,N,Di] -> float32 [B,S,N]."""
    s = jnp.einsum("bshd,bnd->bshn", q_idx.astype(jnp.float32),
                   ki_view.astype(jnp.float32))
    return jnp.sum(jnp.maximum(s, 0.0)
                   * w_idx.astype(jnp.float32)[..., None], axis=2)


def dense_selection(scores, qpos, topk: int):
    """bool [B,S,N]: the ``topk`` best tokens at or before each row's
    position ``qpos`` [B,S] (all of them inside ``topk``), ties to the lower
    position (``lax.top_k`` is stable)."""
    N = scores.shape[-1]
    seen = jnp.arange(N)[None, None, :] <= qpos[..., None]
    # (a sort tells -0.0 from 0.0; a comparison, and the kernel, do not)
    scores = jnp.where(scores == 0.0, 0.0, scores)
    _, idx = lax.top_k(jnp.where(seen, scores, -jnp.inf), min(topk, N))
    B, S = scores.shape[:2]
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], idx
    ].set(True)
    return chosen & seen


def last_block(qpos, kpool: int):
    """The last block of ``kpool`` tokens a row at ``qpos`` sees whole: the
    one whose last token is at or before it (-1: none yet)."""
    return (qpos + 1) // kpool - 1


def tokens_of_blocks(blocks, qpos, kpool: int):
    """bool [B,S,N x kpool]: the tokens a row at ``qpos`` [B,S] attends
    under the selection ``blocks`` [B,S,N] of blocks of ``kpool`` tokens
    (:func:`dense_selection` at :func:`last_block`): those of the chosen
    blocks, and always the tokens after its last whole block."""
    pos = jnp.arange(blocks.shape[-1] * kpool)[None, None, :]
    tail = pos >= ((qpos + 1) // kpool * kpool)[..., None]
    return (jnp.repeat(blocks, kpool, axis=-1) | tail) & (
        pos <= qpos[..., None])


def dense_sparse_attention(q_abs, kv_view, chosen, scale: float,
                           v_width: int):
    """[B,S,H,W] x [B,N,W] under ``chosen`` [B,S,N] -> float32
    [B,S,H,v_width]."""
    kv = kv_view.astype(jnp.float32)
    s = jnp.einsum("bshw,bnw->bshn", q_abs.astype(jnp.float32), kv) * scale
    p = jax.nn.softmax(jnp.where(chosen[:, :, None, :], s, -1e30), axis=-1)
    return jnp.einsum("bshn,bnv->bshv", p, kv[..., :v_width])
