"""Pallas grouped-query attention over a learned selection of paged K / V.

Parity: the lightning indexer of DeepSeek's sparse attention as
Keye-VL-2.0-30B-A3B carries it (``sa_config``): the indexer scores every
cached token for a query from ONE index key a token, and every head of the
query attends the query's ``topk`` best tokens inside the K and V of its own
KV group. Three calls a layer for the one ``[max_slots, token_budget]`` step
the serving engine compiles:

``indexer_scores`` / ``selection_topk``  of ``sparse_latent_attention.py``, as
    they are: they read an index pool ``[L, P+1, page_size, width]`` through
    the page table and know nothing of what the selection is for. The index
    key is stored a whole 128-lane row wide (a narrower one, 64 values here,
    is padded with zeros by the caller, and so are the index queries: the
    dot products are the same and a page's keys stay one tile of a DMA).
``sparse_paged_attention``  the walk of ``paged_attention.py`` with the
    selection's ``(scores, thr, tie)`` as its mask. One program a slot; the
    K and V blocks of ALL KV heads come in once a slot by async copy (whole
    pages, the pool's own layout), double-buffered, with the block's score
    rows beside them; inside a block a loop over ROW TILES that follows the
    slot's real rows (a decoding slot computes one tile, a prompt chunk as
    many as its rows fill) forms the tile's mask once and folds it into
    every KV head's online softmax, the ``G`` query heads of a KV head
    stacked as ``[rows * G, hd]`` against one ``[block_k, hd]`` K tile. The
    walk reads every block of the slot's context and masks what the
    selection left out: its work follows the context, not ``topk`` (a walk
    over a compacted list of the chosen rows is the open follow-up, as for
    the latent form).

:func:`dense_sparse_paged_attention` is the same attention in plain
``jax.numpy`` over a gathered per-slot view: the path of a CPU engine and the
oracle of the kernel's tests.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import LANES, NEG_INF, _normalized, _tile_update
from .paged_attention import (SMEM_TABLE_BYTES, VMEM_BUDGET_BYTES,
                              VMEM_LIMIT_BYTES, _block_pages, _frontiers,
                              _head_tiles)
from .sparse_latent_attention import (BLOCK_K, SELECT_ROWS, _sort_key,
                                      index_scores, select_topk)

# query rows a tile of the walk: what a slot with one real row (a decoding
# one) computes a key block, each row ``G`` stacked rows of every KV head.
# At 8 query heads a KV head under [4, 128] (three slots decoding beside a
# 125-row chunk, contexts of 24 k; my chip run, PR 58, PERF.md section 6) a
# call took 1.87 / 1.35 / 1.91 / 2.09 / 2.62 ms at 8 / 16 / 32 / 64 / 128
# rows, and with four slots decoding 0.75 / 0.78 ms at 8 / 16
ROW_TILE = 16


def _sparse_paged_kernel(pt_ref, cl_ref, nn_ref, layer_ref, q_ref, thr_ref,
                         tie_ref, s_hbm, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                         s_buf, sems, ssems, kh_scr, vh_scr, m_scr, l_scr,
                         acc_scr, *, scale, page_size, pages_per_block,
                         group, rows):
    KV, SG, hd = q_ref.shape[1:]
    ps, ppb = page_size, pages_per_block
    bk = ps * ppb
    mp = pt_ref.shape[1]
    b = pl.program_id(0)
    cl, nn, layer = cl_ref[b], nn_ref[b], layer_ref[0]

    def page_copies(slot, j, page):
        dst = pl.ds(j * ps, ps)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[slot, dst], sems.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[slot, dst], sems.at[1, slot]),
        )

    def score_copy(slot, blk, t):
        at = pl.ds(pl.multiple_of(t * rows, rows), rows)
        return pltpu.make_async_copy(
            s_hbm.at[b, blk, at], s_buf.at[slot, at], ssems.at[slot])

    @pl.when(nn == 0)
    def _idle():  # nothing scheduled: the rows are padding, kept finite
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(nn > 0)
    def _attend():
        n_blocks = jnp.minimum(pl.cdiv(cl + nn, bk), pl.cdiv(mp * ps, bk))
        n_tiles = pl.cdiv(nn, rows)

        def start_fetch(blk, slot):
            def page(j, c):
                # pages past the table re-read its last entry: those keys
                # lie past every row's frontier
                for copy in page_copies(
                        slot, j, pt_ref[b, jnp.minimum(blk * ppb + j, mp - 1)]):
                    copy.start()
                return c

            def tile(t, c):
                score_copy(slot, blk, t).start()
                return c

            lax.fori_loop(0, ppb, page, 0)
            lax.fori_loop(0, n_tiles, tile, 0)

        def wait_fetch(slot):
            def page(j, c):
                for copy in page_copies(slot, j, 0):
                    copy.wait()
                return c

            def tile(t, c):
                score_copy(slot, 0, t).wait()
                return c

            lax.fori_loop(0, ppb, page, 0)
            lax.fori_loop(0, n_tiles, tile, 0)

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
            # head-major copies of the block (paged_attention's)
            for kv, tile in _head_tiles(k_buf.at[slot], KV):
                kh_scr[kv] = tile
            for kv, tile in _head_tiles(v_buf.at[slot], KV):
                vh_scr[kv] = tile

            def tile(t, c):
                r0 = pl.multiple_of(t * rows, rows)
                at = pl.ds(r0, rows)
                qpos = cl + r0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                pos = i * bk + lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
                key = _sort_key(s_buf[slot, at, :])
                thr, tie = thr_ref[0, at, :1], tie_ref[0, at, :1]
                chosen = (pos <= qpos) & (
                    (key > thr) | ((key == thr) & (pos <= tie)))
                # the heads of a query share its row of the selection
                allowed = jnp.broadcast_to(
                    chosen[:, None, :], (rows, group, bk)
                ).reshape(rows * group, bk)
                stacked = pl.ds(pl.multiple_of(r0 * group, rows * group),
                                rows * group)
                for kv in range(KV):
                    _tile_update(
                        q_ref[0, kv, stacked, :], kh_scr[kv], vh_scr[kv],
                        None, None, i * bk, None, scale,
                        m_scr.at[kv, stacked], l_scr.at[kv, stacked],
                        acc_scr.at[kv, stacked], allowed=allowed)
                return c

            lax.fori_loop(0, n_tiles, tile, 0)
            return carry

        lax.fori_loop(0, n_blocks, block, 0)

        def finish(kv, c):
            o_ref[0, kv] = _normalized(
                l_scr.at[kv], acc_scr.at[kv]).astype(o_ref.dtype)
            return c

        lax.fori_loop(0, KV, finish, 0)


def row_tile(S: int, rows: int = ROW_TILE) -> int:
    """Query rows a tile of the walk takes: the most, at most ``rows``, that
    divide the chunk into sublane-whole tiles."""
    return next(r for r in range(min(rows, S), 0, -1)
                if S % r == 0 and (r % 8 == 0 or r == S))


def _vmem_bytes(S, G, KV, hd, bk, rows, q_bytes, kv_bytes) -> int:
    """What one program keeps in VMEM: the (m, l, acc) scratches of every KV
    head over the whole chunk, the double-buffered K, V and score blocks
    with K and V's head-major copies, the pipelined q, out, thr and tie
    blocks, and a tile's [rows * G, block_k] float32 temporaries."""
    SG = S * G
    scratch = KV * SG * (2 * LANES + hd) * 4
    kv_bufs = 3 * 2 * bk * KV * hd * kv_bytes
    q_out = 2 * 2 * KV * SG * hd * q_bytes + 2 * 2 * S * LANES * 4
    scores = 2 * S * bk * 4
    temps = 6 * rows * G * bk * 4
    return scratch + kv_bufs + q_out + scores + temps


def sparse_paged_attention_kernel(q, k_pool, v_pool, scores, thr, tie,
                                  cache_len, page_table, *, layer,
                                  num_new=None, rows: int = ROW_TILE,
                                  interpret: Optional[bool] = None):
    """q [B,S,H,hd] chunk queries against the chosen keys of layer ``layer``
    of the K / V pool stacks [L, P+1, page_size, KV, hd] through
    ``page_table`` [B, max_pages]. ``scores`` float32 [B, blocks, S,
    block_k] and ``thr`` / ``tie`` int32 [B, S] as
    ``sparse_latent_attention.index_scores`` / ``select_topk`` give them:
    row ``i`` of slot ``b`` attends token ``s <= cache_len[b] + i`` iff
    ``key(score) > thr or (key(score) == thr and s <= tie)``, in every head,
    each head over its KV group's K and V. The chunk's own keys are already
    in the pools. Returns [B,S,H,hd]; the rows of a slot with none real are
    zeros, padded rows beside real ones finite."""
    B, S, H, hd = q.shape
    ps, KV = k_pool.shape[2], k_pool.shape[3]
    mp = page_table.shape[1]
    G = H // KV
    ppb = _block_pages(BLOCK_K, ps, mp)
    bk = ps * ppb
    if scores.shape[2:] != (S, bk):
        raise ValueError(f"scores {scores.shape} are not [B, blocks, {S}, "
                         f"{bk}]: the walk reads a score block a key block")
    rows = row_tile(S, rows)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cl, nn = _frontiers(B, S, cache_len, num_new)
    # the G query heads of a KV head stack beside each query: [S * G, hd]
    qg = q.reshape(B, S, KV, G, hd).swapaxes(1, 2).reshape(B, KV, S * G, hd)

    def lanes(a):
        return jnp.broadcast_to(a[:, :, None], (B, S, LANES))

    whole = lambda b, *_: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # page_table, cache_len, num_new, layer
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KV, S * G, hd), whole),
            pl.BlockSpec((1, S, LANES), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, S, LANES), lambda b, *_: (b, 0, 0)),
            # the scores and the pool stacks stay in HBM: a block's score
            # rows and whole pages come in by async copy
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KV, S * G, hd), whole),
        scratch_shapes=[
            pltpu.VMEM((2, bk, KV, hd), k_pool.dtype),
            pltpu.VMEM((2, bk, KV, hd), v_pool.dtype),
            pltpu.VMEM((2, S, bk), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((KV, bk, hd), k_pool.dtype),
            pltpu.VMEM((KV, bk, hd), v_pool.dtype),
            pltpu.VMEM((KV, S * G, LANES), jnp.float32),
            pltpu.VMEM((KV, S * G, LANES), jnp.float32),
            pltpu.VMEM((KV, S * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _sparse_paged_kernel, scale=1.0 / (hd ** 0.5), page_size=ps,
            pages_per_block=ppb, group=G, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, S * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="sparse_paged_attention",
    )(jnp.asarray(page_table, jnp.int32), cl, nn,
      jnp.asarray(layer, jnp.int32).reshape(1), qg, lanes(thr), lanes(tie),
      scores, k_pool, v_pool)
    return out.reshape(B, KV, S, G, hd).swapaxes(1, 2).reshape(B, S, H, hd)


def kernel_reasons(q, k_pool, ki_pool, page_table,
                   interpret: bool) -> List[str]:
    """Why the three calls cannot take these operands ([] = they can)."""
    from ...models.sharding import current_topology

    B, S, H, hd = q.shape
    ps, KV = k_pool.shape[2], k_pool.shape[3]
    mp = page_table.shape[1]
    reasons = []
    topo = current_topology()
    if topo is not None and topo.world_size > 1:
        reasons.append("a mesh of several devices (a query's selection "
                       "serves every head: the kernels are written for one "
                       "device)")
    if H % KV:
        reasons.append(f"H={H} not a multiple of KV={KV}")
    if k_pool.dtype not in (jnp.bfloat16, jnp.float32):
        reasons.append(f"{jnp.dtype(k_pool.dtype).name} KV pool")
    elif k_pool.dtype == jnp.bfloat16 and KV > 1 and KV % 2:
        reasons.append(f"{KV} KV heads do not pair in bf16")
    if S % SELECT_ROWS:
        reasons.append(f"a chunk of {S} rows is not whole 8-row tiles")
    if not interpret:
        sublanes = KV * jnp.dtype(k_pool.dtype).itemsize // 4
        if hd % LANES:
            reasons.append(f"head_dim {hd} not {LANES}-aligned")
        if sublanes not in (1, 2, 4) and (sublanes == 0 or sublanes % 8):
            reasons.append(
                f"{KV} KV heads in {jnp.dtype(k_pool.dtype).name} do not "
                "fill a sublane tile")
        if ki_pool.shape[-1] % LANES:
            reasons.append(f"indexer key of {ki_pool.shape[-1]} is not "
                           f"{LANES}-aligned")
        if B * mp * 4 > SMEM_TABLE_BYTES:
            reasons.append(
                f"a [{B}, {mp}] page table is over the "
                f"{SMEM_TABLE_BYTES >> 10} KiB of SMEM it may take")
    if not reasons and H % KV == 0:
        need = _vmem_bytes(S, H // KV, KV, hd, ps * _block_pages(
            BLOCK_K, ps, mp), row_tile(S), jnp.dtype(q.dtype).itemsize,
            jnp.dtype(k_pool.dtype).itemsize)
        if need > VMEM_BUDGET_BYTES:
            reasons.append(
                f"a [{S} x {H // KV}]-row chunk of {KV} KV heads needs "
                f"{need >> 20} MiB of VMEM (budget "
                f"{VMEM_BUDGET_BYTES >> 20} MiB)")
    return reasons


def indexed_paged_attention(q, q_idx, w_idx, k_pool, v_pool, ki_pool,
                            cache_len, page_table, *, layer, topk: int,
                            num_new=None, interpret: Optional[bool] = None
                            ) -> Tuple[Optional[jax.Array], List[str]]:
    """Scores, selection and attention of one layer through the kernels:
    ``q`` [B,S,H,hd], the rotated index queries ``q_idx`` [B,S,Hi,W] and
    head weights ``w_idx`` [B,S,Hi] (``W`` the index pool's row, zeros past
    the indexer's own width). Returns ``(out [B,S,H,hd], [])``, or ``(None,
    reasons)`` when the operands are not theirs (the caller takes the dense
    lines)."""
    interp = interpret if interpret is not None else (
        jax.default_backend() != "tpu")
    reasons = kernel_reasons(q, k_pool, ki_pool, page_table, interp)
    if reasons:
        from ...utils.logging import log_fallback_once

        log_fallback_once("sparse_paged_attention", reasons)
        return None, reasons
    kw = dict(num_new=num_new, interpret=interp)
    scores = index_scores(q_idx, w_idx, ki_pool, cache_len, page_table,
                          layer=layer, **kw)
    thr, tie = select_topk(scores, cache_len, num_new, topk, interpret=interp)
    return sparse_paged_attention_kernel(
        q, k_pool, v_pool, scores, thr, tie, cache_len, page_table,
        layer=layer, **kw), reasons


def dense_sparse_paged_attention(q, k_view, v_view, chosen) -> jax.Array:
    """q [B,S,H,hd] x per-slot views [B,N,KV,hd] under ``chosen`` [B,S,N]
    (a query's selection, every head's) -> float32 [B,S,H,hd]."""
    B, S, H, hd = q.shape
    KV = k_view.shape[2]
    qg = q.astype(jnp.float32).reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bskgd,bnkd->bskgn", qg,
                   k_view.astype(jnp.float32)) / (hd ** 0.5)
    p = jax.nn.softmax(
        jnp.where(chosen[:, :, None, None, :], s, -1e30), axis=-1)
    return jnp.einsum("bskgn,bnkd->bskgd", p,
                      v_view.astype(jnp.float32)).reshape(B, S, H, hd)
