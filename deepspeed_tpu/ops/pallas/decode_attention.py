"""Pallas cached-KV decode attention (single-token step).

Parity: csrc/transformer/inference attention kernels (the latency-critical
decode matvec). The XLA fallback (models/decoding.py) expands the GQA cache
to fp32 [B,Smax,H,hd] every step; this kernel streams the cache in its
storage dtype, one [block_s, hd] tile per grid step, with fp32 online
softmax in VMEM and per-tile predication that skips blocks beyond the
current cache length — so a 64-token cache in a 4096-slot buffer does 1/64
of the work.

Layouts: q [B, KV, G, hd] (G = H/KV query heads per cache head — the GQA
group shares one cache tile), k/v cache [B, Smax, KV, hd] (the engine's
storage layout; no transpose on the hot path). cache_len rides in SMEM.

``_tile_update`` and ``_normalized`` are the online softmax of every
cached-KV kernel: the two decode kernels here, paged_attention's chunk
kernel and the three selected walks (sparse_paged_attention,
sparse_latent_attention, block_sparse_attention). The running max and sum
live in [rows, LANES] float32 scratches with every lane of a row equal and
are read, updated and written back WHOLE; they meet the [rows, block]
scores and the [rows, width] accumulator through flash_attention's
``_lanes_to`` (whole vregs tiled where the width is a multiple of the
lanes, a lane prefix where it is narrower). Kept as [rows, 1] columns they
cost a lane broadcast at every use on every key tile (PERF.md, PR 33 for
the flash forward and PR 61 for these kernels).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _lanes_to


LANES = 128
NEG_INF = -1e30
DEFAULT_BLOCK_S = 256


def _tile_update(q, k, v, ks, vs, start, cl, scale, m_scr, l_scr, acc_scr,
                 lo=None, allowed=None):
    """One [block_s, hd] K/V tile's contribution to the fp32 online
    softmax (shared by the dense and paged kernels and the sparse walks):
    dequantize when scales ride along, mask past the row's frontier, fold
    into the running (max, sum, acc) scratches. ``cl`` is the frontier: a
    scalar, a per-(row, key) array (paged_attention's chunk rows), or None
    for a tile wholly below every row's frontier. ``lo`` (with ``cl``; a
    window layer) is the last key position a row no longer sees.
    ``allowed`` (a per-(row, key) bool array; a learned selection) masks by
    itself.

    ``m_scr`` / ``l_scr`` are [rows, LANES], every lane of a row the same
    value, and stay so from scratch to scratch: the running max, its guard
    and the correction are computed on whole vregs (a row reduction
    broadcasts into them once) and meet the [rows, block_s] scores and the
    [rows, width] accumulator through ``_lanes_to``, never as a [rows, 1]
    column that every use would broadcast across the lanes again."""
    if ks is not None:
        # int8 cache: dequantize the tile with its per-token scales
        k = (k.astype(jnp.float32) * ks[:, :1]).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs[:, :1]).astype(q.dtype)
    elif k.dtype != q.dtype:
        # mixed storage (kv_cache_dtype="bf16" on an fp32 engine): the
        # MXU matmul needs matching operand dtypes
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, block_s]
    if cl is not None:
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = kpos <= cl
        if lo is not None:
            seen = seen & (kpos > lo)
        s = jnp.where(seen, s, NEG_INF)
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)

    m_prev = m_scr[...]  # [G, LANES], lane-replicated
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # rows with no visible key yet keep m = NEG_INF: guard the exp
    m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(s - _lanes_to(m_safe, s.shape[1]))
    corr = jnp.exp(m_prev - m_safe)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * _lanes_to(corr, acc_scr.shape[1]) + (
        jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    )
    m_scr[...] = m_new


def _normalized(l_scr, acc_scr):
    """The accumulator over its running sum ([rows, LANES], lane-replicated;
    a row that saw no key has sum 0 and stays 0), float32."""
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return acc_scr[...] / _lanes_to(l_safe, acc_scr.shape[1])


def _decode_kernel(*refs, scale, block_s, has_scales=False):
    if has_scales:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, cl_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, cl_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    si = pl.program_id(2)
    ns = pl.num_programs(2)
    # this batch row's new-token position == its cached-token count (the
    # cl operand is the whole per-row [B] vector in SMEM; the grid's b
    # axis picks the row)
    cl = cl_ref[pl.program_id(0)]

    @pl.when(si == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = si * block_s

    @pl.when(start <= cl)  # skip tiles entirely past the live cache
    def _body():
        _tile_update(
            q_ref[0, 0], k_ref[0], v_ref[0],
            ks_ref[0, 0] if has_scales else None,
            vs_ref[0, 0] if has_scales else None,
            start, cl, scale, m_scr, l_scr, acc_scr,
        )

    @pl.when(si == ns - 1)
    def _finalize():
        o_ref[0, 0] = _normalized(l_scr, acc_scr).astype(o_ref.dtype)


def _paged_decode_kernel(*refs, scale, page_size, has_scales=False):
    """Paged twin of :func:`_decode_kernel`: the grid's third axis walks a
    slot's LOGICAL pages; the page table rides as a scalar-prefetch
    operand so the BlockSpec index maps fetch each physical K/V page
    directly from the pool — no per-slot contiguous view ever
    materializes in HBM. Per-row frontier predication is unchanged
    (logical position = si * page_size + offset)."""
    if has_scales:
        (pt_ref, cl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (pt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
        ks_ref = vs_ref = None
    del pt_ref  # consumed by the index maps
    b = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)
    cl = cl_ref[b]

    @pl.when(si == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = si * page_size

    @pl.when(start <= cl)  # pages past the frontier are unmapped — skip
    def _body():
        _tile_update(
            q_ref[0, 0], k_ref[0], v_ref[0],
            ks_ref[0, 0] if has_scales else None,
            vs_ref[0, 0] if has_scales else None,
            start, cl, scale, m_scr, l_scr, acc_scr,
        )

    @pl.when(si == ns - 1)
    def _finalize():
        o_ref[0, 0] = _normalized(l_scr, acc_scr).astype(o_ref.dtype)


def _pick_block(S: int, preferred: int) -> Optional[int]:
    for cand in (preferred, 512, 256, 128):
        if cand <= S and S % cand == 0:
            return cand
    return S if S % 8 == 0 else None


def decode_attention_kernel(q, k_cache, v_cache, cache_len, *,
                            k_scale=None, v_scale=None,
                            block_s: int = DEFAULT_BLOCK_S,
                            interpret: Optional[bool] = None):
    """q [B,1,H,hd] new-token queries vs k/v_cache [B,Smax,KV,hd].

    cache_len: int32 scalar — or a per-row [B] vector for ragged serving
    slot batches — the new token's position (tokens already cached).
    Returns [B,1,H,hd]. Caller guarantees the new token's k/v are already
    written at ``cache_len``. int8 caches pass per-token scales in the
    storage layout [B,KV,Smax,SCALE_LANES]; dequant happens on the tile
    in VMEM.
    """
    B, one, H, hd = q.shape
    assert one == 1, "decode kernel is single-token"
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    bs = _pick_block(Smax, block_s)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / (hd**0.5)
    qg = q.reshape(B, KV, G, hd)
    # per-row [B] in SMEM: scalars broadcast so every row predicates on
    # the same frontier, serving batches bring one frontier per slot
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,))
    ns = Smax // bs
    has_scales = k_scale is not None

    # The TPU lowering requires each block's last-two dims to be (8,128)-
    # divisible or equal to the array dims, so a per-head [bs, hd] tile of a
    # [B, Smax, KV, hd] cache is illegal (head block 1 < KV). Instead view
    # the cache as [B, Smax, KV*hd] — a free contiguous reshape — and slice
    # head kv as the hd-wide column block at index kv, which is lane-aligned
    # whenever hd % 128 == 0 (or KV == 1, where the block spans the row).
    operands = [
        qg,
        k_cache.reshape(B, Smax, KV * hd),
        v_cache.reshape(B, Smax, KV * hd),
    ]
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), lambda b, kv, si: (b, kv, 0, 0)),
        pl.BlockSpec((1, bs, hd), lambda b, kv, si: (b, si, kv)),
        pl.BlockSpec((1, bs, hd), lambda b, kv, si: (b, si, kv)),
    ]
    if has_scales:
        # scales arrive pre-transposed as [B, KV, Smax, SL] (the cache's
        # storage layout — see models/decoding.init_cache), giving a legal
        # (bs, SL) trailing block (SL equals the array dim) with no
        # per-token relayout on the decode path
        SL = k_scale.shape[-1]
        operands += [k_scale, v_scale]
        in_specs += [
            pl.BlockSpec((1, 1, bs, SL), lambda b, kv, si: (b, kv, si, 0)),
            pl.BlockSpec((1, 1, bs, SL), lambda b, kv, si: (b, kv, si, 0)),
        ]
    # whole-array operand: a per-row (1, 1) block of a [B, 1] SMEM array
    # is refused by the chip's compiler
    operands.append(cl)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, block_s=bs, has_scales=has_scales
        ),
        grid=(B, KV, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, kv, si: (b, kv, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, 1, H, hd)


def paged_decode_attention_kernel(q, k_pool, v_pool, cache_len, page_table,
                                  *, k_scale=None, v_scale=None,
                                  interpret: Optional[bool] = None):
    """q [B,1,H,hd] new-token queries vs a block-paged KV pool
    k/v_pool [P+1, page_size, KV, hd] addressed through per-slot page
    tables [B, max_pages] (int32 physical page per logical page; unmapped
    entries point at the NULL page and are predicated off by the
    frontier). ``cache_len`` is the per-row [B] frontier. The page table
    and frontier ride as scalar-prefetch operands
    (pltpu.PrefetchScalarGridSpec) so the block index maps gather each
    K/V page straight from the pool — the paged analogue of vLLM's
    block-table attention, per-row online softmax unchanged. int8 pools
    pass per-token scales [P+1, KV, page_size, SCALE_LANES].
    """
    B, one, H, hd = q.shape
    assert one == 1, "paged decode kernel is single-token"
    P1, ps, KV = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    mp = page_table.shape[1]
    G = H // KV
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / (hd**0.5)
    qg = q.reshape(B, KV, G, hd)
    pt = jnp.asarray(page_table, jnp.int32)
    cl = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,)
    )
    has_scales = k_scale is not None

    # flat head-column view of the pool (same lane-alignment contract as
    # the dense kernel); a (1, ps, hd) block's trailing dims equal the
    # array dims, so any 8-aligned page_size tiles legally
    operands = [
        qg,
        k_pool.reshape(P1, ps, KV * hd),
        v_pool.reshape(P1, ps, KV * hd),
    ]
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), lambda b, kv, si, pt, cl: (b, kv, 0, 0)),
        pl.BlockSpec((1, ps, hd),
                     lambda b, kv, si, pt, cl: (pt[b, si], 0, kv)),
        pl.BlockSpec((1, ps, hd),
                     lambda b, kv, si, pt, cl: (pt[b, si], 0, kv)),
    ]
    if has_scales:
        SL = k_scale.shape[-1]
        operands += [k_scale, v_scale]
        in_specs += [
            pl.BlockSpec((1, 1, ps, SL),
                         lambda b, kv, si, pt, cl: (pt[b, si], kv, 0, 0)),
            pl.BlockSpec((1, 1, ps, SL),
                         lambda b, kv, si, pt, cl: (pt[b, si], kv, 0, 0)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, cache_len
        grid=(B, KV, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, kv, si, pt, cl: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, page_size=ps,
            has_scales=has_scales,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pt, cl, *operands)
    return out.reshape(B, 1, H, hd)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     k_scale=None, v_scale=None, page_table=None,
                     interpret: Optional[bool] = None):
    """Shard-map-aware wrapper: cache heads over tp, batch over dp/fsdp —
    mirrors flash_attention's serving layout. Returns None if the shapes
    don't fit the kernel (caller falls back to the XLA matvec).

    ``page_table`` [B, max_pages] switches to the block-paged form:
    k/v_cache are then page POOLS [P+1, page_size, KV, hd] (int8 scales
    [P+1, KV, page_size, SL]) and the kernel gathers pages through the
    table instead of streaming a contiguous per-slot region."""
    from ...models.sharding import current_topology

    B, one, H, hd = q.shape
    paged = page_table is not None
    if paged:
        ps, KV = k_cache.shape[1], k_cache.shape[2]
        Smax = page_table.shape[1] * ps
    else:
        Smax, KV = k_cache.shape[1], k_cache.shape[2]
    topo = current_topology()
    distributed = topo is not None and topo.world_size > 1
    tp = topo.tp_size if distributed else 1
    interp = interpret if interpret is not None else (
        jax.default_backend() != "tpu"
    )
    reasons = []
    if one != 1:
        reasons.append(f"{one} query tokens (kernel is single-token)")
    if H % KV != 0:
        reasons.append(f"H={H} not a multiple of KV={KV}")
    if hd % 8 != 0:
        reasons.append(f"head_dim {hd} not 8-aligned")
    if paged and ps % 8 != 0:
        reasons.append(f"page_size {ps} not 8-aligned")
    if not paged and _pick_block(Smax, DEFAULT_BLOCK_S) is None:
        reasons.append(f"cache length {Smax} has no 8-aligned block")
    if not interp and hd % LANES != 0 and KV // max(tp, 1) != 1:
        # the flat head-column view needs lane-aligned per-head offsets on
        # the real TPU lowering (interpret mode has no such constraint)
        reasons.append(
            f"head_dim {hd} not {LANES}-aligned with {KV // max(tp, 1)} "
            "local cache heads"
        )
    if distributed and (H % tp != 0 or KV % tp != 0):
        reasons.append(f"H={H}/KV={KV} not divisible by tp={tp}")
    elif distributed and (H // tp) % max(KV // tp, 1) != 0:
        reasons.append(f"GQA group uneven under tp={tp}")
    if reasons:
        from ...utils.logging import log_fallback_once

        log_fallback_once("decode_attention", reasons)
        return None

    if not distributed:
        if paged:
            return paged_decode_attention_kernel(
                q, k_cache, v_cache, cache_len, page_table,
                k_scale=k_scale, v_scale=v_scale, interpret=interp,
            )
        return decode_attention_kernel(
            q, k_cache, v_cache, cache_len,
            k_scale=k_scale, v_scale=v_scale, interpret=interp,
        )

    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("dp", "fsdp") if topo.sizes[a] > 1)
    b_ax = batch_axes if batch_axes else None
    h_ax = "tp" if tp > 1 else None
    has_scales = k_scale is not None
    if paged:
        # page pools are slot-agnostic: heads over tp, pages replicated;
        # the table and frontier ride with the (slot) batch
        kv_spec = P(None, None, h_ax, None)
        scale_spec = P(None, h_ax, None, None)
        q_spec = P(b_ax, None, h_ax, None)
    else:
        kv_spec = P(b_ax, None, h_ax, None)
        scale_spec = P(b_ax, h_ax, None, None)
        q_spec = P(b_ax, None, h_ax, None)
    operands = [q, k_cache, v_cache]
    in_specs = [q_spec, kv_spec, kv_spec]
    if has_scales:
        # dense scales are [B, KV, Smax, SL] (head dim 1 follows tp);
        # paged scales [P+1, KV, ps, SL] shard the same head dim
        operands += [k_scale, v_scale]
        in_specs += [scale_spec, scale_spec]
    # the frontier rides as a per-row [B] vector sharded with the batch
    # (a scalar cache_len broadcasts — every shard sees the same value)
    operands.append(jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (B,)
    ))
    in_specs.append(P(b_ax))
    if paged:
        operands.append(jnp.asarray(page_table, jnp.int32))
        in_specs.append(P(b_ax, None))

    def body(q, kc, vc, *rest):
        rest = list(rest)
        pt = rest.pop() if paged else None
        if has_scales:
            ks, vs, cl = rest
        else:
            (cl,) = rest
            ks = vs = None
        if paged:
            return paged_decode_attention_kernel(
                q, kc, vc, cl, pt,
                k_scale=ks, v_scale=vs, interpret=interp,
            )
        return decode_attention_kernel(
            q, kc, vc, cl, k_scale=ks, v_scale=vs, interpret=interp
        )

    return jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=tuple(in_specs),
        out_specs=P(b_ax, None, h_ax, None),
        check_vma=False,
    )(*operands)
