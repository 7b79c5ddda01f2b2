"""Pallas flash attention for TPU.

Parity: the reference's fused attention CUDA kernels (csrc/transformer and
DeepSpeed-inference attention). TPU-native design: online-softmax tiling in
VMEM with fp32 accumulators, causal block predication, GQA via block-index
mapping (no materialized KV repeat), and a two-kernel backward (dq; dk/dv)
recomputing logits from the saved logsumexp — standard FlashAttention-2
structure on the MXU.

In-kernel masking (r3):
- **segment_ids** (packed sequences): q ids ride lane-broadcast [B,S,LANES],
  kv ids sublane-broadcast [B,SUBLANES,S], so the [bq,bk] same-segment mask
  is two VMEM broadcasts and never a relayout.
- **ALiBi** (BLOOM): per-head slope in SMEM; under ``causal`` the bias
  -slope*(qpos-kpos) is a [bq,1] column plus a [1,bk] row, two broadcast
  adds a score; the [B,H,S,S] bias tensor is never materialized in HBM.
  A score near the diagonal carries the rounding of slope*block_q (about
  1.5e-5 at 512 for a slope that is no power of two), not of its own small
  product: see :func:`_mask_and_bias`.
- **Causal**: positions are a [bq,1] column and a [1,bk] row compared by
  broadcast on every visible tile; tiles wholly above the diagonal
  (:func:`_block_visible`) are no grid step at all: the grid is
  (B, H, live tiles), one flat list of the (q-block, k-block) pairs a call
  can see (:func:`flat_walk`). One body a kernel: a bare body for the
  tiles wholly below the diagonal was built and measured (PR 33) and earned
  under 1 ms of a 437 ms step, so it is not here.
- **Block-sparse layouts** (``block_mask``, ops/sparse_attention.py) ride the
  same walk: the causal triangle is a static layout like any other. What
  has no static layout or needs every tile keeps the dense grid
  (B, H, nq, nk) with the in-kernel predicate: ring hops with traced offsets
  (ring_flash.py), a dense ``bias`` (its dbias paths), non-causal calls.
- **sp composition**: under a DS-Ulysses mesh the kernel shard_maps heads
  over ("tp","sp") — the all-to-alls happen outside (parallel/sequence.py),
  the kernel itself always sees full sequence.

Layouts: q [B, S, H, D] (model layout); kernels run on [B, H, S, D].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Measured on one v5e chip, the kernels alone (PR 51's chip runs, PERF.md
# section 6): [4, 16, 2048, D] bf16, causal, ALiBi, 512x512 tiles, device ms
# a call at D 64 | 128: fwd 0.79 | 0.82, dq 0.99 | 1.00, dk/dv 1.27 | 1.25
# (0.90 | 0.96, 1.21 | 1.25, 1.50 | 1.50 while a (batch, head) walked 16
# grid steps for its 10 visible tiles: PR 33). On those 16-step grids
# 1024-wide tiles gained 0-8 % and Mosaic compiled them three times as long;
# 256-wide tiles lose a fifth to a half. So 512x512 stays, for every kernel.
# The time does not follow D: a 64-deep contraction occupies the 128 x 128
# MXU as long as a 128-deep one. What a tile costs beside its matmuls is
# cross-lane work on [bq, 1] columns, not the mask arithmetic: see
# _fwd_kernel.
# _pick_block degrades to 256/128 automatically when 512 doesn't divide S.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# Backward (dq/dkv) tile overrides; 0 = inherit the forward sizes. The two
# bwd kernels have different operand mixes than the fwd (extra do/lse/delta
# streams, f32 accumulator scratch), so their best tile shape need not be
# the fwd's — a sweep dimension, not a guess.
DEFAULT_BLOCK_Q_BWD = 0
DEFAULT_BLOCK_K_BWD = 0
LANES = 128  # segment-id lane broadcast (TPU tiling of the [bq,bk] mask)
SUBLANES = 8
# lse/delta ride HBM with only SUBLANES redundant copies instead of a full
# 128-lane broadcast: at S=2048/H=8 that saves ~2% of step HBM traffic
# (67MB -> 4MB per tensor per layer-call); kernels only read column 0.
AUX_LANES = 8
NEG_INF = -1e30


def _block_visible(qi, ki, block_q, block_k, qoff=0, koff=0):
    """Causal predicate: does q-block qi see any key in k-block ki?

    qoff/koff globalize the positions when q and kv are blocks of a longer
    sequence (ring attention hops); they may be traced scalars — the
    predicate then evaluates in-kernel instead of at trace time."""
    return qi * block_q + block_q - 1 + qoff >= ki * block_k + koff


def causal_layout(S, block_q, block_k):
    """The lower block triangle a causal call can see, [nq, nk] bool: the
    in-kernel predicate over the block grid (one source of truth)."""
    import numpy as np

    qi = np.arange(S // block_q)[:, None]
    ki = np.arange(S // block_k)[None, :]
    return _block_visible(qi, ki, block_q, block_k)


# One grid step of a static layout's walk, packed in an int32:
# qi << 17 | ki << 3 | live << 2 | last << 1 | first.
_WALK_FIRST, _WALK_LAST, _WALK_LIVE = 1, 2, 4
_WALK_KI_SHIFT, _WALK_QI_SHIFT = 3, 17
_WALK_MAX_BLOCKS = 1 << (_WALK_QI_SHIFT - _WALK_KI_SHIFT)
# The list rides scalar prefetch, so it has to fit the scalar memory beside
# whatever else a kernel keeps there: the chip's compiler (a forward for the
# described v5e, as tests/test_tpu_compile.py compiles) took a list of
# 128 Ki entries and refused 256 Ki ("Used 1.00M of 1.00M smem"), so the
# bound is an eighth of that memory. The causal triangle at 512-wide tiles
# reaches it past S 128 Ki; a block-sparse layout at 128-wide blocks at
# S 64 Ki if an eighth of its tiles are live.
WALK_MAX_STEPS = 32 * 1024


def flat_walk(layout, *, by_col=False):
    """[nq, nk] 0/1 layout -> int32 [steps]: the live tiles in the order a
    kernel visits them, one packed word a grid step.

    Forward and dq walk row-major (a q-block's k-blocks ascending); dk/dv
    walks ``by_col`` (a k-block's q-blocks ascending). ``first`` / ``last``
    mark the ends of a run over one output block: the accumulators are
    zeroed at the first and written out at the last. An output block with no
    live tile keeps one entry, first and last and not live, so it is still
    written (zeros; lse NEG_INF). The causal triangle has no such block and
    its kernels carry no run predicate at all. This is the block-sparse
    lookup table of the reference's triton sdd/dsd kernels: a masked tile is
    neither fetched nor a grid step."""
    import numpy as np

    live = np.asarray(layout) != 0
    if max(live.shape) > _WALK_MAX_BLOCKS:
        raise ValueError(f"layout {live.shape} has more than "
                         f"{_WALK_MAX_BLOCKS} blocks a side")
    if by_col:
        live = live.T
    placed = live.copy()
    placed[~live.any(axis=1), 0] = True  # the empty runs' one dead entry
    run, other = np.nonzero(placed)  # row-major: runs in order, ascending
    edge = run[1:] != run[:-1]
    first, last = np.r_[True, edge], np.r_[edge, True]
    qi, ki = (other, run) if by_col else (run, other)
    return (qi << _WALK_QI_SHIFT | ki << _WALK_KI_SHIFT
            | live[run, other] * _WALK_LIVE | last * _WALK_LAST
            | first * _WALK_FIRST).astype(np.int32)


def _walk_bound(layout):
    """No walk of the layout, in either order, has more grid steps."""
    import numpy as np

    layout = np.asarray(layout)
    return int(np.count_nonzero(layout)) + max(layout.shape)


def walk_steps(layout, *, by_col=False):
    """(grid steps, live tiles) a (batch, head) of a static layout's walk:
    equal unless an output block has no live tile. What the grids of the
    lowered program are checked against (tests/test_tpu_compile.py)."""
    word = flat_walk(layout, by_col=by_col)
    return len(word), int(((word & _WALK_LIVE) != 0).sum())


def _mask_and_bias(s, rel, block_q, block_k, *, causal, seg_q, seg_k, slope,
                   dense=None):
    """Apply causal + segment masks and ALiBi/dense bias to a [bq, bk] fp32
    score tile. ``rel`` is the tile's first query position minus its first
    key position (a scalar, global: ring-hop offsets included); positions
    are a [bq, 1] column and a [1, bk] row, broadcast, never two [bq, bk]
    arrays.

    seg_q: [bq, 1] | None; seg_k: [1, bk] | None; slope: scalar | None;
    dense: [bq, bk] fp32 additive bias tile | None.

    Causal ALiBi is a column plus a row, not a product a score: each term is
    rounded at its own size, so a score near the diagonal is off by about one
    ulp of slope*block_q (1.5e-5 at 512 for a slope that is no power of two,
    as half of BLOOM-560m's sixteen are), far under the bf16 operands' noise;
    tests/test_flash_attention.py holds it to 1e-4."""
    if dense is not None:
        s = s + dense
    if slope is not None or causal:
        # query position relative to the tile's first key / key column
        qrel = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0) + rel
        kcol = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    if slope is not None:
        if causal:
            # on visible keys |qpos - kpos| = qrel - kcol: two broadcast adds
            # a score (the masked keys are overwritten below)
            s = (s - slope * qrel.astype(jnp.float32)
                 + slope * kcol.astype(jnp.float32))
        else:
            s = s - slope * jnp.abs(qrel - kcol).astype(jnp.float32)
    if causal:
        s = jnp.where(qrel >= kcol, s, NEG_INF)
    if seg_q is not None:
        s = jnp.where(seg_q == seg_k, s, NEG_INF)
    return s


def _lanes_to(x, width):
    """A lane-replicated [rows, LANES] column as [rows, width], without a
    lane broadcast where the width allows (a multiple or a prefix of the
    lanes: tiling whole vregs and slicing lanes off are free)."""
    if width % LANES == 0:
        return jnp.tile(x, (1, width // LANES))
    if width <= LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _parse_refs(refs, *, has_seg, has_alibi, has_bias=False, has_offsets=False,
                flat=False):
    """Split a kernel's ([walk_ref], in_refs..., out_refs..., scratch...)
    positional refs; the scalar-prefetch walk leads on the flat grid."""
    walk_ref = None
    if flat:
        walk_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
    i = 3
    seg_q_ref = seg_k_ref = slopes_ref = bias_ref = offsets_ref = None
    if has_bias:
        bias_ref = refs[i]
        i += 1
    if has_seg:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if has_alibi:
        slopes_ref = refs[i]
        i += 1
    if has_offsets:
        offsets_ref = refs[i]  # SMEM (1,2): [qoff, koff]
        i += 1
    extra = refs[i:]
    return (walk_ref, q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slopes_ref,
            bias_ref, offsets_ref, extra)


def _walk_blocks(word):
    """(qi, ki) of a packed walk word (see :func:`flat_walk`)."""
    return (word >> _WALK_QI_SHIFT,
            (word >> _WALK_KI_SHIFT) & (_WALK_MAX_BLOCKS - 1))


def _tile_step(walk_ref, dead, *, causal, block_q, block_k, swap, qoff=0,
               koff=0):
    """Decode this grid step: (first, last, run predicate, rel).

    On the flat grid (B, H, steps) ``walk_ref[step]`` names the tile and
    says whether it opens and closes its output block's run; every step is a
    tile the call can see, so the predicate is the Python ``True`` unless
    the layout has ``dead`` entries (an output block with no live tile). On
    the dense grid (B, H, row, other) the run is the last axis (``swap``:
    the row is the k-block, dk/dv) and causal visibility is tested a step,
    from the block indices and the ring-hop offsets (dynamic when those are
    traced; a walk never combines with offsets — enforced at entry).
    ``rel`` is the tile's first query position minus its first key position,
    what :func:`_mask_and_bias` places its column and row by."""
    if walk_ref is not None:
        word = walk_ref[pl.program_id(2)]
        qi, ki = _walk_blocks(word)
        first = (word & _WALK_FIRST) != 0
        last = (word & _WALK_LAST) != 0
        ok = (word & _WALK_LIVE) != 0 if dead else True
    else:
        row, step = pl.program_id(2), pl.program_id(3)
        qi, ki = (step, row) if swap else (row, step)
        first, last = step == 0, step == pl.num_programs(3) - 1
        ok = (_block_visible(qi, ki, block_q, block_k, qoff, koff)
              if causal else True)
    rel = qi * block_q + qoff - (ki * block_k + koff)
    return first, last, ok, rel


def _when(pred, body):
    """Run ``body`` under ``pred``; a static ``True`` is no test at all."""
    if pred is True:
        body()
    else:
        pl.when(pred)(body)


def _tile_index_maps(flat, swap=False):
    """(qi_of, ki_of): a grid step's q- and k-block index from what an index
    map receives after (b, h) — (step, walk_ref) on the flat grid, the two
    block axes on the dense one (``swap``: k-block first, dk/dv)."""
    if flat:
        return (lambda t, walk: _walk_blocks(walk[t])[0],
                lambda t, walk: _walk_blocks(walk[t])[1])
    if swap:
        return (lambda x, y: y), (lambda x, y: x)
    return (lambda x, y: x), (lambda x, y: y)


def _offs(offsets_ref):
    """(qoff, koff) from the SMEM offsets operand; (0, 0) when absent."""
    if offsets_ref is None:
        return 0, 0
    return offsets_ref[0, 0], offsets_ref[0, 1]


def _head_slope(slopes_ref, head):
    """This head's ALiBi slope from the whole-array [H] SMEM operand (a
    per-head (1,1) block of an [H,1] array is refused by the chip's
    compiler); None when the kernel has no ALiBi."""
    return slopes_ref[head] if slopes_ref is not None else None


def _tile_mask_args(seg_q_ref, seg_k_ref, bias_ref=None):
    seg_q = seg_q_ref[0][:, :1] if seg_q_ref is not None else None  # [bq,1]
    seg_k = seg_k_ref[0][:1, :] if seg_k_ref is not None else None  # [1,bk]
    # bias stays in its storage dtype in HBM (no fp32 shadow copy of a
    # [*,*,S,S] tensor); the [bq,bk] tile upcasts in VMEM
    dense = (
        bias_ref[0, 0].astype(jnp.float32) if bias_ref is not None else None
    )
    return seg_q, seg_k, dense


# -----------------------------------------------------------------------------
# forward
# -----------------------------------------------------------------------------
def _fwd_kernel(*refs, scale, causal, block_q, block_k, has_seg, has_alibi,
                flat=False, dead=False, has_bias=False, has_offsets=False):
    (walk_ref, q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slopes_ref,
     bias_ref, offsets_ref, extra) = (
        _parse_refs(refs, has_seg=has_seg, has_alibi=has_alibi,
                    has_bias=has_bias, has_offsets=has_offsets, flat=flat)
    )
    o_ref, lse_ref, m_scr, l_scr, acc_scr = extra
    qoff, koff = _offs(offsets_ref)
    slope = _head_slope(slopes_ref, pl.program_id(1))
    # flat grid: a step is one tile this q-row sees, its k-blocks in order;
    # dense grid: causal skips blocks fully above the diagonal (dynamic when
    # the blocks carry ring-hop position offsets)
    first, last, should_run, rel = _tile_step(
        walk_ref, dead, causal=causal, block_q=block_q, block_k=block_k,
        swap=False, qoff=qoff, koff=koff,
    )

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _body():
        # keep operands in input dtype (bf16 → full MXU rate), accumulate fp32
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bk, d]
        v = v_ref[0, 0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk] fp32
        seg_q, seg_k, dense = _tile_mask_args(seg_q_ref, seg_k_ref, bias_ref)
        s = _mask_and_bias(
            s, rel, block_q, block_k, causal=causal, seg_q=seg_q, seg_k=seg_k,
            slope=slope, dense=dense,
        )

        # running max / sum stay lane-replicated [bq, LANES]: the row
        # reductions broadcast into them once, and everything after reads
        # whole vregs (tiling a replicated column is free, a [bq, 1] column
        # costs a lane broadcast every use)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # rows with no visible key yet keep m=-inf; exp guard against inf-inf
        m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)
        p = jnp.exp(s - _lanes_to(m_safe, block_k))  # [bq, bk]
        corr = jnp.exp(m_prev - m_safe)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _lanes_to(corr, acc_scr.shape[1]) + (
            jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        m_scr[...] = m_new

    _when(should_run, _body)

    @pl.when(last)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (
            acc_scr[...] / _lanes_to(l_safe, acc_scr.shape[1])
        ).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[...] + jnp.log(l_safe))
        lse_ref[0, 0] = lse[:, :AUX_LANES]


def _mask_specs(has_seg, has_alibi, block_q, block_k, qi_of, ki_of, *,
                bias_bh=None, has_offsets=False):
    """BlockSpecs for the optional mask/bias operands.

    qi_of / ki_of: the grid's :func:`_tile_index_maps`.
    bias_bh: (Bb, Hb) of the dense-bias operand (each 1 → broadcast), or
    None when there is no dense bias.
    has_offsets: a (1,2) SMEM [qoff, koff] position-offset operand rides
    along (ring attention hops)."""
    specs = []
    if bias_bh is not None:
        Bb, Hb = bias_bh
        specs.append(
            pl.BlockSpec(
                (1, 1, block_q, block_k),
                lambda b, h, *g: (b if Bb > 1 else 0, h if Hb > 1 else 0,
                                  qi_of(*g), ki_of(*g)),
            )
        )
    if has_seg:
        specs.append(
            pl.BlockSpec((1, block_q, LANES),
                         lambda b, h, *g: (b, qi_of(*g), 0))
        )
        specs.append(
            pl.BlockSpec((1, SUBLANES, block_k),
                         lambda b, h, *g: (b, 0, ki_of(*g)))
        )
    if has_alibi:
        # the whole [H] slopes vector, indexed by head inside the kernel
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if has_offsets:
        specs.append(
            pl.BlockSpec((1, 2), lambda b, h, *g: (0, 0),
                         memory_space=pltpu.SMEM)
        )
    return specs


def _broadcast_segment_ids(segment_ids, S):
    """[B,S] int32 → (q-side [B,S,LANES], kv-side [B,SUBLANES,S]).

    A (q_ids, kv_ids) pair is accepted for the ring-attention hops, where
    the local q block and the visiting kv block come from different chunks
    of the global sequence."""
    if isinstance(segment_ids, tuple):
        sq_ids, sk_ids = segment_ids
    else:
        sq_ids = sk_ids = segment_ids
    sq_ids = sq_ids.astype(jnp.int32)
    sk_ids = sk_ids.astype(jnp.int32)
    seg_q = jax.lax.broadcast_in_dim(sq_ids, (*sq_ids.shape, LANES), (0, 1))
    seg_k = jax.lax.broadcast_in_dim(
        sk_ids, (sk_ids.shape[0], SUBLANES, sk_ids.shape[1]), (0, 2)
    )
    return seg_q, seg_k


def _grid_call(kernel, rows, in_specs, out_specs, out_shape, scratch_shapes,
               operands, walk, interpret):
    """One pallas_call over (B, H, len(walk)) with the walk as its scalar
    prefetch, or over the dense (B, H, *rows) when there is no walk."""
    B, H = operands[0].shape[:2]
    if walk is None:
        return pl.pallas_call(
            kernel,
            grid=(B, H, *rows),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
            ),
            interpret=interpret,
        )(*operands)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, len(walk)), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(walk), *operands)


def _as_walk(walk):
    """(int32 vector | None, has it a dead entry) of a static walk."""
    import numpy as np

    if walk is None:
        return None, False
    walk = np.asarray(walk, np.int32)
    return walk, bool(((walk & _WALK_LIVE) == 0).any())


def _flash_fwd(q, k, v, bias, seg, slopes, tables, offsets=None, *, causal,
               scale, block_q, block_k, interpret):
    """``tables``: the layout's row-major :func:`flat_walk` (static), or
    None for the dense grid."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    nq, nk = pl.cdiv(S, block_q), pl.cdiv(S, block_k)
    has_seg, has_alibi = seg is not None, slopes is not None
    has_bias, has_offsets = bias is not None, offsets is not None
    walk, dead = _as_walk(tables)
    qi_of, ki_of = _tile_index_maps(walk is not None)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, has_seg=has_seg, has_alibi=has_alibi,
        flat=walk is not None, dead=dead, has_bias=has_bias,
        has_offsets=has_offsets,
    )
    operands = [q, k, v]
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, h, *g: (b, h, qi_of(*g), 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, *g: (b, h // group, ki_of(*g), 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, *g: (b, h // group, ki_of(*g), 0)),
    ]
    if has_bias:
        operands.append(bias)
    if has_seg:
        seg_q, seg_k = _broadcast_segment_ids(seg, S)
        operands += [seg_q, seg_k]
    if has_alibi:
        operands.append(slopes.astype(jnp.float32))
    if has_offsets:
        operands.append(offsets)
    in_specs += _mask_specs(has_seg, has_alibi, block_q, block_k, qi_of,
                            ki_of, has_offsets=has_offsets,
                            bias_bh=bias.shape[:2] if has_bias else None)
    out, lse = _grid_call(
        kernel,
        (nq, nk),
        in_specs,
        [
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, *g: (b, h, qi_of(*g), 0)),
            pl.BlockSpec((1, 1, block_q, AUX_LANES),
                         lambda b, h, *g: (b, h, qi_of(*g), 0)),
        ],
        [
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, AUX_LANES), jnp.float32),
        ],
        [
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        operands,
        walk,
        interpret,
    )
    return out, lse


# -----------------------------------------------------------------------------
# backward
# -----------------------------------------------------------------------------
def _recompute_p_dp(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slope,
                    bias_ref, do_ref, lse_ref, delta_ref, rel, *, scale,
                    causal, block_q, block_k):
    """The backward kernels' shared logit recompute: returns
    (p [bq,bk] fp32, dp [bq,bk] fp32, delta [bq,1] fp32, do, q, k, v).
    ONE definition so dq, dk/dv, and dbias can never desynchronize; ``rel``
    places the tile, see :func:`_mask_and_bias`."""
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, :1]  # [bq, 1]
    delta = delta_ref[0, 0][:, :1]  # [bq, 1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    seg_q, seg_k, dense = _tile_mask_args(seg_q_ref, seg_k_ref, bias_ref)
    s = _mask_and_bias(
        s, rel, block_q, block_k, causal=causal, seg_q=seg_q, seg_k=seg_k,
        slope=slope, dense=dense,
    )
    p = jnp.exp(s - lse)  # fully-masked rows: lse=NEG_INF → guard below
    p = jnp.where(s <= NEG_INF, 0.0, p)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return p, dp, delta, do, q, k, v


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, has_seg, has_alibi,
                   flat=False, dead=False, has_bias=False, emit_dbias=False,
                   has_offsets=False):
    (walk_ref, q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slopes_ref,
     bias_ref, offsets_ref, extra) = (
        _parse_refs(refs, has_seg=has_seg, has_alibi=has_alibi,
                    has_bias=has_bias, has_offsets=has_offsets, flat=flat)
    )
    if emit_dbias:
        do_ref, lse_ref, delta_ref, dq_ref, dbias_ref, dq_scr = extra
    else:
        do_ref, lse_ref, delta_ref, dq_ref, dq_scr = extra
        dbias_ref = None
    qoff, koff = _offs(offsets_ref)
    slope = _head_slope(slopes_ref, pl.program_id(1))
    first, last, should_run, rel = _tile_step(
        walk_ref, dead, causal=causal, block_q=block_q, block_k=block_k,
        swap=False, qoff=qoff, koff=koff,
    )

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body():
        p, dp, delta, do, q, k, v = _recompute_p_dp(
            q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slope, bias_ref,
            do_ref, lse_ref, delta_ref, rel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        dst = p * (dp - delta)  # dL/d(logits): bias sees it unscaled
        if dbias_ref is not None:
            dbias_ref[0, 0] = dst.astype(dbias_ref.dtype)
        ds = dst * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when(should_run, _body)

    if dbias_ref is not None and should_run is not True:
        # every tile of the dbias output must be written, including the
        # causally-skipped ones (a dense bias rides the dense grid)
        @pl.when(jnp.logical_not(should_run))
        def _zero_dbias():
            dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, has_seg, has_alibi,
                    flat=False, dead=False, has_bias=False, has_offsets=False):
    (walk_ref, q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slopes_ref,
     bias_ref, offsets_ref, extra) = (
        _parse_refs(refs, has_seg=has_seg, has_alibi=has_alibi,
                    has_bias=has_bias, has_offsets=has_offsets, flat=flat)
    )
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = extra
    qoff, koff = _offs(offsets_ref)
    slope = _head_slope(slopes_ref, pl.program_id(1))
    first, last, should_run, rel = _tile_step(
        walk_ref, dead, causal=causal, block_q=block_q, block_k=block_k,
        swap=True, qoff=qoff, koff=koff,
    )

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _body():
        p, dp, delta, do, q, k, v = _recompute_p_dp(
            q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slope, bias_ref,
            do_ref, lse_ref, delta_ref, rel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, d]

    _when(should_run, _body)

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bias_grad_kernel(*refs, scale, causal, block_q, block_k, has_seg,
                      has_alibi, B, H, Bb, Hb):
    """dbias for a *broadcast* bias ([1,H,S,S], [B,1,S,S], or [1,1,S,S]).

    Grid (nq, nk, B*H): the broadcast dim(s) iterate innermost so each
    output tile accumulates in VMEM scratch and is written exactly once —
    peak dbias memory is the bias's own shape, never [B,H,S,S] (a T5-style
    shared rel-pos bias would otherwise pay a B× fp32 blow-up in backward).
    Recomputes the two logit matmuls; that trade (2 extra tile matmuls vs
    a [B,H,S,S] HBM tensor) is the bandwidth-bound-friendly direction."""
    (_, q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slopes_ref,
     bias_ref, _offsets_unused, extra) = (
        _parse_refs(refs, has_seg=has_seg, has_alibi=has_alibi, has_bias=True)
    )
    do_ref, lse_ref, delta_ref, dbias_ref, scr = extra
    qi, ki, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # broadcast dim innermost (see _bias_grad_index below)
    if Bb == 1:
        inner, inner_n = t % B, B          # b sweeps fastest
        if Hb == 1:
            inner, inner_n = t, B * H      # everything accumulates
        head = t // B
    else:  # (B, 1): h sweeps fastest
        inner, inner_n = t % H, H
        head = t % H
    slope = _head_slope(slopes_ref, head)

    @pl.when(inner == 0)
    def _init():
        scr[:] = jnp.zeros_like(scr)

    should_run = _block_visible(qi, ki, block_q, block_k) if causal else True
    rel = qi * block_q - ki * block_k

    def _body():
        p, dp, delta, _, _, _, _ = _recompute_p_dp(
            q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, slope, bias_ref,
            do_ref, lse_ref, delta_ref, rel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        scr[:] += p * (dp - delta)

    _when(should_run, _body)

    @pl.when(inner == inner_n - 1)
    def _write():
        dbias_ref[0, 0] = scr[:].astype(dbias_ref.dtype)


def _bias_grad_call(q, k, v, bias, seg, slopes, do, lse, delta, *,
                    causal, scale, block_q, block_k, interpret, group):
    """pallas_call wrapper for :func:`_bias_grad_kernel` (dense bias never
    composes with a block-sparse layout — enforced at the public entry)."""
    B, H, S, D = q.shape
    Bb, Hb = bias.shape[:2]
    nq, nk = pl.cdiv(S, block_q), pl.cdiv(S, block_k)
    has_seg, has_alibi = seg is not None, slopes is not None

    if Bb == 1:  # b innermost (h outer); (1,1) accumulates across both
        b_of = lambda t: t % B
        h_of = lambda t: t // B
    else:  # (B, 1): h innermost
        b_of = lambda t: t // H
        h_of = lambda t: t % H

    operands = [q, k, v, bias]
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda qi, ki, t: (b_of(t), h_of(t), qi, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda qi, ki, t: (b_of(t), h_of(t) // group, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda qi, ki, t: (b_of(t), h_of(t) // group, ki, 0)),
        pl.BlockSpec((1, 1, block_q, block_k),
                     lambda qi, ki, t: (b_of(t) if Bb > 1 else 0,
                                        h_of(t) if Hb > 1 else 0, qi, ki)),
    ]
    if has_seg:
        seg_q, seg_k = _broadcast_segment_ids(seg, S)
        operands += [seg_q, seg_k]
        in_specs += [
            pl.BlockSpec((1, block_q, LANES),
                         lambda qi, ki, t: (b_of(t), qi, 0)),
            pl.BlockSpec((1, SUBLANES, block_k),
                         lambda qi, ki, t: (b_of(t), 0, ki)),
        ]
    if has_alibi:
        operands.append(slopes.astype(jnp.float32))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands += [do, lse, delta]
    in_specs += [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda qi, ki, t: (b_of(t), h_of(t), qi, 0)),
        pl.BlockSpec((1, 1, block_q, AUX_LANES),
                     lambda qi, ki, t: (b_of(t), h_of(t), qi, 0)),
        pl.BlockSpec((1, 1, block_q, AUX_LANES),
                     lambda qi, ki, t: (b_of(t), h_of(t), qi, 0)),
    ]
    dbias = pl.pallas_call(
        functools.partial(
            _bias_grad_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, has_seg=has_seg, has_alibi=has_alibi,
            B=B, H=H, Bb=Bb, Hb=Hb,
        ),
        grid=(nq, nk, B * H),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, block_q, block_k),
            lambda qi, ki, t: (b_of(t) if Bb > 1 else 0,
                               h_of(t) if Hb > 1 else 0, qi, ki)),
        # accumulate fp32 in scratch; the one write per tile casts, so the
        # output carries the bias dtype directly (no fp32 shadow + cast pass)
        out_shape=jax.ShapeDtypeStruct((Bb, Hb, S, S), bias.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return dbias


def _flash_bwd(q, k, v, out, lse, do, bias, seg, slopes, tables, offsets=None,
               *, causal, scale, block_q, block_k, interpret, delta=None):
    """``tables``: the layout's (row-major, by-column) :func:`flat_walk`
    pair for dq and dk/dv (static), or None for the dense grids."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    nq, nk = pl.cdiv(S, block_q), pl.cdiv(S, block_k)
    has_seg, has_alibi = seg is not None, slopes is not None
    has_bias, has_offsets = bias is not None, offsets is not None
    if delta is None:
        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )
        delta = jnp.broadcast_to(delta[..., None], (*delta.shape, AUX_LANES))

    mask_operands = []
    if has_bias:
        mask_operands.append(bias)
    if has_seg:
        seg_q, seg_k = _broadcast_segment_ids(seg, S)
        mask_operands += [seg_q, seg_k]
    if has_alibi:
        mask_operands.append(slopes.astype(jnp.float32))
    if has_offsets:
        mask_operands.append(offsets)
    operands = [q, k, v, *mask_operands, do, lse, delta]
    bias_bh = bias.shape[:2] if has_bias else None
    # full-shape bias: its gradient IS [B,H,S,S], so the dq kernel emits the
    # tiles inline for free. Broadcast bias: a dedicated accumulation kernel
    # keeps peak dbias memory at the bias's own shape (see _bias_grad_kernel).
    emit_dbias = has_bias and bias_bh == (B, H)

    def in_specs(qi_of, ki_of):
        q_like = pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, *g: (b, h, qi_of(*g), 0))
        kv_like = pl.BlockSpec(
            (1, 1, block_k, D),
            lambda b, h, *g: (b, h // group, ki_of(*g), 0))
        aux = pl.BlockSpec((1, 1, block_q, AUX_LANES),
                           lambda b, h, *g: (b, h, qi_of(*g), 0))
        # do / lse / delta all follow the q-block index
        return ([q_like, kv_like, kv_like]
                + _mask_specs(has_seg, has_alibi, block_q, block_k, qi_of,
                              ki_of, bias_bh=bias_bh, has_offsets=has_offsets)
                + [q_like, aux, aux])

    flags = dict(scale=scale, causal=causal, block_q=block_q,
                 block_k=block_k, has_seg=has_seg, has_alibi=has_alibi,
                 has_bias=has_bias, has_offsets=has_offsets)

    # --- dq (a q-block's k-blocks in order) --------------------------------
    walk, dead = _as_walk(tables[0] if tables else None)
    qi_of, ki_of = _tile_index_maps(walk is not None)
    dq_out_specs = pl.BlockSpec((1, 1, block_q, D),
                                lambda b, h, *g: (b, h, qi_of(*g), 0))
    dq_out_shape = jax.ShapeDtypeStruct((B, H, S, D), q.dtype)
    if emit_dbias:
        # each tile written exactly once → emit in the bias dtype directly
        # (a dense bias never combines with a walk: enforced at the entry)
        dq_out_specs = [dq_out_specs, pl.BlockSpec(
            (1, 1, block_q, block_k), lambda b, h, x, y: (b, h, x, y))]
        dq_out_shape = [dq_out_shape,
                        jax.ShapeDtypeStruct((B, H, S, S), bias.dtype)]

    dq = _grid_call(
        functools.partial(_bwd_dq_kernel, flat=walk is not None, dead=dead,
                          emit_dbias=emit_dbias, **flags),
        (nq, nk),
        in_specs(qi_of, ki_of),
        dq_out_specs,
        dq_out_shape,
        [pltpu.VMEM((block_q, D), jnp.float32)],
        operands,
        walk,
        interpret,
    )
    dbias = None
    if emit_dbias:
        dq, dbias = dq
    elif has_bias:
        dbias = _bias_grad_call(
            q, k, v, bias, seg, slopes, do, lse, delta, causal=causal,
            scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret, group=group,
        )

    # --- dk/dv (a k-block's q-blocks in order); GQA-sum over the group after
    walk, dead = _as_walk(tables[1] if tables else None)
    qi_of, ki_of = _tile_index_maps(walk is not None, swap=True)
    dkv_spec = pl.BlockSpec((1, 1, block_k, D),
                            lambda b, h, *g: (b, h, ki_of(*g), 0))
    dk, dv = _grid_call(
        functools.partial(_bwd_dkv_kernel, flat=walk is not None, dead=dead,
                          **flags),
        (nk, nq),
        in_specs(qi_of, ki_of),
        [dkv_spec, dkv_spec],
        [
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        ],
        [
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        operands,
        walk,
        interpret,
    )
    if group > 1:
        dk = dk.reshape(B, KV, group, S, D).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, KV, group, S, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv, dbias


# -----------------------------------------------------------------------------
# public op ([B, S, H, D] layout, custom vjp)
# -----------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(6, 14)))
def _flash_attention_bhsd(q, k, v, bias, seg, slopes, walks, causal, scale,
                          block_q, block_k, block_q_bwd, block_k_bwd,
                          interpret):
    """``walks``: None (dense grids) or the static layout's three
    :func:`flat_walk` lists as tuples of ints (hashable: they are no
    operand, each call builds its scalar-prefetch vector from them) —
    forward at (block_q, block_k) granularity, dq and dk/dv at
    (block_q_bwd, block_k_bwd)."""
    out, _ = _flash_fwd(
        q, k, v, bias, seg, slopes, walks[0] if walks else None,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _fa_fwd(q, k, v, bias, seg, slopes, walks, causal, scale, block_q,
            block_k, block_q_bwd, block_k_bwd, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(
        q, k, v, bias, seg, slopes, walks[0] if walks else None,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    # Name the kernel outputs so remat policies can save them: under plain
    # dots_saveable a jax.checkpoint'd block re-runs this whole forward
    # kernel in backward just to regenerate (out, lse) — the "dots_flash"
    # policy (runtime/activation_checkpointing.py) saves these two tensors
    # (~S*D + S floats per head) and XLA dead-code-eliminates the recompute.
    out = checkpoint_name(out, "flash_out")
    # tag the residual lse AFTER dropping the redundant lane copies so the
    # policy saves [B,H,S], not the kernel's [B,H,S,AUX_LANES] layout
    lse_s = checkpoint_name(lse[..., 0], "flash_lse")
    return out, (q, k, v, bias, seg, slopes, out, lse_s)


def _fa_bwd(walks, causal, scale, block_q, block_k, block_q_bwd, block_k_bwd,
            interpret, res, do):
    q, k, v, bias, seg, slopes, out, lse_s = res
    lse = jnp.broadcast_to(lse_s[..., None], (*lse_s.shape, AUX_LANES))
    dq, dk, dv, dbias = _flash_bwd(
        q, k, v, out, lse, do, bias, seg, slopes,
        walks[1:] if walks else None, causal=causal, scale=scale,
        block_q=block_q_bwd, block_k=block_k_bwd, interpret=interpret,
    )
    # segment ids are integer primals: cotangents float0
    import numpy as np

    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    dslopes = None if slopes is None else jnp.zeros_like(slopes)
    return dq, dk, dv, dbias, dseg, dslopes


_flash_attention_bhsd.defvjp(_fa_fwd, _fa_bwd)


def _pick_block(S: int, preferred: int) -> Optional[int]:
    """Largest aligned block size (multiple of 128) that divides S."""
    for cand in (preferred, 512, 256, 128):
        if cand % 128 == 0 and cand <= S and S % cand == 0:
            return cand
    return None


def set_default_block_sizes(block_q: int = 0, block_k: int = 0,
                            block_q_bwd: int = 0,
                            block_k_bwd: int = 0) -> None:
    """Process-wide default override (sweeps/tests). Engines use the scoped
    form below so two engines with different configs don't fight.

    0 keeps the current default for that dim."""
    global DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    global DEFAULT_BLOCK_Q_BWD, DEFAULT_BLOCK_K_BWD
    if block_q:
        DEFAULT_BLOCK_Q = int(block_q)
    if block_k:
        DEFAULT_BLOCK_K = int(block_k)
    if block_q_bwd:
        DEFAULT_BLOCK_Q_BWD = int(block_q_bwd)
    if block_k_bwd:
        DEFAULT_BLOCK_K_BWD = int(block_k_bwd)


_block_scope_stack: list = []

def current_block_sizes() -> tuple:
    """The (block_q, block_k) preference in effect right now: innermost
    scoped override, else the process defaults. Consumed by every flash
    composition (flat, sparse, ring) so a tuned config applies uniformly."""
    scoped = _block_scope_stack[-1] if _block_scope_stack else (0, 0, 0, 0)
    return (scoped[0] or DEFAULT_BLOCK_Q, scoped[1] or DEFAULT_BLOCK_K)


def current_bwd_block_sizes() -> tuple:
    """The (block_q_bwd, block_k_bwd) preference: scoped override, else the
    process defaults. 0 entries mean "inherit the forward size" — resolved
    at each composition's entry, not here, because the fwd resolution may
    itself degrade per shape (_pick_block)."""
    scoped = _block_scope_stack[-1] if _block_scope_stack else (0, 0, 0, 0)
    return (scoped[2] or DEFAULT_BLOCK_Q_BWD, scoped[3] or DEFAULT_BLOCK_K_BWD)


def _log_fallback_once(reasons) -> None:
    from ...utils.logging import log_fallback_once

    log_fallback_once("flash_attention", reasons)


class block_sizes_scope:
    """Scoped tile-size override, active while an engine traces its step."""

    def __init__(self, block_q: int = 0, block_k: int = 0,
                 block_q_bwd: int = 0, block_k_bwd: int = 0):
        self.sizes = (int(block_q), int(block_k),
                      int(block_q_bwd), int(block_k_bwd))

    def __enter__(self):
        _block_scope_stack.append(self.sizes)
        return self

    def __exit__(self, *exc):
        _block_scope_stack.pop()


def flash_attention(
    q, k, v, *, causal: bool = True, bias=None, segment_ids=None,
    alibi_slopes=None, block_mask=None, block_q: Optional[int] = None,
    block_k: Optional[int] = None, block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None, interpret: Optional[bool] = None,
):
    """Flash attention in model layout q[B,S,H,D], k/v[B,S,KV,D] → [B,S,H,D].

    segment_ids [B,S], alibi_slopes [H], and a dense additive ``bias``
    shaped [B|1, H|1, S, S] are all handled in-kernel (the bias is block-
    fetched per tile; its backward writes a [B,H,S,S] dbias — the same
    tensor the XLA fallback would materialize — while the forward never
    builds it). Other shapes fall back to the XLA reference with a
    one-shot log naming the reason, as do cross-length attention and
    unaligned shapes. Under an installed MeshTopology with >1 device, the
    kernel runs inside shard_map — batch over dp/fsdp, heads over tp, and
    heads over ("tp","sp") on a DS-Ulysses mesh (pallas_call has no GSPMD
    partitioning rules, so without this the compiler would replicate it).
    """
    from ..attention import xla_attention
    from ...models.sharding import current_topology

    B, S, H, D = q.shape
    KV = k.shape[2]
    pref_q, pref_k = current_block_sizes()
    if block_q is None:
        block_q = pref_q
    if block_k is None:
        block_k = pref_k
    pref_qb, pref_kb = current_bwd_block_sizes()
    if block_q_bwd is None:
        block_q_bwd = pref_qb
    if block_k_bwd is None:
        block_k_bwd = pref_kb
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    topo = current_topology()
    distributed = topo is not None and topo.world_size > 1
    tp = topo.tp_size if topo is not None else 1
    sp = topo.sp_size if topo is not None else 1
    head_div = tp * sp if distributed else 1  # ulysses shards heads over both
    local_H = H // head_div if distributed else H
    local_KV = max(KV // head_div, 1) if distributed else KV
    bq, bk = _pick_block(S, block_q), _pick_block(S, block_k)
    # bwd tiles: 0 = inherit the (resolved) fwd tile; a user-supplied
    # block_mask pins them to the fwd sizes because its granularity is
    # fixed by the mask shape (the causal triangle below is built again at
    # bwd granularity instead)
    bqb = (_pick_block(S, block_q_bwd) if block_q_bwd else None) or bq
    bkb = (_pick_block(S, block_k_bwd) if block_k_bwd else None) or bk
    if block_mask is not None:
        bqb, bkb = bq, bk
    bias_ok = bias is None or (
        bias.ndim == 4
        and bias.shape[0] in (1, B)
        and bias.shape[1] in (1, H)
        and bias.shape[2:] == (S, S)
        # a batch-full bias can't ride a batch-sharded mesh tile-for-tile
        # unless it also shards; broadcast bias ([1,...]) always works
        and not (distributed and bias.shape[0] not in (1,))
    )
    layout_np = None
    if block_mask is not None:
        try:
            import numpy as _np

            layout_np = _np.asarray(block_mask)
        except Exception:
            layout_np = None
    reasons = []
    if not bias_ok:
        reasons.append(
            f"dense bias shape {tuple(bias.shape)} is not in-kernel-eligible "
            f"([B|1, H|1, {S}, {S}]"
            + (", batch dim must be 1 on a sharded mesh)" if distributed
               else ")")
        )
    if bias is not None and block_mask is not None:
        reasons.append(
            "dense bias does not compose with a block-sparse layout in-kernel"
        )
    if block_mask is not None and layout_np is None:
        reasons.append(
            "block_mask must be trace-time static (numpy) for the "
            "kernels' walk of its live tiles"
        )
    if layout_np is not None and _walk_bound(layout_np) > WALK_MAX_STEPS:
        reasons.append(
            f"block_mask {layout_np.shape} has up to "
            f"{_walk_bound(layout_np)} grid steps, over the "
            f"{WALK_MAX_STEPS} the scalar memory holds"
        )
    if k.shape[1] != S:
        reasons.append(f"cross-length attention (q seq {S}, kv seq {k.shape[1]})")
    if bq is None or bk is None:
        reasons.append(f"seq {S} has no 128-aligned divisor tile")
    if H % KV != 0:
        reasons.append(f"heads {H} not a multiple of kv heads {KV}")
    if D % 8 != 0:
        reasons.append(f"head_dim {D} not a multiple of 8")
    if distributed and (H % head_div != 0 or KV % head_div != 0):
        reasons.append(
            f"heads ({H} q / {KV} kv) not divisible by tp*sp={head_div}"
        )
    if distributed and H % head_div == 0 and KV % head_div == 0 \
            and local_H % local_KV != 0:
        reasons.append(
            f"local heads {local_H} not a multiple of local kv {local_KV} "
            f"under tp*sp={head_div}"
        )
    if reasons:
        _log_fallback_once(reasons)
        if block_mask is not None:
            # never silently drop the sparsity pattern: expand the block
            # mask to a dense token bias for the fallback (jnp so traced
            # masks expand too)
            bm = jnp.asarray(block_mask)
            if (
                k.shape[1] != S
                or S % bm.shape[0] != 0
                or S % bm.shape[1] != 0
            ):
                raise ValueError(
                    f"block_mask {bm.shape} incompatible with seq {S} on the "
                    f"XLA fallback path"
                )
            tok = jnp.repeat(
                jnp.repeat(bm, S // bm.shape[0], axis=0),
                S // bm.shape[1], axis=1,
            )
            mask_bias = jnp.where(tok > 0, 0.0, NEG_INF)[None, None]
            bias = mask_bias if bias is None else bias + mask_bias
        return xla_attention(
            q, k, v, causal=causal, bias=bias, segment_ids=segment_ids,
            alibi_slopes=alibi_slopes,
        )
    scale = 1.0 / (D**0.5)
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    seg = segment_ids.astype(jnp.int32) if segment_ids is not None else None
    slopes = (
        jnp.asarray(alibi_slopes, jnp.float32)
        if alibi_slopes is not None
        else None
    )
    # A static layout's kernels walk its live tiles and no others (see
    # flat_walk): a masked tile is neither fetched nor a grid step. Plain
    # causal attention IS such a layout, the lower block triangle, so it
    # rides the same walk: (B, H, 10) at S 2048 with 512-wide tiles where the
    # dense grid has 16 steps a (batch, head). What has no static layout or
    # needs every tile keeps the dense grid with its in-kernel predicate: a
    # dense bias (its dbias paths write every tile), a non-causal call, ring
    # hops (ring_flash.py), and a triangle whose list would pass
    # WALK_MAX_STEPS.
    walks = None
    if layout_np is not None:
        if layout_np.shape != (S // bq, S // bk):
            raise ValueError(
                f"block_mask shape {layout_np.shape} != (nq={S // bq}, "
                f"nk={S // bk}) for seq {S} with blocks ({bq}, {bk})"
            )
        layout_np = layout_bwd = layout_np != 0
        if causal:
            # the dense grid's per-step visibility test, folded in here
            layout_np = layout_bwd = layout_np & causal_layout(S, bq, bk)
    elif causal and bias is None:
        # the bwd kernels walk the triangle at their own tiles' granularity
        layout_np, layout_bwd = causal_layout(S, bq, bk), causal_layout(
            S, bqb, bkb)
        if max(map(_walk_bound, (layout_np, layout_bwd))) > WALK_MAX_STEPS:
            layout_np = None
    if layout_np is not None:
        walks = tuple(
            tuple(w.tolist())
            for w in (flat_walk(layout_np), flat_walk(layout_bwd),
                      flat_walk(layout_bwd, by_col=True))
        )
    bias_f = bias  # storage dtype rides to the kernel; tiles upcast in VMEM

    def kernel(qt, kt, vt, bias_, seg_, slopes_):
        return _flash_attention_bhsd(
            qt, kt, vt, bias_, seg_, slopes_, walks, causal, scale, bq, bk,
            bqb, bkb, interpret
        )

    if distributed:
        from jax.sharding import PartitionSpec as P

        batch_axes = tuple(a for a in ("dp", "fsdp") if topo.sizes[a] > 1)
        head_axes = tuple(
            a for a in (("tp",) if sp == 1 else ("tp", "sp"))
            if topo.sizes[a] > 1
        )
        # inside an enclosing manual shard_map (pipeline schedule, stacked-
        # grads 1-bit path) some axes are already Manual: the nested
        # shard_map must use the context's abstract mesh and may only map
        # the still-Auto axes — arrays arrive already local on Manual ones
        am = jax.sharding.get_abstract_mesh()
        auto = {
            name
            for name, t in zip(am.axis_names, am.axis_types)
            if t == jax.sharding.AxisType.Auto
        }
        in_manual = len(auto) < len(am.axis_names)
        if in_manual:
            batch_axes = tuple(a for a in batch_axes if a in auto)
            head_axes = tuple(a for a in head_axes if a in auto)
        b_ax = batch_axes if batch_axes else None
        h_ax = head_axes if head_axes else None
        mapped = set(batch_axes) | set(head_axes)

        if not mapped:
            # everything relevant is already Manual/local: run the kernel
            # directly on the local shards
            out = kernel(qt, kt, vt, bias_f, seg, slopes)
            return jnp.swapaxes(out, 1, 2)

        spec_q = P(b_ax, h_ax, None, None)
        # shard_map can't take None operands: pass dummies, re-None inside
        s_in = seg if seg is not None else jnp.zeros((B, S), jnp.int32)
        sl_in = slopes if slopes is not None else jnp.zeros((H,), jnp.float32)
        bias_in = (
            bias_f if bias_f is not None else jnp.zeros((1, 1, 1, 1), jnp.float32)
        )
        # bias batch dim is 1 on a mesh (checked above); head dim shards
        # with the heads when present, else replicates
        bias_spec = P(
            None, h_ax if bias_f is not None and bias_f.shape[1] > 1 else None,
            None, None,
        )

        def body(qt, kt, vt, bias_, s_, sl_):
            return kernel(
                qt, kt, vt,
                bias_ if bias_f is not None else None,
                s_ if seg is not None else None,
                sl_ if slopes is not None else None,
            )

        kw = {}
        if in_manual:
            kw["axis_names"] = mapped
        out = jax.shard_map(
            body,
            mesh=am if in_manual else topo.mesh,
            in_specs=(
                spec_q, spec_q, spec_q,
                bias_spec,
                P(b_ax, None),  # segment ids: full sequence per shard
                P(h_ax),  # per-head slopes follow the head sharding
            ),
            out_specs=spec_q,
            check_vma=False,
            **kw,
        )(qt, kt, vt, bias_in, s_in, sl_in)
    else:
        out = kernel(qt, kt, vt, bias_f, seg, slopes)
    return jnp.swapaxes(out, 1, 2)


def register():
    from ..attention import register_attention_impl

    register_attention_impl("flash", flash_attention)


register()
