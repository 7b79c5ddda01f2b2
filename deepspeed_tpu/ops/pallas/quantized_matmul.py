"""Weight-only quantized projection: int8/int4 weights stream from HBM.

Parity: DeepSpeed-Inference weight-only quantized GEMM (the reference's
csrc/transformer/inference int8 kernels dequantize inside the GEMM). The
XLA-level alternative — dequantize-then-dot — materializes a full-width
bf16 copy of the weights EVERY decode step inside the while-loop (measured
on v5e: 286 tok/s vs 864 bf16 at 410M — the dequant write+read more than
forfeits the halved weight stream). This Pallas kernel keeps the dequant
in VMEM: HBM traffic per step is the int8/int4 bytes plus scales, nothing
else.

Decode matvecs are HBM-bandwidth-bound (batch·seq ≤ ~8 rows), so the
roofline win is the byte ratio: ~1.9x for int8, ~3.6x for int4.

Layout (ops/quantizer.pack_quantize_blockwise): qdata [G, B, N] int8 with
the contraction dim d = G·B blocked at 128, scale fp32 [G, 1, N]; int4
packs blocks split-half (byte plane g = blocks g and g + G/2) → qdata
[G/2, B, N].
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..quantizer import PackedWeight


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(x_ref, q_ref, s_ref, o_ref, *, nibbles: bool):
    x = x_ref[...].astype(jnp.float32)  # [M, D]
    q = q_ref[...]  # int8 [G, B, bn] (int4: [G//2, B, bn] split-half)
    s = s_ref[...]  # [G, 1, bn] f32
    # fp32 serving must match the >8-row dequantize-einsum path (~1e-6):
    # the default dot precision truncates f32 inputs to bf16 multiplies
    # (~1e-2 relative — measured), which would make prefill and decode
    # disagree numerically. bf16 serving keeps the fast default.
    prec = (
        jax.lax.Precision.HIGHEST
        if o_ref.dtype == jnp.float32
        else None
    )
    # the fold runs in f32 on purpose — measured on v5e at 410M: f32 fold
    # = 873 tok/s vs bf16 fold = 738 (16-bit register packing relayouts
    # cost more than the halved convert width) vs per-block post-dot
    # scaling = 679 (small-dot latency); a Mosaic batched dot is
    # unsupported ("batch dims must be equal"). s[g,n]·(x·q[g,:,n]) ==
    # x·(q[g,:,n]·s[g,n]): the full-width dequant tile exists only in
    # VMEM, HBM saw int8/int4 bytes.
    if nibbles:
        # int4 byte plane g holds blocks g (low nibble) and g + G/2
        # (high) — quantizer split-half packing. Unpack + scale-fold per
        # plane, then a sublane-dim concat restores natural block order:
        # no lane-dim shape op anywhere (Mosaic rejects those), and x
        # needs no rearrangement at all.
        Gh, B, bn = q.shape
        # int32 nibble math: Mosaic cannot legalize shifts on int8
        # vectors (arith.shli). (x & 15 ^ 8) - 8 sign-extends the low
        # nibble; the sign-extended byte >> 4 is the signed high nibble.
        q32 = q.astype(jnp.int32)
        low = (((jnp.bitwise_and(q32, 15) ^ 8) - 8)
               .astype(jnp.float32) * s[:Gh]).reshape(Gh * B, bn)
        high = (jnp.right_shift(q32, 4)
                .astype(jnp.float32) * s[Gh:]).reshape(Gh * B, bn)
        qf = jnp.concatenate([low, high], axis=0)
        y = jax.lax.dot_general(
            x, qf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
    else:
        G, B, bn = q.shape
        qf = (q.astype(jnp.float32) * s).reshape(G * B, bn)
        y = jax.lax.dot_general(
            x, qf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "nibbles"))
def _packed_matvec(x2d, qdata, scale, *, block_n: int, nibbles: bool):
    Gq, Bq, _ = qdata.shape  # int4 split-half: Gq = G//2 byte planes
    Gs = scale.shape[0]  # scales always carry the full block count G
    N = scale.shape[-1]
    M, D = x2d.shape
    grid = (N // block_n,)
    return pl.pallas_call(
        functools.partial(_kernel, nibbles=nibbles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((M, D), lambda j: (0, 0)),
            pl.BlockSpec((Gq, Bq, block_n), lambda j: (0, 0, j)),
            pl.BlockSpec((Gs, 1, block_n), lambda j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((M, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2d.dtype),
        interpret=_interpret(),
    )(x2d, qdata, scale)


def _pick_block_n(N: int, D: int) -> int:
    """Largest power-of-two divisor of N keeping the int8 tile ≲ 4 MiB of
    VMEM; N itself when it's small."""
    budget = max((4 << 20) // max(D, 1), 128)
    bn = 128
    while bn * 2 <= min(N, budget) and N % (bn * 2) == 0:
        bn *= 2
    return bn if N % bn == 0 else N


# rows at or below this run the streaming kernel; larger shapes (prefill,
# training would never see PackedWeight) are compute-bound and dequantize
# once into a regular MXU matmul instead. Configurable per engine via
# inference.matvec_max_rows (init_inference) — e.g. the k=9 speculative
# verify window is 10 rows and needs ≥ 10 to stay on the streaming path.
_MATVEC_MAX_ROWS = 8
_matvec_rows_override = None


@contextlib.contextmanager
def matvec_max_rows_scope(rows):
    """Trace-time override of the streaming-matvec row threshold (None →
    keep the current value). Scoped like the other kernel selectors so
    engines with different configs in one process don't fight; must wrap
    the TRACE of the consuming program (inference engines enter it via
    their _impl_ctx)."""
    global _matvec_rows_override
    prev = _matvec_rows_override
    if rows is not None:
        _matvec_rows_override = int(rows)
    try:
        yield
    finally:
        _matvec_rows_override = prev


def matvec_max_rows() -> int:
    """The active streaming-kernel row threshold."""
    if _matvec_rows_override is not None:
        return _matvec_rows_override
    return _MATVEC_MAX_ROWS

# Measured negative (r5): fusing qkv (and wi+wg) into ONE kernel call by
# concatenating qdata/scale along columns in-trace LOST on-chip — int8
# decode fell to 0.93x bf16 in-window vs 1.13x unfused (int4 1.12x vs
# 1.27x). The int8 concat is evidently not hoisted out of the decode
# while-loop (or the wider single grid schedules worse), so per-weight
# launches stay.

# trace-time path observability: tests assert the tp>1 decode matvec
# actually STREAMS (takes a kernel path) instead of only checking packed
# HBM residency — counts bump when a path is traced, not per step.
# expert_* are the MoE expert-bank twins (packed_expert_proj).
_STREAM_TRACES = {"single": 0, "sharded": 0, "expert_single": 0,
                  "expert_sharded": 0}


def streaming_trace_counts() -> dict:
    return dict(_STREAM_TRACES)


def reset_streaming_trace_counts() -> None:
    for k in _STREAM_TRACES:
        _STREAM_TRACES[k] = 0


def _spec_axes(entry) -> tuple:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _axes_extent(mesh, axes: tuple) -> int:
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def _matvec_pspec_entries(w):
    """(row_entry, col_entry) of the weight's matmul dims, or None.

    The pspec is the ORIGINAL (possibly stacked [L, d, n]) weight's spec;
    a lax.scan over the stacked leaf hands packed_proj a per-layer slice
    whose aux still carries the full spec — so only the trailing two
    entries describe the live (d, n) dims, and any sharded leading
    (layer) entry disqualifies the per-slice wrapper."""
    if w.pspec is None:
        return None
    ndim = max(len(w.shape), 2)
    entries = tuple(w.pspec) + (None,) * (ndim - len(tuple(w.pspec)))
    if any(e is not None for e in entries[:-2]):
        return None
    return entries[-2], entries[-1]


def _sharded_matvec_ok(w, topo, x_cols: int) -> bool:
    """Whether the per-shard streaming kernel applies to this packed leaf
    on this mesh: a remembered spec whose shards keep whole 128-lane
    tiles and whole quantization blocks (int4 nibble pairs cannot split
    across row shards — quantizer split-half packing)."""
    rc = _matvec_pspec_entries(w)
    if rc is None or w.qdata.ndim != 3:
        return False
    row_axes, col_axes = _spec_axes(rc[0]), _spec_axes(rc[1])
    mesh = topo.mesh
    try:
        re_, ce = _axes_extent(mesh, row_axes), _axes_extent(mesh, col_axes)
    except KeyError:
        return False
    if re_ == 1 and ce == 1:
        return False  # replicated: the single-device kernel path applies
    G, N = w.scale.shape[0], w.scale.shape[-1]
    return (
        N % ce == 0
        and (N // ce) % 128 == 0
        and G % re_ == 0
        and x_cols % re_ == 0
        and w.qdata.shape[0] % re_ == 0
        and not (w.nibbles and re_ > 1)
    )


def _packed_matvec_sharded(x2d, w, topo):
    """Run the streaming matvec PER SHARD under tp>1 serving.

    A bare pallas_call has no GSPMD partitioning rule, so without this
    wrapper the sharded qdata/scale operands dequantize full-width in
    XLA every decode step (measured 3x slower at 410M). Full-manual
    shard_map over the whole mesh: column
    shards emit their output slice with no collective; row (contraction)
    shards psum their partials — the same collective GSPMD would insert,
    but the HBM stream per shard is the int8/int4 bytes."""
    from jax.sharding import PartitionSpec as P

    row_e, col_e = _matvec_pspec_entries(w)
    row_axes = _spec_axes(row_e)
    mesh = topo.mesh
    re_, ce = _axes_extent(mesh, row_axes), _axes_extent(
        mesh, _spec_axes(col_e)
    )
    N_loc = w.scale.shape[-1] // ce
    D_loc = x2d.shape[1] // re_
    qspec = P(row_e, None, col_e)
    sspec = P(row_e, None, col_e)

    def body(xl, qd, sc):
        y = _packed_matvec(
            xl, qd, sc,
            block_n=_pick_block_n(N_loc, D_loc),
            nibbles=w.nibbles,
        )
        if row_axes:
            # contraction-sharded (row-parallel): reduce the partials in
            # fp32 — XLA's CPU AllReducePromotion pass crashes on bf16
            # all-reduce under shard_map (same workaround as the pipeline)
            y = jax.lax.psum(y.astype(jnp.float32), row_axes).astype(y.dtype)
        return y

    run = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, row_e), qspec, sspec),
        out_specs=P(None, col_e),
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )
    _STREAM_TRACES["sharded"] += 1
    return run(x2d, w.qdata, w.scale)


def _expert_pspec_entries(w) -> tuple:
    """(expert, row, col) PartitionSpec entries of a packed EXPERT BANK's
    live [E, d, n] dims, or None. Mirrors :func:`_matvec_pspec_entries`:
    the pspec is the ORIGINAL stacked [L, E, d, n] weight's spec — a
    lax.scan over the stacked leaf hands the per-layer [E, d, n] slice
    with the full spec still in aux, so only the trailing THREE entries
    describe the live dims, and any sharded leading (layer) entry
    disqualifies the wrapper."""
    if w.pspec is None:
        return None
    ndim = max(len(w.shape), 3)
    entries = tuple(w.pspec) + (None,) * (ndim - len(tuple(w.pspec)))
    if any(e is not None for e in entries[:-3]):
        return None
    return entries[-3], entries[-2], entries[-1]


def _expert_matvec_ok(w, topo, x_cols: int) -> bool:
    """Whether the per-shard expert streaming kernel applies on this
    mesh: a remembered spec whose expert shards keep whole experts,
    whose column shards keep whole 128-lane tiles, and whose row shards
    keep whole quantization blocks (int4 nibble pairs cannot split
    across row shards)."""
    rc = _expert_pspec_entries(w)
    if rc is None or w.qdata.ndim != 4:
        return False
    e_axes, row_axes, col_axes = (_spec_axes(e) for e in rc)
    mesh = topo.mesh
    try:
        ee = _axes_extent(mesh, e_axes)
        re_ = _axes_extent(mesh, row_axes)
        ce = _axes_extent(mesh, col_axes)
    except KeyError:
        return False
    if ee == 1 and re_ == 1 and ce == 1:
        return False  # replicated: the single-device expert path applies
    E, G, N = w.qdata.shape[0], w.scale.shape[-3], w.scale.shape[-1]
    return (
        E % ee == 0
        and N % ce == 0
        and (N // ce) % 128 == 0
        and G % re_ == 0
        and x_cols % re_ == 0
        and w.qdata.shape[1] % re_ == 0
        and not (w.nibbles and re_ > 1)
    )


def _packed_expert_matvec_local(x3d, qdata, scale, *, nibbles: bool,
                                block_n: int):
    """Per-expert streaming matvecs on LOCAL [E, C, D] rows against the
    local packed bank [E, G, B, n]: one kernel launch per expert (E is a
    small static count — the per-weight-launch rule the r5 fusion A/B
    settled stays)."""
    return jnp.stack([
        _packed_matvec(x3d[e], qdata[e], scale[e], block_n=block_n,
                       nibbles=nibbles)
        for e in range(x3d.shape[0])
    ])


def _packed_expert_sharded(x3d, w, topo):
    """Run the expert streaming matvec PER SHARD under an ep (and/or tp)
    mesh — the PR-3 full-manual shard_map treatment applied to expert
    banks: a bare pallas_call has no GSPMD partitioning rule, so without
    this wrapper ep-sharded qdata/scale operands dequantize full-width
    in XLA every decode step. Expert shards are embarrassingly parallel;
    column (tp) shards emit their output slice with no collective; row
    (contraction) shards psum fp32 partials exactly like
    :func:`_packed_matvec_sharded`."""
    from jax.sharding import PartitionSpec as P

    e_entry, row_e, col_e = _expert_pspec_entries(w)
    row_axes = _spec_axes(row_e)
    mesh = topo.mesh
    re_ = _axes_extent(mesh, row_axes)
    ce = _axes_extent(mesh, _spec_axes(col_e))
    N_loc = w.scale.shape[-1] // ce
    D_loc = x3d.shape[-1] // re_

    def body(xl, qd, sc):
        y = _packed_expert_matvec_local(
            xl, qd, sc, nibbles=w.nibbles,
            block_n=_pick_block_n(N_loc, D_loc),
        )
        if row_axes:
            # contraction-sharded: fp32 reduce (the CPU AllReducePromotion
            # workaround, same as _packed_matvec_sharded)
            y = jax.lax.psum(y.astype(jnp.float32), row_axes).astype(y.dtype)
        return y

    run = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(e_entry, None, row_e),
            P(e_entry, row_e, None, col_e),
            P(e_entry, row_e, None, col_e),
        ),
        out_specs=P(e_entry, None, col_e),
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )
    _STREAM_TRACES["expert_sharded"] += 1
    return run(x3d, w.qdata, w.scale)


def packed_expert_proj(x: jax.Array, w) -> "jax.Array | None":
    """x [E, C, D] @ w [E, D, N] where w is a PackedWeight EXPERT BANK
    (qdata [E, G, B, N]): the weight-only int8/int4 streaming matvec run
    per expert, per shard — the serving MoE path's expert FFN
    (moe/sharded_moe._expert_proj). Returns None when the streaming
    kernel does not apply (row count over the matvec threshold, lanes
    not tile-aligned, or an undividable shard geometry) and the caller
    dequantizes into a regular MXU matmul instead."""
    from ...models.sharding import current_topology

    if w.qdata.ndim != 4 or w.scale.shape[-1] % 128 != 0:
        return None
    E, C, D = x.shape
    if C > matvec_max_rows():
        return None
    N = w.scale.shape[-1]
    topo = current_topology()
    if topo is None or topo.world_size == 1:
        _STREAM_TRACES["expert_single"] += 1
        return _packed_expert_matvec_local(
            x, w.qdata, w.scale, nibbles=w.nibbles,
            block_n=_pick_block_n(N, D),
        )
    if _expert_matvec_ok(w, topo, D):
        return _packed_expert_sharded(x, w, topo)
    rc = _expert_pspec_entries(w)
    if rc is not None:
        try:
            replicated = all(
                _axes_extent(topo.mesh, _spec_axes(e)) == 1 for e in rc
            )
        except KeyError:
            # pspec names an axis absent from this mesh: fall back to
            # the dequantize path like every sibling predicate
            replicated = False
        if replicated:
            # replicated on a >1 mesh: the single-device loop streams
            _STREAM_TRACES["expert_single"] += 1
            return _packed_expert_matvec_local(
                x, w.qdata, w.scale, nibbles=w.nibbles,
                block_n=_pick_block_n(N, D),
            )
    return None


def packed_proj(x: jax.Array, w) -> jax.Array:
    """x[..., d] @ w[d, n] where w may be a PackedWeight.

    Dense weights pass straight to einsum (the training path pays only an
    isinstance check — or a decomposed collective-matmul ring when the
    tensor_parallel.overlap_comm scope routes the call site through
    parallel/tensor_overlap instead). PackedWeight + decode-sized x (≤ 8
    rows) runs the Pallas streaming kernel; under tp>1 the kernel runs
    per-shard inside a full-manual shard_map when the leaf remembers its
    partition spec (PackedWeight.pspec) and the packed geometry divides.
    Anything else dequantizes and uses the MXU.
    """
    if not isinstance(w, PackedWeight):
        return jnp.einsum("...d,dn->...n", x, w)
    from ...models.sharding import current_topology

    topo = current_topology()
    lead = x.shape[:-1]
    rows = int(np.prod(lead)) if lead else 1
    if (
        rows <= matvec_max_rows()
        and w.qdata.ndim == 3
        and w.scale.shape[-1] % 128 == 0
    ):
        N = w.scale.shape[-1]
        x2d = x.reshape(rows, x.shape[-1])
        if topo is None or topo.world_size == 1:
            _STREAM_TRACES["single"] += 1
            y = _packed_matvec(
                x2d, w.qdata, w.scale,
                block_n=_pick_block_n(N, x.shape[-1]),
                nibbles=w.nibbles,
            )
            return y.reshape(*lead, N)
        if _sharded_matvec_ok(w, topo, x2d.shape[1]):
            return _packed_matvec_sharded(x2d, w, topo).reshape(*lead, N)
    return jnp.einsum("...d,dn->...n", x, w.dequantize())
