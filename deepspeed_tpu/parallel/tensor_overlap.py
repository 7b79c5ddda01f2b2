"""Decomposed (ring) collective matmul: overlap TP collectives with compute.

Under tensor parallelism the repo historically leaned on GSPMD to insert
the Megatron all-gather/reduce-scatter pairs at projection boundaries, so
every TP layer serialized an ICI collective against the matmul that could
hide it — the compute/collective overlap gap T3 (arxiv 2401.16677)
quantifies. This module makes the overlap explicit: the collective is
decomposed into a ring of ``ppermute`` hops and the matmul into per-shard
chunks, so each hop's DMA flies while the MXU multiplies the
previously-arrived chunk (XLA's latency-hiding scheduler overlaps the
independent ``collective-permute-start``/``-done`` with the dots).

Two forms, matching the Megatron-SP projection pair:

- :func:`allgather_matmul` — column-parallel in-projections (qkv, mlp-in).
  The activation arrives *sequence-sharded over tp*; each of the tp chunks
  does a ring hop while the previously-arrived chunk multiplies the local
  column shard of the weight, accumulating into the output at the source
  shard's row offset. Result: full-sequence activations × W[:, tp-shard]
  without ever materializing the gathered input or exposing the gather.
- :func:`matmul_reducescatter` — row-parallel out-projections (attn-out,
  mlp-out). Partial products ride the ring and accumulate per hop, so the
  reduce-scatter hides under the next chunk's matmul. ``scatter="seq"``
  leaves the output sequence-sharded over tp (the Megatron-SP layout);
  ``scatter="features"`` scatters the output-feature dim and optionally
  ring-gathers it back — the decomposed all-reduce the single-token decode
  path needs (its length-1 sequence cannot shard).

Variants:

- ``bidirectional=True`` splits the riding payload in half and sends the
  halves around both ring directions simultaneously; TPU ICI links are
  full-duplex, so per-hop wire time halves (same hop count, half the bytes
  per direction).
- ``quantized=True`` moves int8 + per-lane fp32 scales over the wire
  (ZeRO++ qwZ composition, reusing ``_quantize_lanewise`` from
  runtime/zero/quantized.py). Gather-side wires quantize ONCE at the
  source and forward the same int8 payload every hop (error == one
  fake-quant round-trip, hop-count independent); scatter-side riding
  accumulators must re-quantize per hop, so error grows O(tp) — see
  docs/collective_matmul.md for the error analysis.
- ``reference=True`` is the pure-XLA path (stock ``all_gather`` /
  ``all_to_all`` + ordered local reduction) — the CPU-mesh oracle the
  tests pin the ring against, and the "let XLA schedule it" fallback. The
  scatter-side reference reduces in explicit ring order (the qgZ
  all-to-all formulation), which pins the fp32 summation order so the
  unquantized unidirectional ring is *bitwise* comparable.

Every program here is a FULL-manual ``shard_map`` over the whole mesh;
the rings are built through
:func:`deepspeed_tpu.comm.collectives.permute`, which validates the
permutation against the shardlint R3 ring/chain contract at construction
time and reports hop bytes to the comms logger.

Model wiring rides :func:`overlap_scope` (trace-time, like the kernel
selection scopes): the engine enters it from the
``tensor_parallel.overlap_comm`` config section and
models/transformer.py's projection sites dispatch through
:func:`tp_in_proj` / :func:`tp_out_proj`, falling back to the plain
GSPMD path whenever the scope is off, shapes don't divide, the weight is
packed (int8/int4 serving), or tracing already sits inside a manual
shard_map (the pipeline schedule).
"""

from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm import collectives
from ..models.sharding import current_topology, manual_axis_names

__all__ = [
    "allgather_matmul",
    "matmul_reducescatter",
    "overlap_scope",
    "current_overlap",
    "tp_in_proj",
    "tp_out_proj",
    "ring_wire_bytes_per_step",
]


# --------------------------------------------------------------------- scope
_local = threading.local()


def current_overlap():
    """The active overlap_comm config (None when off)."""
    cfg = getattr(_local, "overlap", None)
    if cfg is not None and getattr(cfg, "enabled", False):
        return cfg
    return None


@contextlib.contextmanager
def overlap_scope(cfg):
    """Trace-time activation of decomposed TP projections (scoped like the
    Pallas kernel selectors: engines with different configs in one process
    don't fight). ``cfg`` is a ``tensor_parallel.overlap_comm`` section
    (anything with .enabled/.chunks/.bidirectional/.quantized_hops) or
    None to keep the current setting."""
    prev = getattr(_local, "overlap", None)
    if cfg is not None:
        _local.overlap = cfg
    try:
        yield
    finally:
        _local.overlap = prev


# ------------------------------------------------------------ ring plumbing
def _ring_perms(tp: int) -> Tuple[list, list]:
    """(forward, backward) full-ring permutations — single full cycles,
    the exact shape shardlint R3 certifies as hang-free."""
    fwd = [(i, (i + 1) % tp) for i in range(tp)]
    bwd = [(i, (i - 1) % tp) for i in range(tp)]
    return fwd, bwd


def _hop(x, axis, perm):
    """One validated, comms-logged ring hop."""
    return collectives.permute(x, axis, perm)


def _q(x: jax.Array):
    """Quantize an arbitrary-rank wire payload: lanes are the trailing dim,
    everything else flattens into the quantized (row) axis. ONE shared
    implementation — the int8 codec of comm/wires.py (bitwise identical
    to the pre-wires private ``_quantize_lanewise``)."""
    from ..comm.wires import quantize_lanewise

    q, scale = quantize_lanewise(x.reshape((-1, x.shape[-1])))
    return q.reshape(x.shape), scale


def _dq(q: jax.Array, scale: jax.Array, dtype):
    from ..comm.wires import dequantize_lanewise

    flat = dequantize_lanewise(
        q.reshape((-1, q.shape[-1])), scale, dtype
    )
    return flat.reshape(q.shape)


def _row_chunks(rows: int, chunks: int) -> List[Tuple[int, int]]:
    """Ceil-split [0, rows) into ``chunks`` (start, size) slices; uneven
    row counts give the leading slices one extra row. Pure scheduling
    granularity: each output row is still produced by exactly one dot, so
    chunking never changes numerics (bitwise)."""
    chunks = max(1, min(int(chunks), rows)) if rows else 1
    base, extra = divmod(rows, chunks)
    out, start = [], 0
    for c in range(chunks):
        size = base + (1 if c < extra else 0)
        out.append((start, size))
        start += size
    return out


def _mm(xblk: jax.Array, w: jax.Array, chunks: int) -> jax.Array:
    """xblk [b, rows, K] @ w [K, N] computed in ``chunks`` row slices (the
    unit XLA can overlap a hop DMA against)."""
    slices = _row_chunks(xblk.shape[1], chunks)
    if len(slices) == 1:
        return jnp.einsum("bsk,kn->bsn", xblk, w)
    return jnp.concatenate(
        [
            jnp.einsum("bsk,kn->bsn", xblk[:, s:s + z], w)
            for s, z in slices
        ],
        axis=1,
    )


# ----------------------------------------------------- all-gather × matmul
def _ring_allgather_matmul(x, ws, axis: str, tp: int, *, chunks: int,
                           bidirectional: bool, quantized: bool):
    """Ring body (inside shard_map): x local [b, m, K] seq-sharded over
    ``axis``; ws local column shards [K, n_j]. Returns one [b, m*tp, n_j]
    per weight — X_full @ W_j without materializing X_full."""
    i = lax.axis_index(axis)
    b, m, _K = x.shape
    fwd, bwd = _ring_perms(tp)
    outs = [jnp.zeros((b, m * tp, w.shape[1]), x.dtype) for w in ws]

    def write(outs, xc, src, lo, rows):
        # rows [lo, lo+rows) of shard `src` land at global rows
        # src*m + lo; every row is produced by exactly one dot
        return [
            lax.dynamic_update_slice(
                o, _mm(xc, w, chunks).astype(o.dtype), (0, src * m + lo, 0)
            )
            for o, w in zip(outs, ws)
        ]

    if not bidirectional or m < 2 or tp == 1:
        if quantized:
            wq, wscale = _q(x)  # quantize ONCE; the wire forwards verbatim
        src = i
        for s in range(tp):
            xc = _dq(wq, wscale, x.dtype) if quantized else x
            outs = write(outs, xc, src, 0, m)
            if s < tp - 1:
                if quantized:
                    wq = _hop(wq, axis, fwd)
                    wscale = _hop(wscale, axis, fwd)
                else:
                    x = _hop(x, axis, fwd)
                src = (src - 1) % tp
        return outs

    # bidirectional: half the rows ride each direction; both directions
    # move simultaneously, so per-hop wire time halves on full-duplex ICI
    ma = m - m // 2
    xa, xb = x[:, :ma], x[:, ma:]
    if quantized:
        aq, ascale = _q(xa)
        bq, bscale = _q(xb)
    for s in range(tp):
        src_a = (i - s) % tp
        src_b = (i + s) % tp
        xca = _dq(aq, ascale, x.dtype) if quantized else xa
        xcb = _dq(bq, bscale, x.dtype) if quantized else xb
        # halves land in disjoint row ranges of the source's block, so both
        # always write — including the even-tp step where src_a == src_b
        # (that shard's two halves arrive from opposite directions at once)
        outs = write(outs, xca, src_a, 0, ma)
        outs = write(outs, xcb, src_b, ma, m - ma)
        if s < tp - 1:
            if quantized:
                aq, ascale = _hop(aq, axis, fwd), _hop(ascale, axis, fwd)
                bq, bscale = _hop(bq, axis, bwd), _hop(bscale, axis, bwd)
            else:
                xa = _hop(xa, axis, fwd)
                xb = _hop(xb, axis, bwd)
    return outs


def _ref_allgather_matmul(x, ws, axis: str, tp: int, *, quantized: bool):
    """Pure-XLA reference: stock all_gather then one dot per weight. With
    quantized wires the gather moves the same int8+scale payload the ring
    would, so ring and reference stay bitwise-identical."""
    if quantized:
        wq, wscale = _q(x)
        x = _dq(wq, wscale, x.dtype)
    xg = collectives.all_gather(x, axis, gather_dimension=1, tiled=True)
    return [jnp.einsum("bsk,kn->bsn", xg, w) for w in ws]


# ------------------------------------------------- matmul × reduce-scatter
def _ring_matmul_reducescatter(x, w, axis: str, tp: int, *, chunks: int,
                               bidirectional: bool, quantized: bool,
                               scatter: str):
    """Ring body (inside shard_map): x local [b, S, K/tp] (contraction
    sharded), w local [K/tp, N]. The riding fp32 accumulator picks up one
    local partial per hop; the hop hides under the next block's matmul.

    scatter="seq": returns [b, S/tp, N] (output block i of the sequence).
    scatter="features": returns [b, S, N/tp] (output block i of the
    feature dim — the decode form; S need not divide)."""
    i = lax.axis_index(axis)
    b, S, _k = x.shape
    fwd, bwd = _ring_perms(tp)
    N = w.shape[1]

    if scatter == "seq":
        m = S // tp
        split_full = N  # bidirectional halves split the output columns

        def part(blk, lo, width):
            xs = lax.dynamic_slice(x, (0, blk * m, 0), (b, m, x.shape[2]))
            return _mm(xs, w[:, lo:lo + width], chunks).astype(jnp.float32)
    else:
        m = N // tp
        split_full = S  # bidirectional halves split the sequence rows

        def part(blk, lo, width):
            ws_ = lax.dynamic_slice(w, (0, blk * m), (w.shape[0], m))
            return _mm(x[:, lo:lo + width], ws_, chunks).astype(jnp.float32)

    def requant_hop(acc, perm):
        if quantized:
            q, scale = _q(acc)
            q = _hop(q, axis, perm)
            scale = _hop(scale, axis, perm)
            return _dq(q, scale, jnp.float32)
        return _hop(acc, axis, perm)

    if not bidirectional or tp == 1 or split_full < 2:
        # acc destined for block b starts at device (b+1) and rides the
        # forward ring; at step s device i holds the acc for (i-1-s)
        acc = part((i - 1) % tp, 0, split_full)
        for s in range(1, tp):
            acc = requant_hop(acc, fwd)
            acc = acc + part((i - 1 - s) % tp, 0, split_full)
        return acc.astype(x.dtype)

    # bidirectional: the accumulator splits in half along the non-scattered
    # dim; half A rides forward (blocks i-1-s), half B backward (i+1+s)
    wa = split_full - split_full // 2
    wb = split_full - wa
    acc_a = part((i - 1) % tp, 0, wa)
    acc_b = part((i + 1) % tp, wa, wb)
    for s in range(1, tp):
        acc_a = requant_hop(acc_a, fwd)
        acc_b = requant_hop(acc_b, bwd)
        acc_a = acc_a + part((i - 1 - s) % tp, 0, wa)
        acc_b = acc_b + part((i + 1 + s) % tp, wa, wb)
    cat_axis = 2 if scatter == "seq" else 1
    return jnp.concatenate([acc_a, acc_b], axis=cat_axis).astype(x.dtype)


def _ref_matmul_reducescatter(x, w, axis: str, tp: int, *, quantized: bool,
                              scatter: str):
    """Pure-XLA reference: one local partial dot, then a reduce-scatter
    implemented as all_to_all + ordered local fp32 reduction (the ZeRO++
    qgZ formulation — values quantize at most once, sums happen after
    dequant). The reduction order is pinned to the ring's visit order
    (i+1, i+2, …, i), so the unquantized unidirectional ring matches this
    reference BITWISE."""
    i = lax.axis_index(axis)
    b, S, _k = x.shape
    partial = jnp.einsum("bsk,kn->bsn", x, w).astype(jnp.float32)
    if scatter == "seq":
        m = S // tp
        blocks = partial.reshape(b, tp, m, partial.shape[2])
        blocks = jnp.moveaxis(blocks, 1, 0)  # [tp, b, m, N]
    else:
        m = partial.shape[2] // tp
        blocks = partial.reshape(b, S, tp, m)
        blocks = jnp.moveaxis(blocks, 2, 0)  # [tp, b, S, m]
    if quantized:
        # per-BLOCK scales (leading tp dim) so the all_to_all can split
        # them alongside the int8 payload — the qgZ formulation: each
        # partial block quantizes exactly once, the sum runs after dequant
        flat = blocks.reshape(tp, -1, blocks.shape[-1])
        amax = jnp.max(jnp.abs(flat.astype(jnp.float32)), axis=1,
                       keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / 127.0  # [tp, 1, lanes]
        q = jnp.clip(
            jnp.round(flat.astype(jnp.float32) / scale), -127, 127
        ).astype(jnp.int8).reshape(blocks.shape)
        q = collectives.all_to_all(q, axis, 0, 0, tiled=False)
        scale = collectives.all_to_all(scale, axis, 0, 0, tiled=False)
        gathered = (
            q.reshape(tp, -1, q.shape[-1]).astype(jnp.float32) * scale
        ).reshape(q.shape)
    else:
        gathered = collectives.all_to_all(blocks, axis, 0, 0, tiled=False)
    # gathered[j] = partial_j[block i]; sum in ring order j = i+1, …, i
    rolled = jnp.roll(gathered, -(i + 1), axis=0)
    acc = rolled[0]
    for s in range(1, tp):
        acc = acc + rolled[s]
    return acc.astype(x.dtype)


# ----------------------------------------------------------- public wrappers
def _shard_map_full(body, topo, in_specs, out_specs):
    """Full-manual shard_map over the WHOLE mesh: every axis is manual."""
    return jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=set(topo.mesh.axis_names),
        check_vma=False,
    )


def _as3d(x):
    return (x[None], True) if x.ndim == 2 else (x, False)


def allgather_matmul(x, ws, topo=None, axis: str = "tp", *, chunks: int = 1,
                     bidirectional: bool = False, quantized: bool = False,
                     reference: bool = False,
                     batch_axes=("dp", "fsdp"), seq_axes=("sp",)):
    """Column-parallel decomposed collective matmul on GLOBAL arrays.

    x: [B, S, K] (or [S, K]) with S gatherable over ``axis``; ws: one
    weight [K, N_j] (or a sequence of them sharing x — qkv ride ONE ring).
    Returns outputs [B, S, N_j] with N_j sharded over ``axis`` (and S
    still sharded over ``seq_axes``). Requires B % (batch axes), S %
    (seq axes × tp) and N_j % tp to divide; callers check via
    :func:`tp_in_proj` and fall back."""
    topo = topo or current_topology()
    single = not isinstance(ws, (list, tuple))
    ws_ = [ws] if single else list(ws)
    x3, squeeze = _as3d(x)
    tp = topo.sizes[axis]
    in_specs = (
        (P(batch_axes, (*seq_axes, axis), None),)
        + tuple(P(None, axis) for _ in ws_)
    )
    out_specs = tuple(P(batch_axes, seq_axes, axis) for _ in ws_)

    def body(xl, *wl):
        if reference:
            outs = _ref_allgather_matmul(
                xl, wl, axis, tp, quantized=quantized
            )
        else:
            outs = _ring_allgather_matmul(
                xl, wl, axis, tp, chunks=chunks,
                bidirectional=bidirectional, quantized=quantized,
            )
        return tuple(outs)

    outs = _shard_map_full(body, topo, in_specs, out_specs)(x3, *ws_)
    if squeeze:
        outs = tuple(o[0] for o in outs)
    return outs[0] if single else tuple(outs)


def matmul_reducescatter(x, w, topo=None, axis: str = "tp", *,
                         scatter: str = "seq", gather_result: bool = False,
                         chunks: int = 1, bidirectional: bool = False,
                         quantized: bool = False, reference: bool = False,
                         batch_axes=("dp", "fsdp"), seq_axes=("sp",)):
    """Row-parallel decomposed collective matmul on GLOBAL arrays.

    x: [B, S, K] (or [S, K]) with K sharded over ``axis``; w: [K, N] row-
    sharded. scatter="seq" returns [B, S, N] sequence-sharded over
    (seq_axes, axis) — the Megatron-SP layout; scatter="features" returns
    the feature dim sharded (S need not divide — the decode form), and
    ``gather_result=True`` appends a stock all-gather so the output comes
    back replicated over ``axis`` (decomposed all-reduce: the
    reduce-scatter half hides under the matmul ring, only the gather half
    stays on the wire)."""
    topo = topo or current_topology()
    x3, squeeze = _as3d(x)
    tp = topo.sizes[axis]
    if scatter == "seq":
        in_specs = (P(batch_axes, seq_axes, axis), P(axis, None))
        out_specs = P(batch_axes, (*seq_axes, axis), None)
    else:
        in_specs = (P(None, None, axis), P(axis, None))
        out_specs = P(None, None, axis)

    def body(xl, wl):
        if reference:
            out = _ref_matmul_reducescatter(
                xl, wl, axis, tp, quantized=quantized, scatter=scatter
            )
        else:
            out = _ring_matmul_reducescatter(
                xl, wl, axis, tp, chunks=chunks,
                bidirectional=bidirectional, quantized=quantized,
                scatter=scatter,
            )
        if scatter == "features" and gather_result:
            out = collectives.all_gather(
                out, axis, gather_dimension=2, tiled=True
            )
        return out

    if scatter == "features" and gather_result:
        out_specs = P(None, None, None)
    out = _shard_map_full(body, topo, in_specs, out_specs)(x3, w)
    return out[0] if squeeze else out


def _forward_quantized(plain_fn, quant_fn):
    """Straight-through wrapper for quantized hop wires in TRAINING.

    Quantizing the wire is a forward-value approximation, not a gradient
    transformation: the int8 casts inside the ring otherwise zero the
    activation cotangents (integer arrays carry float0 tangents), which
    would silently cut every layer below the projection off from the
    loss. Forward runs the quantized ring; backward is the exact
    unquantized transpose (full-width backward wires — the same split
    ZeRO++ makes between qwZ forward gathers and the separate qgZ
    gradient knob)."""

    @jax.custom_vjp
    def f(*args):
        return quant_fn(*args)

    def fwd(*args):
        return quant_fn(*args), args

    def bwd(args, g):
        _, vjp = jax.vjp(plain_fn, *args)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


# ------------------------------------------------- model-facing dispatchers
def _active(topo):
    cfg = current_overlap()
    if cfg is None:
        return None
    if topo is None or topo.tp_size <= 1:
        return None
    if manual_axis_names():
        return None  # pipeline manual shard_map: cannot nest, fall back
    return cfg


def _dense(w) -> bool:
    from ..ops.quantizer import PackedWeight

    return not isinstance(w, PackedWeight)


def _div(a: int, b: int) -> bool:
    return b > 0 and a % b == 0


def tp_in_proj(x, ws: Sequence[jax.Array]):
    """Column-parallel projection(s) sharing one gathered activation.

    With the overlap scope active and shapes dividing, all ``ws`` ride ONE
    ring (qkv cost one gather, not three); otherwise returns the plain
    einsum per weight (GSPMD inserts whatever collective the layout
    needs). Always returns a tuple aligned with ``ws``."""
    from ..ops.pallas.quantized_matmul import packed_proj

    topo = current_topology()
    cfg = _active(topo)
    if (
        cfg is not None
        and x.ndim == 3
        and all(_dense(w) and w.ndim == 2 for w in ws)
        and _div(x.shape[0], topo.sizes["dp"] * topo.sizes["fsdp"])
        and _div(x.shape[1], topo.sizes["sp"] * topo.tp_size)
        and all(_div(w.shape[1], topo.tp_size) for w in ws)
    ):
        kw = dict(chunks=int(cfg.chunks),
                  bidirectional=bool(cfg.bidirectional))
        if cfg.quantized_hops:
            return _forward_quantized(
                lambda a, *w: allgather_matmul(a, list(w), topo, **kw),
                lambda a, *w: allgather_matmul(
                    a, list(w), topo, quantized=True, **kw
                ),
            )(x, *ws)
        return allgather_matmul(x, list(ws), topo, **kw)
    return tuple(packed_proj(x, w) for w in ws)


def tp_out_proj(x, w):
    """Row-parallel projection. With the overlap scope active: the
    sequence-scatter ring when the sequence divides (training/prefill —
    output arrives sequence-sharded over (sp, tp), which the surrounding
    block keeps for the residual path), else the feature-scatter +
    gather ring (decode: S=1 cannot shard, so the all-reduce decomposes
    and its reduce-scatter half hides under the matmul). Falls back to
    the plain einsum (GSPMD all-reduce) otherwise."""
    from ..ops.pallas.quantized_matmul import packed_proj

    topo = current_topology()
    cfg = _active(topo)
    if cfg is None or not _dense(w) or x.ndim != 3 or w.ndim != 2:
        return packed_proj(x, w)
    kw = dict(
        chunks=int(cfg.chunks),
        bidirectional=bool(cfg.bidirectional),
    )
    tp = topo.tp_size
    if not _div(x.shape[2], tp):
        return packed_proj(x, w)

    def run(**form):
        if cfg.quantized_hops:
            return _forward_quantized(
                lambda a, b: matmul_reducescatter(a, b, topo, **form, **kw),
                lambda a, b: matmul_reducescatter(
                    a, b, topo, quantized=True, **form, **kw
                ),
            )(x, w)
        return matmul_reducescatter(x, w, topo, **form, **kw)

    if (
        _div(x.shape[0], topo.sizes["dp"] * topo.sizes["fsdp"])
        and _div(x.shape[1], topo.sizes["sp"] * tp)
    ):
        return run(scatter="seq")
    if _div(w.shape[1], tp) and (
        x.shape[1] == 1
        or topo.sizes["dp"] * topo.sizes["fsdp"] == 1
    ):
        # decode-shaped only: the feature form's in_specs replicate the
        # batch over dp — free for serving (batch already replicated),
        # but in dp-sharded training it would all-gather the batch and
        # redundantly compute the projection everywhere, so a training
        # shape that misses the seq form falls back to GSPMD instead
        return run(scatter="features", gather_result=True)
    return packed_proj(x, w)


def seq_shard_axes(x=None):
    """Sequence-dim sharding entry for activation constraints at block
    boundaries: ("sp", "tp") while the overlap scope is active (the
    Megatron-SP layout the scatter ring produces and the gather ring
    consumes — residual adds then cost zero collectives), plain "sp"
    otherwise.

    Pass the activation so the layout decision uses the SAME divisibility
    predicate as the projection dispatchers: when the rings will fall
    back (S=1 decode, a sequence sp·tp doesn't divide, an awkward batch),
    constraining the residual stream over tp anyway would buy a reshard
    per block boundary for nothing."""
    topo = current_topology()
    if _active(topo) is None:
        return "sp"
    if x is not None and x.ndim >= 3:
        if not (
            _div(x.shape[-2], topo.sizes["sp"] * topo.tp_size)
            and _div(x.shape[-3], topo.sizes["dp"] * topo.sizes["fsdp"])
        ):
            return "sp"
    return ("sp", "tp")


def _proj_widths(model_cfg) -> List[int]:
    """Every projection width the wired transformer rings touch — ONE
    enumeration shared by the static gate and the byte accounting so the
    two can never drift."""
    widths = [model_cfg.hidden_size, getattr(model_cfg, "ffn",
                                             model_cfg.hidden_size)]
    if hasattr(model_cfg, "num_heads") and hasattr(model_cfg, "hd"):
        widths.append(model_cfg.num_heads * model_cfg.hd)
        kv = getattr(model_cfg, "kv_heads", model_cfg.num_heads)
        widths.append(kv * model_cfg.hd)
    return widths


def static_widths_divide(model_cfg, tp: int) -> bool:
    """Whether the transformer's projection widths divide tp — the static
    half of the dispatchers' predicates. Engines gate the overlap scope on
    this at construction: widths never change at runtime, so a model that
    fails here would pay the (sp, tp) residual layout for rings that can
    never engage. (The dynamic half — batch/seq divisibility — is checked
    per activation by seq_shard_axes and the dispatchers.)"""
    if not hasattr(model_cfg, "hidden_size"):
        return True  # not transformer-shaped: the dispatchers decide
    return all(_div(w, tp) for w in _proj_widths(model_cfg))


# ----------------------------------------------------------- ring accounting
def ring_wire_bytes_per_step(model_cfg, topo, cfg, batch: int, seq: int,
                             itemsize: int = 2,
                             accum_steps: int = 1) -> Optional[dict]:
    """Analytic per-device ring bytes for ONE optimizer step of the wired
    transformer (trace-time comm hooks under-count scanned layers, so the
    engine reports this static figure to the comms logger instead).

    Per layer, four rings: one gather (qkv, shared), one seq-scatter
    (attn-out), one gather (mlp-in [+gate]), one seq-scatter (mlp-out).
    Wire bytes per ring = payload × (tp-1) hops (bidirectional sends the
    same total split across both directions; quantized hops shrink the
    payload to int8 + fp32 lane scales). Backward doubles it: the
    transpose of a ppermute ring is the reversed ring carrying
    same-shaped cotangents. Returns None for non-transformer models."""
    for attr in ("hidden_size", "num_layers"):
        if not hasattr(model_cfg, attr):
            return None
    tp = topo.tp_size
    if tp <= 1 or cfg is None or not getattr(cfg, "enabled", False):
        return None
    dpf = topo.sizes["dp"] * topo.sizes["fsdp"]
    sp = topo.sizes["sp"]
    d = model_cfg.hidden_size
    # same divisibility predicates the dispatchers apply — when they would
    # fall back to plain GSPMD projections, NO ring runs and the honest
    # figure is "nothing streamed", not a phantom 4-rings-per-layer count
    # (seq <= 0 means the caller had no sequence length to offer: same)
    if (
        seq <= 0
        or batch <= 0
        or not _div(batch, dpf)
        or not _div(seq, sp * tp)
        or not static_widths_divide(model_cfg, tp)
    ):
        return None
    b_loc = max(batch // max(dpf, 1), 1)
    s_blk = max(seq // max(sp * tp, 1), 1)
    hops = tp - 1

    def gather_wire(k_width, quantized):
        if quantized:
            return (b_loc * s_blk * k_width * 1 + k_width * 4) * hops
        return b_loc * s_blk * k_width * itemsize * hops

    def scatter_wire(n_width, quantized):
        # riding accumulator is fp32 (int8 + lane scales when quantized)
        if quantized:
            return (b_loc * s_blk * n_width * 1 + n_width * 4) * hops
        return b_loc * s_blk * n_width * 4 * hops

    def per_layer(quantized):
        return (
            gather_wire(d, quantized)   # qkv in-projection (shared ring)
            + scatter_wire(d, quantized)  # attention out-projection
            + gather_wire(d, quantized)   # mlp in-projection (+gate)
            + scatter_wire(d, quantized)  # mlp out-projection
        )

    steps = max(accum_steps, 1)
    layers = model_cfg.num_layers
    quantized = bool(getattr(cfg, "quantized_hops", False))
    fwd = per_layer(quantized) * layers * steps
    plain = per_layer(False) * layers * steps
    # backward: the transposed rings carry full-width cotangents. With
    # quantized_hops the straight-through VJP additionally REPLAYS the
    # unquantized forward ring inside jax.vjp before transposing — so the
    # backward wire is ~2x the plain forward, not a mirror of the int8 one.
    bwd = 2 * plain if quantized else plain
    return {
        "bytes_per_step": fwd + bwd,
        "fwd_bytes_per_step": fwd,
        "rings_per_layer": 4,
        "hops_per_ring": hops,
    }
