"""Communication logger.

Parity: deepspeed/comm/comm.py comms_logger + deepspeed/utils/comms_logging.py.
Subscribes to the hook bus in deepspeed_tpu.comm.collectives; every collective
issued from shard_map code (pipeline p2p, MoE all-to-all, Ulysses exchange,
1-bit optimizer comms) is recorded at *trace time* with op name, mesh axis and
payload bytes. XLA-inserted collectives (from sharding annotations) are not
visible here — they are surfaced by the flops profiler's HLO pass instead.

Bandwidth estimates use the reference's algbw/busbw formulas
(deepspeed/utils/comms_logging.py get_bw): busbw applies the (n-1)/n ring
correction for all_gather/reduce_scatter/all_reduce (2x).
"""

from __future__ import annotations

import math
import time
from collections import defaultdict
from typing import Dict, List, Optional

from ..comm.collectives import register_comm_hook, unregister_comm_hook
from ..utils.logging import log_dist


def get_bw(comm_op: str, size_bytes: int, duration_s: float, n: int) -> tuple:
    """(algbw, busbw) in Gbps. Parity: deepspeed/utils/comms_logging.get_bw."""
    if duration_s <= 0:
        return 0.0, 0.0
    tput = size_bytes * 8 / duration_s / 1e9  # Gbps
    if comm_op in ("all_to_all", "all_to_all_single"):
        busbw = tput * ((n - 1) / n)
    elif comm_op in ("all_gather", "all_gather_into_tensor", "reduce_scatter",
                     "reduce_scatter_tensor"):
        busbw = tput * ((n - 1) / n)
    elif comm_op in ("all_reduce",):
        busbw = tput * (2 * (n - 1) / n)
    else:  # send/recv/broadcast/ppermute/barrier
        busbw = tput
    return tput, busbw


class CommsLogger:
    """Records per-op counts/bytes; prints a summary table on demand.

    With a steptrace ``registry`` attached (profiling/steptrace.py),
    every analytic-stream record (``record_streams`` / ``record_ring``
    / ``record_offload`` / ``record_kv``) also emits a ``comm/*``
    registry sample, so a traced run sees the hidden-stream accounting
    on the same timeline as its spans. ``registry=None`` (default) is
    the zero-overhead path."""

    def __init__(self, config=None, registry=None):
        self.verbose = bool(getattr(config, "verbose", False))
        self.prof_all = bool(getattr(config, "prof_all", True))
        self.registry = registry
        self.prof_ops: List[str] = list(getattr(config, "prof_ops", []) or [])
        self.counts: Dict[str, int] = defaultdict(int)
        self.bytes: Dict[str, int] = defaultdict(int)
        self.per_axis: Dict[tuple, int] = defaultdict(int)
        # offload-stream accounting (bucketed ZeRO-offload update): the
        # host↔HBM optimizer-state DMA is not a collective, so the hook bus
        # never sees it — the engine reports it explicitly per step
        self.offload_steps = 0
        self.offload_bytes_in = 0
        self.offload_bytes_out = 0
        self.offload_slots = 0
        self.offload_slot_bytes = 0
        # decomposed-ring accounting (tensor_parallel.overlap_comm rings
        # AND the moe.overlap_a2a exchange hops AND the stage3 prefetch
        # gathers — every "ici"-kind analytic stream): scanned layers
        # trace their ring hops once, so the hook bus under-counts them —
        # the engine reports the analytic per-step wire bytes here
        # (tensor_overlap.ring_wire_bytes_per_step,
        # a2a_overlap.moe_a2a_bytes_per_step,
        # prefetch.prefetch_wire_bytes_per_step)
        self.ring_steps = 0
        self.ring_bytes = 0
        # serving KV-arena accounting (serving/engine.analytic_streams):
        # the slot engine's per-step cache read/write is plain HBM
        # traffic, not a collective — reported analytically per step
        self.kv_steps = 0
        self.kv_bytes = 0
        self._t0 = time.time()
        register_comm_hook(self._on_op)

    def _enabled_for(self, op: str) -> bool:
        return self.prof_all or op in self.prof_ops

    @staticmethod
    def _axis_names(axis) -> tuple:
        if isinstance(axis, str):
            return (axis,)
        return tuple(str(a) for a in axis)

    def _on_op(self, op: str, axis, nbytes: int) -> None:
        if not self._enabled_for(op):
            return
        self.counts[op] += 1
        self.bytes[op] += nbytes
        self.per_axis[(op, self._axis_names(axis))] += nbytes
        if self.verbose:
            log_dist(f"comm: {op} axis={axis} bytes={nbytes}")

    def stop(self) -> None:
        unregister_comm_hook(self._on_op)

    # ------------------------------------------------ offload stream stats
    def record_offload(self, nbytes_in: int, nbytes_out: int,
                       slots: int = 1, slot_bytes: int = 0,
                       steps: int = 1) -> None:
        """Account one (or ``steps`` chained) bucketed-offload optimizer
        steps: ``nbytes_in``/``nbytes_out`` are the per-step host→HBM and
        HBM→host stream totals, ``slots`` the rotating-buffer depth (2 when
        double-buffered) and ``slot_bytes`` one layer slice — so
        ``slots * slot_bytes`` is the peak bytes in flight."""
        self.offload_steps += steps
        self.offload_bytes_in += nbytes_in * steps
        self.offload_bytes_out += nbytes_out * steps
        self.offload_slots = max(self.offload_slots, slots)
        self.offload_slot_bytes = max(self.offload_slot_bytes, slot_bytes)
        if self.registry is not None:
            self.registry.sample(
                "comm/offload_bytes_per_step", nbytes_in + nbytes_out,
                step=self.offload_steps,
            )

    @property
    def offload_bytes_in_flight(self) -> int:
        """Peak concurrent offload-stream bytes (slots × one layer slice)."""
        return self.offload_slots * self.offload_slot_bytes

    # ------------------------------------------------- TP overlap ring stats
    def record_ring(self, nbytes_per_step: int, steps: int = 1) -> None:
        """Account ``steps`` steps of decomposed-ring traffic (the ONE
        intake for every "ici"-kind analytic stream: TP projection rings,
        MoE a2a chunk hops, stage-3 prefetch gathers):
        ``nbytes_per_step`` is the per-device wire total across all rings
        of one optimizer step (forward + transposed backward hops)."""
        self.ring_steps += steps
        self.ring_bytes += nbytes_per_step * steps
        if self.registry is not None:
            self.registry.sample("comm/ring_bytes_per_step", nbytes_per_step,
                                 step=self.ring_steps)

    # -------------------------------------------------- serving KV stats
    def record_kv(self, nbytes_per_step: int, steps: int = 1) -> None:
        """Account ``steps`` serving-engine steps of slot-KV-arena HBM
        traffic (``nbytes_per_step`` = analytic k+v arena bytes streamed
        per step; serving/engine.serving_kv_stream)."""
        self.kv_steps += steps
        self.kv_bytes += nbytes_per_step * steps
        if self.registry is not None:
            self.registry.sample("comm/kv_bytes_per_step", nbytes_per_step,
                                 step=self.kv_steps)

    def kv_summary(self, duration_s: Optional[float] = None) -> str:
        """One line of serving KV-arena accounting (empty when idle)."""
        if not self.kv_steps:
            return ""
        dur = self.elapsed if duration_s is None else duration_s
        per_step = self.kv_bytes / self.kv_steps
        gbps = self.kv_bytes * 8 / dur / 1e9 if dur > 0 else 0.0
        return (
            f"serving kv arena: {self.kv_steps} steps, "
            f"{per_step / 2**20:.2f} MiB/step (k+v stream), "
            f"{gbps:.2f} Gbps over window"
        )

    # ------------------------------------------------ shared stream intake
    def record_streams(self, streams, steps: int = 1) -> None:
        """ONE analytic-stream accounting path for every hidden-stream
        subsystem: takes the normalized dict ``engine.analytic_streams()``
        produces (also what the cost planner and rule R8 consume) and
        dispatches to the per-kind accounting. Streams the mesh cannot
        actually run (``assumed: True`` — the CPU lint mesh pricing a
        declared offload) are planner-only and never recorded."""
        for s in (streams or {}).values():
            if not s or s.get("assumed"):
                continue
            kind = s.get("kind")
            if kind == "offload":
                # the schema guarantees bytes_per_step; the engine's
                # richer dicts split it into in/out halves
                half = s.get("bytes_per_step", 0) // 2
                self.record_offload(
                    s.get("bytes_in", half), s.get("bytes_out", half),
                    slots=s.get("slots", 1),
                    slot_bytes=s.get("slot_bytes", 0),
                    steps=steps,
                )
            elif kind == "ici":
                self.record_ring(s.get("bytes_per_step", 0), steps=steps)
            elif kind == "hbm":
                # the serving engine's per-step KV-arena stream
                self.record_kv(s.get("bytes_per_step", 0), steps=steps)

    def ring_summary(self, duration_s: Optional[float] = None) -> str:
        """One line of ring-wire accounting (empty when no rings ran)."""
        if not self.ring_steps:
            return ""
        dur = self.elapsed if duration_s is None else duration_s
        per_step = self.ring_bytes / self.ring_steps
        gbps = self.ring_bytes * 8 / dur / 1e9 if dur > 0 else 0.0
        return (
            f"decomposed rings (tp/a2a/prefetch): {self.ring_steps} steps, "
            f"{per_step / 2**20:.2f} MiB/step wire (fwd+bwd hops), "
            f"{gbps:.2f} Gbps over window"
        )

    @staticmethod
    def overlap_ratio(serial_step_s: float, overlapped_step_s: float,
                      stream_s: float) -> float:
        """Fraction of a hidden stream's wall time actually hidden under
        compute, from a serial-vs-overlapped A/B: the stream time that
        stopped being exposed, over the stream there was to hide. 0 =
        fully serialized, 1 = fully overlapped. ``stream_s`` is the
        estimated stream wall time (bytes / link bandwidth) — the
        offload A/B passes the host-DMA seconds, the decomposed-TP ring
        A/B the ring-wire seconds.

        This is THE hardened degenerate-input path (there is exactly
        one): an empty/zero-byte stream (stream_s 0), unmeasured step
        times (0 or negative), NaN/inf from a failed A/B leg, or
        non-numeric inputs all report 0.0 (nothing demonstrably
        overlapped) instead of raising, so a bench summary never dies on
        its accounting line."""
        vals = (serial_step_s, overlapped_step_s, stream_s)
        try:
            finite = all(math.isfinite(float(v)) for v in vals)
        except (TypeError, ValueError):
            return 0.0
        if not finite or stream_s <= 0 or serial_step_s <= 0 \
                or overlapped_step_s <= 0:
            return 0.0
        ratio = (serial_step_s - overlapped_step_s) / stream_s
        return max(0.0, min(1.0, ratio))

    # legacy spelling (PR-1 offload A/B callers): same function — the
    # offload ratio IS the generic overlap ratio with DMA seconds
    offload_overlap_ratio = overlap_ratio

    def offload_summary(self, duration_s: Optional[float] = None) -> str:
        """One line of offload-stream accounting (empty when none ran)."""
        if not self.offload_steps:
            return ""
        dur = self.elapsed if duration_s is None else duration_s
        total = self.offload_bytes_in + self.offload_bytes_out
        gbps = total * 8 / dur / 1e9 if dur > 0 else 0.0
        per_step = total / self.offload_steps
        return (
            f"offload stream: {self.offload_steps} steps, "
            f"{per_step / 2**30:.2f} GiB/step (in+out), "
            f"{self.offload_bytes_in_flight / 2**20:.1f} MiB in flight "
            f"({self.offload_slots} slot(s)), {gbps:.2f} Gbps over window"
        )

    @property
    def elapsed(self) -> float:
        return time.time() - self._t0

    def summary(
        self,
        axis_sizes: Optional[Dict[str, int]] = None,
        duration_s: Optional[float] = None,
    ) -> str:
        """Render the reference's log_summary()-style table.

        With ``duration_s`` (default: wall time since construction) and
        ``axis_sizes`` (topology.sizes), adds the reference's algbw/busbw
        columns — aggregate estimates over the whole window, since per-op
        timing does not exist inside a fused XLA program."""
        dur = self.elapsed if duration_s is None else duration_s
        lines = [
            f"{'op':<22}{'count':>8}{'total bytes':>16}{'avg bytes':>14}"
            f"{'algbw(Gbps)':>13}{'busbw(Gbps)':>13}"
        ]
        for op in sorted(self.counts):
            c, b = self.counts[op], self.bytes[op]
            # largest participating axis-group degree for the busbw correction
            n = 1
            for (o, axis_names), _bytes in self.per_axis.items():
                if o != op or not axis_sizes:
                    continue
                group = 1
                for name in axis_names:
                    group *= axis_sizes.get(name, 1)
                n = max(n, group)
            alg, bus = get_bw(op, b, dur, max(n, 2))
            lines.append(
                f"{op:<22}{c:>8}{b:>16}{b // max(c, 1):>14}{alg:>13.3f}{bus:>13.3f}"
            )
        off = self.offload_summary(duration_s=dur)
        if off:
            lines.append(off)
        ring = self.ring_summary(duration_s=dur)
        if ring:
            lines.append(ring)
        kv = self.kv_summary(duration_s=dur)
        if kv:
            lines.append(kv)
        return "\n".join(lines)

    def log_summary(self, axis_sizes: Optional[Dict[str, int]] = None) -> None:
        log_dist("comms summary (trace-time ops)\n" + self.summary(axis_sizes))

    def write_to(self, monitor, step: int) -> None:
        """Feed the monitor backends through the steptrace registry's
        single ``write_events`` bridge (one coherent ``comm/*``
        namespace next to ``train/*``/``serve/*``/``plan/*``)."""
        from .steptrace import write_events

        events = [
            (f"comm/{op}_bytes", float(b), step)
            for op, b in sorted(self.bytes.items())
        ]
        # _avg tags: these are running means over the whole window — the
        # per-step instantaneous samples live under the un-suffixed tags
        # (record_offload/record_ring/record_kv registry emitters); one
        # tag must never carry both semantics
        if self.offload_steps:
            events.append((
                "comm/offload_bytes_per_step_avg",
                float(self.offload_bytes_in + self.offload_bytes_out)
                / self.offload_steps, step,
            ))
        if self.ring_steps:
            events.append((
                "comm/ring_bytes_per_step_avg",
                float(self.ring_bytes) / self.ring_steps, step,
            ))
        if self.kv_steps:
            events.append((
                "comm/kv_bytes_per_step_avg",
                float(self.kv_bytes) / self.kv_steps, step,
            ))
        write_events(monitor, events)
