"""From a profiler trace to numbers: the benchmark's own reduction.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` (nothing but jax)
into plain lists, and :func:`reduce_trace` turns those into what the
per-layer readers use:

* busy time: the union of the intervals in which an operation runs on a
  device's ``XLA Ops`` line, per device, averaged over the devices;
* self time per device operation: an operation's duration minus the part
  its nested children cover (a ``while`` holds its body's operations);
* collectives: the intervals of collective operations, and the part of them
  during which no other operation of that device runs ("exposed");
* idle gaps: the stretches of the first device's timeline with no operation,
  each attributed to the benchmark's host span (``bench/...``, written with
  ``jax.profiler.TraceAnnotation``) that covers most of it.

    python benchmarks/trace_reduce.py <trace dir or .xplane.pb>

prints what a trace holds (planes, lines, the heaviest events and their
stats): look at one by hand before trusting a pattern.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # start..done spans of asynchronous operations
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
# HLO instruction names of cross-device operations, sync or async halves
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?(\.|$)")

# On a TPU an event of the "XLA Ops" line is named by its whole HLO
# instruction: "%fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion(...), kind=..."
_HLO = re.compile(r"^%?(?P<instr>\S+) = \(?(?P<shape>[a-z]+[0-9]*\[[0-9,]*\])?"
                  r".*?\s(?P<op>[a-z][a-z0-9\-]*)\(")
PALLAS_CALL = "tpu_custom_call"  # what a Pallas kernel is on the device

Interval = Tuple[float, float]


def op_label(name: str) -> str:
    """``"<instruction> <first result shape> <opcode>"`` of an HLO event
    name ("fusion.7 bf16[8,128] fusion", "closed_call.16 bf16[4,16,2048,64]
    tpu_custom_call"); any other name as it is."""
    m = _HLO.match(name)
    if not m:
        return name[:96]
    op = PALLAS_CALL if f'custom_call_target="{PALLAS_CALL}"' in name else m["op"]
    return " ".join(x for x in (m["instr"], m["shape"], op) if x)


@dataclass
class Event:
    name: str
    start: float  # ns
    dur: float    # ns
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


Trace = Dict[str, Dict[str, List[Event]]]  # plane -> line -> events


# ----------------------------------------------------------------- loading
def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return hits[-1]


def _plain(profile, keep_stats: bool) -> Trace:
    out: Trace = {}
    for plane in profile.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                stats = {}
                if keep_stats:
                    for k, v in e.stats:
                        if isinstance(v, (str, int, float)):
                            stats[k] = v
                evs.append(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns), stats))
    return out


def load(path: str, keep_stats: bool = True) -> Trace:
    from jax.profiler import ProfileData

    return _plain(ProfileData.from_file(find_xplane(path)), keep_stats)


def load_text_proto(text: str, keep_stats: bool = True) -> Trace:
    """A hand-written XSpace in protobuf text format (the unit tests)."""
    from jax.profiler import ProfileData

    return _plain(ProfileData.from_text_proto(text), keep_stats)


# --------------------------------------------------------------- intervals
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by the
    (disjoint, sorted) intervals ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def overlap(a: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(max(0.0, min(y, hi) - max(x, lo)) for x, y in a)


def self_segments(events: Sequence[Event]) -> List[Tuple[Event, float, float]]:
    """Split one timeline's (properly nested) events into disjoint segments,
    each owned by the innermost event running then."""
    segs: List[Tuple[Event, float, float]] = []
    stack: List[list] = []  # [event, end, cursor]

    def close(t: float) -> None:
        while stack and stack[-1][1] <= t:
            ev, end, cur = stack.pop()
            if end > cur:
                segs.append((ev, cur, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for ev in sorted(events, key=lambda e: (e.start, -e.dur)):
        close(ev.start)
        end = ev.end
        if stack:
            top = stack[-1]
            if ev.start > top[2]:
                segs.append((top[0], top[2], ev.start))
            top[2] = max(top[2], ev.start)
            end = min(end, top[1])  # a child never outlives its parent
        stack.append([ev, end, ev.start])
    close(float("inf"))
    return segs


# --------------------------------------------------------------- reduction
@dataclass
class Reduced:
    devices: int
    window: Interval                       # ns, on the trace's clock
    busy: Dict[int, List[Interval]]        # per device
    self_ns: Dict[str, float]              # op label -> ns, summed over devices
    collective: Dict[int, List[Interval]]
    exposed_collective_ns: float           # summed over devices
    spans: Dict[str, List[Interval]]       # host span name -> intervals

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(total(b) for b in self.busy.values()) / self.devices / 1e9

    def idle_pct(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def exposed_collective_pct(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * self.exposed_collective_ns / self.devices / (
            self.window[1] - self.window[0])

    def op_seconds(self, pattern: str) -> float:
        """Self seconds per device of operations whose label (see
        :func:`op_label`) matches ``pattern``."""
        rx = re.compile(pattern)
        ns = sum(t for name, t in self.self_ns.items() if rx.search(name))
        return ns / max(self.devices, 1) / 1e9

    def pallas_seconds(self) -> float:
        """Self seconds per device inside Pallas kernels."""
        return self.op_seconds(rf" {PALLAS_CALL}$")

    def top_ops(self, n: int = 10) -> List[List[object]]:
        """[[name, seconds per device], ...] by self time."""
        ranked = sorted(self.self_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(self.devices, 1) / 1e9] for k, v in ranked]

    def gaps(self, device: Optional[int] = None) -> List[Interval]:
        if not self.busy:
            return []
        dev = min(self.busy) if device is None else device
        return subtract([self.window], self.busy[dev])

    def span_of(self, lo: float, hi: float) -> str:
        """The host span covering most of [lo, hi]."""
        best, best_cov = "(no bench span)", 0.0
        for name, ivs in self.spans.items():
            cov = overlap(ivs, lo, hi)
            if cov > best_cov:
                best, best_cov = name, cov
        return best

    def idle_gaps(self, longest: int = 5, sums: int = 5) -> List[List[object]]:
        """The ``longest`` idle gaps as [span, seconds], then the idle time
        summed by span as ["sum " + span, seconds]."""
        gaps = [(hi - lo, self.span_of(lo, hi)) for lo, hi in self.gaps()]
        gaps.sort(reverse=True)
        out: List[List[object]] = [[n, d / 1e9] for d, n in gaps[:longest]]
        by: Dict[str, float] = defaultdict(float)
        for d, n in gaps:
            by[n] += d
        for n, d in sorted(by.items(), key=lambda kv: -kv[1])[:sums]:
            out.append(["sum " + n, d / 1e9])
        return out

    def host_outside_device_s(self, span: str) -> List[float]:
        """For every host span of that name: its length minus the time the
        first device was busy inside it."""
        if not self.busy:
            return []
        busy = self.busy[min(self.busy)]
        return [((hi - lo) - overlap(busy, lo, hi)) / 1e9
                for lo, hi in self.spans.get(span, [])]


def reduce_trace(trace: Trace) -> Reduced:
    busy: Dict[int, List[Interval]] = {}
    coll: Dict[int, List[Interval]] = {}
    self_ns: Dict[str, float] = defaultdict(float)
    exposed = 0.0
    lo, hi = float("inf"), float("-inf")
    for plane, lines in trace.items():
        m = DEVICE_PLANE.match(plane)
        if not m or OPS_LINE not in lines:
            continue
        dev = int(m.group(1))
        segs = self_segments(lines[OPS_LINE])
        compute, collective = [], []
        labels: Dict[str, str] = {}
        for ev, a, b in segs:
            label = labels.get(ev.name) or labels.setdefault(
                ev.name, op_label(ev.name))
            self_ns[label] += b - a
            (collective if COLLECTIVE.match(label) else compute).append((a, b))
        # an asynchronous collective is in flight from its -start to its
        # -done: that whole span counts, and what compute covers is hidden
        collective += [(e.start, e.end) for e in lines.get(ASYNC_LINE, [])
                       if COLLECTIVE.match(op_label(e.name))]
        busy[dev] = union([(a, b) for _, a, b in segs])
        coll[dev] = union(collective)
        exposed += total(subtract(coll[dev], union(compute)))
        if busy[dev]:
            lo, hi = min(lo, busy[dev][0][0]), max(hi, busy[dev][-1][1])
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for line in trace.get(HOST_PLANE, {}).values():
        for ev in line:
            if ev.name.startswith(SPAN_PREFIX):
                spans[ev.name].append((ev.start, ev.end))
                lo, hi = min(lo, ev.start), max(hi, ev.end)
    if lo > hi:
        lo = hi = 0.0
    return Reduced(len(busy), (lo, hi), busy, dict(self_ns), coll, exposed,
                   {k: sorted(v) for k, v in spans.items()})


# ---------------------------------------------------------------- describe
def describe(trace: Trace, top: int = 25) -> str:
    out = []
    for plane, lines in trace.items():
        out.append(f"PLANE {plane}")
        for name, evs in lines.items():
            span = (f"{(max(e.end for e in evs) - min(e.start for e in evs)) / 1e6:.1f} ms"
                    if evs else "-")
            out.append(f"  LINE {name!r}: {len(evs)} events over {span}")
            agg: Dict[str, float] = defaultdict(float)
            sample: Dict[str, Event] = {}
            for e in evs:
                agg[e.name] += e.dur
                sample.setdefault(e.name, e)
            n = top if (DEVICE_PLANE.match(plane) or name == "python") else 4
            for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]:
                st = {a: (str(b)[:90]) for a, b in sample[k].stats.items()}
                out.append(f"    {v / 1e6:10.3f} ms  {op_label(k)}  {st}")
    return "\n".join(out)


if __name__ == "__main__":
    tr = load(sys.argv[1])
    print(describe(tr))
    r = reduce_trace(tr)
    print(f"devices {r.devices} window {r.window_s:.4f} s busy {r.busy_s:.4f} s"
          f" idle {r.idle_pct()} % exposed collectives "
          f"{r.exposed_collective_pct()} %")
    print("top ops:", r.top_ops())
    print("idle gaps:", r.idle_gaps())
