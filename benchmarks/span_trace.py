"""The program's own phase spans, read off the profiler's host plane.

Since PR 39 the engines open every phase span as a
``jax.profiler.TraceAnnotation`` (``deepspeed_tpu/profiling/steptrace.py``
``Phase``), so a traced run carries them on the trace's clock beside the
device's operations:

* serving, one ``serve/step`` a turn with its children ``serve/plan``,
  ``serve/dispatch`` (and ``serve/page_in`` inside it where pages are
  promoted), ``serve/device`` (the host blocked on the results of the step
  in flight) and ``serve/complete``. A turn dispatches step n+1 and folds
  step n, so ``serve/dispatch`` carries ``step=`` the number it dispatches,
  ``serve/device`` / ``serve/complete`` the number they fold, and
  ``serve/step`` both (``dispatched=``, ``folded=``; 0 = none);
* training, one ``train/step`` a call of ``train_batch`` with
  ``train/batch_prep``, ``train/dispatch`` and ``train/commit`` (what
  follows the dispatch's return), ``train/input_wait`` before it when the
  engine pulls the batch itself, and ``train/device`` only where the
  registry is on (it fences).

A program without these spans (the parent of PR 39) yields nothing here, and
a reader then reports nothing. The window, the device and its busy intervals
are ``trace_reduce.Reduced``'s, so a share of idle time read here is a part
of ``device_idle_pct`` of the same run.

    python -m benchmarks.span_trace <trace dir or .xplane.pb> [min gap ms]

prints every idle gap of the first device over ``min gap`` (20 ms) with the
innermost program span (or benchmark span) that covers half of it or more:
the place of a stall inside a turn.
"""

from __future__ import annotations

import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import kinds_trace, trace_reduce

PREFIXES = ("serve/", "train/")
SERVE_STEP, TRAIN_STEP = "serve/step", "train/step"
# the host works (the device could be waiting for it) / the host waits
SERVE_HOST_WORK = ("serve/plan", "serve/dispatch", "serve/complete",
                   "serve/page_in")
SERVE_DEVICE_WAIT = ("serve/device",)
TRAIN_HOST_WORK = ("train/input_wait", "train/batch_prep", "train/dispatch",
                   "train/commit")

# thread line -> the program's events on it, by start
Lines = Dict[str, List[trace_reduce.Event]]


def spans_of(trace: trace_reduce.Trace,
             prefixes: Sequence[str] = PREFIXES) -> Lines:
    out: Lines = {}
    for line, events in trace.get(trace_reduce.HOST_PLANE, {}).items():
        mine = [e for e in events if e.name.startswith(tuple(prefixes))]
        if mine:
            out[line] = sorted(mine, key=lambda e: (e.start, -e.dur))
    return out


def program_spans(ctx) -> Lines:
    """The traced run's ``serve/*`` and ``train/*`` events (with their
    arguments as ``stats``), read once a run; empty without a trace."""
    if getattr(ctx, "program_spans", None) is None:
        trace = kinds_trace.full_trace(ctx)
        ctx.program_spans = spans_of(trace) if trace else {}
    return ctx.program_spans


def named(lines: Lines, names: Optional[Sequence[str]] = None
          ) -> List[trace_reduce.Event]:
    """The events of those names (None: all of them), every thread's."""
    return [e for events in lines.values() for e in events
            if names is None or e.name in names]


def children_ms(lines: Lines, parent: str, children: Sequence[str],
                counted=lambda ev: True) -> List[float]:
    """For every ``parent`` event that ``counted`` accepts: the summed
    durations of the ``children`` events inside it on its thread, in ms."""
    out = []
    for events in lines.values():
        kids = [e for e in events if e.name in children]
        for p in events:
            if p.name == parent and counted(p):
                out.append(sum(k.dur for k in kids
                               if k.start >= p.start and k.end <= p.end) / 1e6)
    return out


def is_a_step(ev: trace_reduce.Event) -> bool:
    """A ``serve/step`` that dispatched or folded: a turn in which the
    scheduler planned nothing with nothing in flight says 0 and 0."""
    return bool(ev.stats.get("dispatched") or ev.stats.get("folded"))


def median_children_ms(ctx, parent: str, children: Sequence[str],
                       counted=lambda ev: True) -> Optional[float]:
    ms = children_ms(program_spans(ctx), parent, children, counted)
    return statistics.median(ms) if ms else None


def idle_inside_pct(ctx, names: Sequence[str]) -> Optional[float]:
    """Share of the traced window in which the first device runs nothing
    while the host is inside a span of ``names``; None without a device or
    without any such span."""
    r = ctx.reduced
    if r is None or not r.busy or r.window_s <= 0:
        return None
    events = named(program_spans(ctx), names)
    if not events:
        return None
    inside = trace_reduce.union([(e.start, e.end) for e in events])
    gaps = r.gaps()
    idle_ns = trace_reduce.total(gaps) - trace_reduce.total(
        trace_reduce.subtract(gaps, inside))
    return 100.0 * idle_ns / (r.window[1] - r.window[0])


# ---------------------------------------------------------------- describe
def gap_places(trace: trace_reduce.Trace, min_s: float = 0.02
               ) -> List[Tuple[float, float, str, dict]]:
    """[(seconds into the window, seconds long, place, the place's
    arguments)] for every idle gap of the first device of at least
    ``min_s``: the place is the innermost program or benchmark span that
    covers at least half of the gap, or else the one that covers most."""
    r = trace_reduce.reduce_trace(trace)
    spans = named(spans_of(trace, (*PREFIXES, trace_reduce.SPAN_PREFIX)))
    out = []
    for lo, hi in r.gaps():
        if hi - lo < min_s * 1e9:
            continue
        best, key = None, (False, 0.0, 0.0)
        for e in spans:
            cov = min(e.end, hi) - max(e.start, lo)
            half = 2 * cov >= hi - lo
            # past half the shortest span wins, short of it the widest cover
            rank = (half, -e.dur if half else cov, -e.dur)
            if cov > 0 and rank > key:
                best, key = e, rank
        out.append(((lo - r.window[0]) / 1e9, (hi - lo) / 1e9,
                    best.name if best else "(no span)",
                    dict(best.stats) if best else {}))
    return out


def describe(trace: trace_reduce.Trace, min_s: float = 0.02) -> str:
    r = trace_reduce.reduce_trace(trace)
    lines = spans_of(trace)
    out = [f"window {r.window_s:.4f} s, idle {r.idle_pct()} %"]
    by: Dict[str, List[float]] = defaultdict(list)
    for e in named(lines):
        by[e.name].append(e.dur / 1e6)
    for name, ms in sorted(by.items()):
        out.append(f"  {name:<24}{len(ms):>6} x  median "
                   f"{statistics.median(ms):9.3f} ms  longest {max(ms):9.3f}")
    for at, dur, place, args in gap_places(trace, min_s):
        out.append(f"  gap of {dur:.4f} s at {at:.4f} s: inside {place} {args}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(trace_reduce.load(sys.argv[1]),
                   float(sys.argv[2]) / 1e3 if len(sys.argv) > 2 else 0.02))
