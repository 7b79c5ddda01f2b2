"""What the readers of a model with layer kinds take from a traced run
beside the shared reduction (``trace_reduce.Reduced``): the program's own
annotation of each device step, and the device time of the operations that
take a given operand.

The serving engine wraps the call of its one step in the profiler
annotation ``serve/device_step`` and gives it the step's attention work as
arguments (``attended_<kind>``, ``fetched_<kind>``, ``rows``; see
``ServingEngine._count_keys``), so a trace carries the counts of exactly the
steps it timed. A program without the annotation (or without a kind) yields
nothing, and a reader then reports nothing.
"""

from __future__ import annotations

import os
import re
from collections import defaultdict
from typing import Dict, Optional

from benchmarks import trace_reduce

STEP_EVENT = "serve/device_step"


def full_trace(ctx) -> Optional[trace_reduce.Trace]:
    """The traced run's profile with every event's arguments, read once a
    run (the shared reduction drops them)."""
    if ctx.reduced is None:
        return None
    if getattr(ctx, "full_trace", None) is None:
        ctx.full_trace = trace_reduce.load(
            os.path.join(ctx.root, ".bench_out", "trace"), keep_stats=True)
    return ctx.full_trace


def step_counts(ctx) -> Optional[Dict[str, float]]:
    """The arguments of the traced steps' annotations, summed, with
    ``steps`` the number of annotated steps; None if there was none."""
    trace = full_trace(ctx)
    if trace is None:
        return None
    out: Dict[str, float] = defaultdict(float)
    for line in trace.get(trace_reduce.HOST_PLANE, {}).values():
        for ev in line:
            if ev.name == STEP_EVENT:
                out["steps"] += 1
                for k, v in ev.stats.items():
                    if isinstance(v, (int, float)):
                        out[k] += v
    return dict(out) if out else None


def operand_seconds(ctx, pattern: str) -> Optional[float]:
    """Self seconds per device of the operations whose instruction (a
    device event is named by its whole instruction, operands and their
    shapes included, though not by the scope it was traced under: my chip
    run, PR 30) matches the regular expression ``pattern``; None if none
    does."""
    trace = full_trace(ctx)
    if trace is None:
        return None
    rx = re.compile(pattern)
    ns, devices = 0.0, 0
    hit: Dict[str, bool] = {}
    for plane, lines in trace.items():
        if not trace_reduce.DEVICE_PLANE.match(plane) or (
                trace_reduce.OPS_LINE not in lines):
            continue
        devices += 1
        for ev, a, b in trace_reduce.self_segments(lines[trace_reduce.OPS_LINE]):
            if ev.name not in hit:
                hit[ev.name] = bool(rx.search(ev.name))
            if hit[ev.name]:
                ns += b - a
    return ns / devices / 1e9 if ns else None


def traced_steps(ctx) -> int:
    r = ctx.reduced
    return len(r.spans.get("bench/engine.step", [])) if r else 0
