"""What the traced serve steps held, and how long each took: one record a
step, read off the program's own spans (``span_trace.program_spans``).

Since PR 54 ``serve/dispatch`` and ``serve/device`` say what their step
holds: ``scheduled_tokens`` (its real rows), ``prompt_rows`` (those of them
in prompt chunks; the rest are decode rows), ``prompt_slots`` and
``decode_slots`` (the slots that fed a chunk, or a decode row, a verify
window or a cached prompt's final token) and ``context_tokens`` (the sum
over the working slots of what their attention or state has behind it after
the step). ``serve/device(n)`` is the host blocked on step n's results, so
its END is when the host had them, and a traced step needs that one event:
a fold whose dispatch lies before the trace began counts from its own
arguments. ``dense_rows``, the rows the program computes a step whatever
they carry, is a constant of the engine and comes from any traced
``serve/device_step``.

The time of step n, ``T(n)``, is the end of ``serve/device(n)`` less the end
of ``serve/device(n-1)``: fold to fold. It is the DEVICE's time for n only
where the device went straight from n-1 to n (``queued``): the turn that
dispatched n says ``overlapped=1``, folded n-1, and its ``serve/device(n-1)``
blocked for longer than 0.1 ms, so n was queued while n-1 still ran. A step
that starts from idle, or a ``serial`` engine's, is left out of the TIMES
and stays in the COUNTS. Where no step of a tail qualifies (an open loop
that never queues) the times are all fold-to-fold intervals of consecutive
steps, so a time is defined wherever two consecutive steps were traced.

A closed-loop cell traces the last seconds of a fixed replay, so a side that
is a little faster traces other steps: ``chunk_steps_pct`` and
``first_traced_step`` say which, beside the kernels' per-step numbers of the
same ledger line. A program without the arguments (the parent of PR 54)
yields no record, and a reader then reports nothing.

    python -m benchmarks.step_kinds <trace dir or .xplane.pb>

prints the traced steps' range and, for decode-only steps and for steps
with a prompt chunk apart: how many, the median and the longest ``T``,
tokens a step and context a slot; then the whole tail's median (and mean:
a tail of two kinds of step has two humps, and its rate is tokens a step
over the MEAN) beside the median ``bench/engine.step`` and its tokens a
second.
"""

from __future__ import annotations

import statistics
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from benchmarks import kinds_trace, span_trace, trace_reduce

COUNTS = ("scheduled_tokens", "prompt_rows", "prompt_slots", "decode_slots",
          "context_tokens")
BLOCKED_NS = 0.1e6  # a serve/device longer than this waited for the device


@dataclass
class Step:
    n: int                      # the step's number (dispatches from 1)
    end: float                  # ns: when the host had its results
    scheduled_tokens: int
    prompt_rows: int
    prompt_slots: int
    decode_slots: int
    context_tokens: int
    dense_rows: Optional[int] = None
    ms: Optional[float] = None  # T(n); None where n-1 was not traced
    queued: bool = False        # the device went straight from n-1 to n

    @property
    def slots(self) -> int:
        return self.prompt_slots + self.decode_slots


def steps_of(lines: span_trace.Lines) -> List[Step]:
    """One record a traced fold that says what it held, by step number."""
    out: List[Step] = []
    for events in lines.values():
        dense = next((int(e.stats["dense_rows"]) for e in events
                      if e.name == kinds_trace.STEP_EVENT
                      and "dense_rows" in e.stats), None)
        folds = {int(e.stats["step"]): e for e in events
                 if e.name == "serve/device" and "step" in e.stats}
        # step n -> the turn that dispatched it
        turns = {int(e.stats["dispatched"]): e.stats for e in events
                 if e.name == span_trace.SERVE_STEP
                 and e.stats.get("dispatched")}
        for n, ev in sorted(folds.items()):
            if not all(k in ev.stats for k in COUNTS):
                continue  # a program that does not say what a step holds
            step = Step(n, ev.end, *(int(ev.stats[k]) for k in COUNTS),
                        dense_rows=dense)
            before, turn = folds.get(n - 1), turns.get(n, {})
            if before is not None:
                step.ms = (ev.end - before.end) / 1e6
                step.queued = bool(
                    turn.get("overlapped") and turn.get("folded") == n - 1
                    and before.dur > BLOCKED_NS)
            out.append(step)
    return out


def steps(ctx) -> List[Step]:
    """The traced run's steps, read once a run; empty without a trace or
    without the arguments."""
    if getattr(ctx, "step_kinds", None) is None:
        ctx.step_kinds = steps_of(span_trace.program_spans(ctx))
    return ctx.step_kinds


def times_ms(found: Sequence[Step], kind=lambda s: True) -> List[float]:
    """``T(n)`` of the steps of that kind whose device went straight from
    n-1 to n; where no step of the TAIL did, of every step after a traced
    one."""
    timed = [s for s in found if s.queued] or [
        s for s in found if s.ms is not None]
    return [s.ms for s in timed if kind(s)]


def has_chunk(step: Step) -> bool:
    return step.prompt_rows > 0


# ------------------------------------------------- what the readers report
def step_ms(found: Sequence[Step]) -> Optional[float]:
    ms = times_ms(found)
    return statistics.median(ms) if ms else None


def tokens_per_step(found: Sequence[Step]) -> Optional[float]:
    if not found:
        return None
    return sum(s.scheduled_tokens for s in found) / len(found)


def chunk_steps_pct(found: Sequence[Step]) -> Optional[float]:
    if not found:
        return None
    return 100.0 * sum(map(has_chunk, found)) / len(found)


def computed_rows_real_pct(found: Sequence[Step]) -> Optional[float]:
    computed = sum(s.dense_rows or 0 for s in found)
    if not computed:
        return None
    return 100.0 * sum(s.scheduled_tokens for s in found) / computed


def context_tokens_per_slot(found: Sequence[Step]) -> Optional[float]:
    slots = sum(s.slots for s in found)
    return sum(s.context_tokens for s in found) / slots if slots else None


def first_traced_step(found: Sequence[Step]) -> Optional[int]:
    return min(s.n for s in found) if found else None


# ---------------------------------------------------------------- describe
def by_kind(found: Sequence[Step]) -> Dict[str, Dict[str, float]]:
    """For decode-only steps and for steps with a prompt chunk: ``steps``,
    ``timed`` of them, ``median_ms`` / ``longest_ms`` of those,
    ``tokens_per_step`` and ``context_per_slot``."""
    out = {}
    for name, kind in (("decode-only", lambda s: not has_chunk(s)),
                       ("with a chunk", has_chunk)):
        mine = [s for s in found if kind(s)]
        ms = times_ms(found, kind)
        out[name] = dict(
            steps=len(mine), timed=len(ms),
            median_ms=statistics.median(ms) if ms else None,
            longest_ms=max(ms) if ms else None,
            tokens_per_step=tokens_per_step(mine),
            context_per_slot=context_tokens_per_slot(mine))
    return out


def describe(trace: trace_reduce.Trace) -> str:
    lines = span_trace.spans_of(
        trace, (*span_trace.PREFIXES, "bench/engine.step"))
    found = steps_of(lines)
    if not found:
        return "no traced serve/device says what its step held"

    def f(x, spec):
        return "-" if x is None else format(x, spec)

    ms = times_ms(found)
    out = [f"steps {found[0].n}..{found[-1].n}: {len(found)} traced, "
           f"{sum(s.queued for s in found)} queued behind the step before, "
           f"{len(ms)} timed; dense_rows {found[0].dense_rows}",
           f"  {'kind':<14}{'steps':>7}{'timed':>7}{'median ms':>11}"
           f"{'longest ms':>12}{'tokens/step':>13}{'context/slot':>14}"]
    for name, k in by_kind(found).items():
        out.append(
            f"  {name:<14}{k['steps']:>7}{k['timed']:>7}"
            f"{f(k['median_ms'], '.3f'):>11}{f(k['longest_ms'], '.3f'):>12}"
            f"{f(k['tokens_per_step'], '.2f'):>13}"
            f"{f(k['context_per_slot'], '.1f'):>14}")
    walls = [e.dur / 1e6
             for e in span_trace.named(lines, ["bench/engine.step"])]
    tail_s = (found[-1].end - found[0].end) / 1e9
    rate = (sum(s.scheduled_tokens for s in found[1:]) / tail_s
            if tail_s > 0 else None)
    out.append(
        f"  all: step_ms {f(step_ms(found), '.3f')} (mean "
        f"{f(statistics.fmean(ms) if ms else None, '.3f')}), median "
        f"bench/engine.step {f(statistics.median(walls) if walls else None, '.3f')}"
        f" ms over {len(walls)}; tokens_per_step "
        f"{f(tokens_per_step(found), '.2f')}, the tail's tokens/s "
        f"{f(rate, '.1f')} (folds {found[0].n + 1}..{found[-1].n} over "
        f"{tail_s:.3f} s); chunk_steps_pct {f(chunk_steps_pct(found), '.2f')}, "
        f"computed_rows_real_pct {f(computed_rows_real_pct(found), '.2f')}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(trace_reduce.load(sys.argv[1])))
