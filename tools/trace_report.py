#!/usr/bin/env python
"""trace_report: inspect + validate steptrace Chrome trace-event JSON.

    python tools/trace_report.py trace.json            # per-phase table,
                                                       # predicted-vs-measured
                                                       # deltas, top-k spans
    python tools/trace_report.py --validate trace.json # schema gate: exit 1
                                                       # on malformed events,
                                                       # negative durations,
                                                       # unclosed request span
                                                       # trees, or engine-step
                                                       # phase coverage drift
    python tools/trace_report.py --top 20 trace.json
    python tools/trace_report.py --steps 0 trace.json  # every serving step
                                                       # with what it held

Reads traces written by ``engine.trace_export(path)`` /
``ServingEngine.trace_export(path)`` / ``bench_serve --trace out.json``
(deepspeed_tpu/profiling/steptrace.py; docs/observability.md). Pure
stdlib on purpose — the report runs on any machine the JSON lands on,
no jax required.

The ``--validate`` contract (the CI gate in ci.yml):

- every event carries ``ph``/``name`` and a numeric ``ts``; complete
  ("X") events carry a numeric non-negative ``dur``;
- async request events balance: every "b" has a matching "e" per
  (category, id, name) with no end-before-begin;
- every request span tree is CLOSED: a ``serve.request`` id must open
  with QUEUED and terminate in a DONE or EVICTED instant;
- per engine step (``serve/step`` / ``train/step``) and per fleet
  router tick (``fleet/tick`` — the aggregated fleet trace from
  ``Router.trace_export`` / ``bench_serve --replicas N --trace``), the
  sum of its phase spans' self-times must land within
  ``--coverage-tol`` (default 10%) of the step's measured wall clock —
  phases that silently stop covering the step are how attribution rots.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Any, Dict, List

STEP_NAMES = ("serve/step", "train/step", "fleet/tick")
REQUEST_CAT = "serve.request"
TERMINALS = ("DONE", "EVICTED")
# absolute slack on the per-step coverage check: host scheduling jitter
# on a microsecond-scale step must not fail a percentage gate
COVERAGE_ABS_US = 300.0


def load_events(path: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):
        events = data.get("traceEvents")
    else:
        events = data
    if not isinstance(events, list):
        raise ValueError("no traceEvents list found")
    return events


def _x_events(events):
    return [e for e in events if e.get("ph") == "X"]


# ------------------------------------------------------------- validation
def validate(events: List[Dict[str, Any]],
             coverage_tol: float = 0.10) -> List[str]:
    problems: List[str] = []
    for i, e in enumerate(events):
        if not isinstance(e, dict) or "ph" not in e:
            problems.append(f"event #{i}: not a trace event (no ph)")
            continue
        if e.get("ph") != "M" and not isinstance(e.get("name"), str):
            problems.append(f"event #{i}: missing name")
        if not isinstance(e.get("ts"), (int, float)):
            problems.append(f"event #{i} ({e.get('name')}): non-numeric ts")
        if e.get("ph") == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)):
                problems.append(
                    f"event #{i} ({e.get('name')}): X event without dur"
                )
            elif dur < 0:
                problems.append(
                    f"event #{i} ({e.get('name')}): negative duration {dur}"
                )
    if problems:
        return problems  # structural breakage; the walks below need shape

    # async begin/end balance, in timestamp order per (cat, id, name)
    opens: Dict[tuple, int] = defaultdict(int)
    per_request: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    for e in sorted(events, key=lambda e: e["ts"]):
        ph = e["ph"]
        if ph not in ("b", "e", "i"):
            continue
        key = (e.get("cat"), e.get("id"), e["name"])
        if ph == "b":
            opens[key] += 1
        elif ph == "e":
            opens[key] -= 1
            if opens[key] < 0:
                problems.append(
                    f"async end before begin: {key[2]!r} id={key[1]!r}"
                )
                opens[key] = 0
        if e.get("cat") == REQUEST_CAT and e.get("id") is not None:
            per_request[str(e["id"])].append(e)
    for (cat, aid, name), n in opens.items():
        if n != 0:
            problems.append(
                f"unclosed async span: {name!r} id={aid!r} ({n} open)"
            )

    # request trees: QUEUED opens the tree, DONE/EVICTED closes it
    for rid, evs in sorted(per_request.items()):
        names = [e["name"] for e in evs]
        if "QUEUED" not in names:
            problems.append(f"request {rid}: no QUEUED span")
        terminal = [e for e in evs
                    if e["ph"] == "i" and e["name"] in TERMINALS]
        if not terminal:
            problems.append(
                f"request {rid}: span tree not closed (no DONE/EVICTED "
                f"instant; saw {sorted(set(names))})"
            )

    # engine-step phase coverage: per step span, the phases inside it
    # (same tid, same namespace, fully contained) must sum to the step's
    # wall clock within tolerance — phase self-times ARE the breakdown
    xs = _x_events(events)
    for step_name in STEP_NAMES:
        ns = step_name.split("/")[0] + "/"
        steps = [e for e in xs if e["name"] == step_name]
        phases = [
            e for e in xs
            if e["name"].startswith(ns) and e["name"] != step_name
        ]
        for s in steps:
            t0, t1 = s["ts"], s["ts"] + s["dur"]
            inside = [
                p for p in phases
                if p.get("tid") == s.get("tid")
                and p["ts"] >= t0 - 1 and p["ts"] + p["dur"] <= t1 + 1
            ]
            if not inside:
                problems.append(
                    f"{step_name} at ts={s['ts']}: no phase spans inside"
                )
                continue
            covered = sum(p["dur"] for p in inside)
            drift = abs(covered - s["dur"])
            if drift > coverage_tol * s["dur"] + COVERAGE_ABS_US:
                problems.append(
                    f"{step_name} at ts={s['ts']}: phase self-times cover "
                    f"{covered:.0f}us of a {s['dur']:.0f}us step "
                    f"(> {coverage_tol:.0%} drift)"
                )
    return problems


# --------------------------------------------------------------- reporting
def _self_times(xs: List[Dict[str, Any]]) -> List[tuple]:
    """(self_us, event) per X event: duration minus directly nested spans
    on the same tid (standard interval-stack walk)."""
    out = []
    by_tid: Dict[Any, List[Dict[str, Any]]] = defaultdict(list)
    for e in xs:
        by_tid[e.get("tid")].append(e)
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[tuple] = []  # (end_ts, [child_dur_acc]) — acc is a list
        accs = {}
        for e in evs:
            while stack and stack[-1][0] <= e["ts"]:
                stack.pop()
            if stack:
                accs[stack[-1][1]][0] += e["dur"]
            key = id(e)
            accs[key] = [0.0]
            stack.append((e["ts"] + e["dur"], key))
        for e in evs:
            out.append((max(e["dur"] - accs[id(e)][0], 0.0), e))
    return out


STEP_ARGS = ("scheduled_tokens", "prompt_rows", "prompt_slots",
             "decode_slots", "context_tokens")


def serve_steps(xs: List[Dict[str, Any]], limit: int = 0) -> List[str]:
    """One line a serving step, by its number: what ``serve/dispatch`` says
    the step held (its real rows, those of them in prompt chunks, the slots
    that fed a chunk or a decode row, the context behind them) beside the
    host's dispatch and its wait for the step's results."""
    rows: Dict[Any, Dict[str, Any]] = defaultdict(dict)
    for e in xs:
        args = e.get("args") or {}
        if e["name"] == "serve/dispatch" and "step" in args:
            rows[args["step"]].update(args, dispatch_ms=e["dur"] / 1e3)
        elif e["name"] == "serve/device" and "step" in args:
            rows[args["step"]].update(args, device_ms=e["dur"] / 1e3)
    if not rows:
        return []
    lines = ["", "serve steps (what each held; dispatch and the wait for it):",
             f"{'step':>6}" + "".join(f"{k:>18}" for k in STEP_ARGS)
             + f"{'dispatch ms':>13}{'device ms':>11}"]
    for n in sorted(rows)[:limit or None]:
        r = rows[n]
        lines.append(
            f"{n:>6}" + "".join(f"{r.get(k, '-'):>18}" for k in STEP_ARGS)
            + "".join(f"{r[k]:>{w}.2f}" if k in r else f"{'-':>{w}}"
                      for k, w in (("dispatch_ms", 13), ("device_ms", 11))))
    if limit and len(rows) > limit:
        lines.append(f"  ... {len(rows) - limit} more (--steps 0: all)")
    return lines


def report(events: List[Dict[str, Any]], topk: int = 10,
           steps: int = 10) -> str:
    xs = _x_events(events)
    if not xs:
        return "trace has no complete (X) spans"
    lines: List[str] = []
    window = max(e["ts"] + e["dur"] for e in xs) - min(e["ts"] for e in xs)
    selfs = _self_times(xs)
    agg: Dict[str, List[float]] = defaultdict(list)
    agg_self: Dict[str, float] = defaultdict(float)
    for self_us, e in selfs:
        agg[e["name"]].append(e["dur"])
        agg_self[e["name"]] += self_us
    lines.append(
        f"{'phase':<30}{'count':>7}{'total ms':>12}{'mean ms':>10}"
        f"{'self ms':>11}{'% window':>10}"
    )
    for name, durs in sorted(agg.items(), key=lambda kv: -sum(kv[1])):
        total = sum(durs)
        lines.append(
            f"{name:<30}{len(durs):>7}{total / 1e3:>12.2f}"
            f"{total / len(durs) / 1e3:>10.2f}{agg_self[name] / 1e3:>11.2f}"
            f"{100.0 * total / window if window > 0 else 0.0:>10.1f}"
        )

    plan = [e for e in xs if e.get("cat") == "plan"]
    if plan:
        lines.append("")
        lines.append("predicted vs measured (plan/* spans, shardplan "
                     "annotations):")
        lines.append(
            f"{'stream':<24}{'pred bytes/step':>17}{'pred s/step':>13}"
            f"{'meas step s':>13}{'pred/meas':>11}"
        )
        for e in plan:
            a = e.get("args", {})
            ratio = a.get("predicted_over_measured")
            lines.append(
                f"{e['name']:<24}"
                f"{a.get('predicted_bytes_per_step', 0):>17,}"
                f"{a.get('predicted_s_per_step', 0.0):>13.6f}"
                f"{a.get('measured_step_s', 0.0):>13.6f}"
                f"{ratio if ratio is not None else float('nan'):>11.4f}"
            )

    lines.extend(serve_steps(xs, steps))
    lines.append("")
    lines.append(f"top {topk} spans by self time:")
    for self_us, e in sorted(selfs, key=lambda t: -t[0])[:topk]:
        lines.append(
            f"  {e['name']:<30}{self_us / 1e3:>10.2f} ms "
            f"(at {e['ts'] / 1e3:.2f} ms)"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="trace_report", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("trace", help="Chrome trace-event JSON path")
    ap.add_argument("--validate", action="store_true",
                    help="schema gate: exit 1 on any violation")
    ap.add_argument("--coverage-tol", type=float, default=0.10,
                    help="per-step phase coverage tolerance (default 0.10)")
    ap.add_argument("--top", type=int, default=10,
                    help="top-k spans by self time in the report")
    ap.add_argument("--steps", type=int, default=10,
                    help="serving steps listed with what each held "
                         "(0: all of them)")
    args = ap.parse_args(argv)

    try:
        events = load_events(args.trace)
    except (OSError, ValueError) as e:
        print(f"trace_report: cannot load {args.trace}: {e}",
              file=sys.stderr)
        return 1

    if args.validate:
        problems = validate(events, coverage_tol=args.coverage_tol)
        if problems:
            print(f"trace_report: {len(problems)} violation(s) in "
                  f"{args.trace}:")
            for p in problems:
                print(f"  - {p}")
            return 1
        n_req = len({
            e.get("id") for e in events
            if e.get("cat") == REQUEST_CAT and e.get("id") is not None
        })
        print(
            f"trace_report: {args.trace} OK — "
            f"{sum(1 for e in events if e.get('ph') == 'X')} spans, "
            f"{n_req} closed request tree(s)"
        )
        return 0

    print(report(events, topk=args.top, steps=args.steps))
    return 0


if __name__ == "__main__":
    sys.exit(main())
