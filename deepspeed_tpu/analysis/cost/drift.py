"""Drift ledger: the predicted-vs-measured pairs that keep shardplan honest.

The planner's whole value is that a Plan's roofline can stand in for a
compile-and-measure probe (autotuning/planner_search.py prunes and ranks
on it). That substitution is only safe while predictions track reality,
so every measured survivor banks a ``(predicted, measured)`` pair here:

- the autotuner appends one entry per compiled top-k survivor;
- ``tools/autoplan.py --campaign`` appends one entry per measured
  lattice survivor, tagged ``campaign``;
- ``tools/autoplan.py --check`` is the CI regression gate: it re-runs
  the search on the reduced 410M leg, banks fresh pairs, and exits 1
  when any pair leaves the documented band.

Systematic drift — the *median* ratio of several same-generation entries
leaving the recalibration band — produces a concrete suggestion for the
``cost/hardware.py`` constant that is actually binding (peak_flops for
compute-bound steps, hbm_bw / ici_bw otherwise). The ledger never edits
the table itself: recalibration is a reviewed change, not a side effect.

Bands (documented in docs/autotuning.md):

- TPU generations: predicted/measured step time within [0.5, 2.0] —
  the roofline ignores launch overhead and imperfect overlap, so a
  factor-2 envelope is the honest claim.
- ``cpu`` generation (the lint/CI host mesh): [1/25, 25] — host speed
  varies wildly across machines; the band exists to catch cost-model
  breakage (flops or bytes off by orders of magnitude), not to grade
  the host envelope.
- Within ONE run, the survivor ratios must agree with each other to a
  factor of ``SPREAD_BAND`` — relative pricing (the thing ranking
  depends on) is machine-independent and held to a tighter standard.
- Peak-HBM predictions vs XLA's ``memory_analysis()``: [0.90, 1.10]
  (the re-tightened ISSUE-4 band).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

BANDS: Dict[str, Tuple[float, float]] = {"cpu": (1 / 25.0, 25.0)}
DEFAULT_BAND: Tuple[float, float] = (0.5, 2.0)
SPREAD_BAND: float = 3.0
PEAK_BAND: Tuple[float, float] = (0.90, 1.10)
# the CI gate's anchor-program band: the ±10% claim is calibrated on the
# full 410M stage-0 leg (tier-1 test); the gate's reduced anchor leaves
# a little room for model-size and jax-version variation while still
# catching real liveness-model breakage
GATE_PEAK_BAND: Tuple[float, float] = (0.85, 1.15)
RECAL_BAND: Tuple[float, float] = (0.8, 1.25)
RECAL_MIN_SAMPLES: int = 3

_BOUND_CONSTANT = {"compute": "peak_flops", "hbm": "hbm_bw", "ici": "ici_bw"}


def band_for(gen: str) -> Tuple[float, float]:
    return BANDS.get(gen, DEFAULT_BAND)


def check_pair(predicted: Optional[float], measured: Optional[float],
               gen: str, *, ratio: Optional[float] = None,
               band: Optional[Tuple[float, float]] = None
               ) -> Dict[str, Any]:
    """ONE (predicted, measured) pair against its generation's band —
    THE definition of "drifted", shared by the offline ledger gate
    (:func:`check`) and the healthwatch live drift alarm
    (profiling/healthwatch.py ``plan_drift``), so the band constants
    exist exactly once.

    Returns ``{"ok", "ratio", "band", "gen"}``; an unmeasurable pair
    (measured <= 0 / None) yields ``ratio None, ok False``. Callers
    holding a precomputed ratio (ledger rows) pass ``ratio=``; ``band=``
    overrides the generation lookup (the gate's --band flag)."""
    if ratio is None and predicted is not None and measured:
        try:
            if float(measured) > 0:
                ratio = float(predicted) / float(measured)
        except (TypeError, ValueError):
            ratio = None
    lo, hi = band if band is not None else band_for(gen)
    ok = isinstance(ratio, (int, float)) and lo <= ratio <= hi
    return {
        "ok": bool(ok),
        "ratio": round(ratio, 6) if isinstance(ratio, (int, float))
        else None,
        "band": (round(lo, 6), round(hi, 6)),
        "gen": gen,
    }


def default_ledger_path() -> str:
    """``SHARDPLAN_DRIFT_LEDGER`` env override, else a stable per-user
    cache location — NOT the cwd: planner-mode autotuning auto-engages
    for library callers, and a library must not scatter perf/ dirs
    wherever the process happens to run. The CI gate passes an
    explicit repo-anchored path."""
    return os.environ.get(
        "SHARDPLAN_DRIFT_LEDGER",
        os.path.join(os.path.expanduser("~"), ".cache", "deepspeed_tpu",
                     "drift.jsonl"),
    )


def binding_term(plan) -> str:
    """Which roofline term set ``est_step_s`` — the constant a
    recalibration would touch."""
    terms = {"compute": plan.compute_s, "hbm": plan.hbm_s,
             "ici": plan.ici_s}
    return max(terms, key=terms.get)


def make_entry(plan, measured_step_s: float, *, source: str,
               extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One ledger row from a Plan and a wall clock. ``ratio`` is
    predicted/measured: < 1 means the machine ran slower than the
    envelope, > 1 means the plan over-charged the step."""
    measured = float(measured_step_s)
    entry: Dict[str, Any] = {
        "ts": round(time.time(), 1),
        "source": source,
        "gen": plan.hardware.gen,
        "predicted_step_s": round(float(plan.est_step_s), 6),
        "measured_step_s": round(measured, 6),
        "ratio": round(float(plan.est_step_s) / measured, 6)
        if measured > 0 else None,
        "bound": binding_term(plan),
        "predicted_peak_gib": round(plan.peak_hbm_bytes / (1 << 30), 3),
    }
    if extra:
        entry.update(extra)
    return entry


class DriftLedger:
    """Append-only JSONL of drift entries (one file, many runs)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_ledger_path()

    def append(self, entry: Dict[str, Any]) -> None:
        """Best-effort append: an unwritable ledger (read-only CI
        checkout, a path component that's a file, missing permissions)
        logs ONE warning and drops the entry — the ledger is evidence,
        and evidence-keeping must never crash a tuner run."""
        try:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(self.path, "a") as f:
                f.write(json.dumps(entry) + "\n")
        except OSError as e:
            from ...utils.logging import logger

            logger.warning(
                f"drift ledger unwritable ({self.path}): {e} — entry "
                "dropped, run continues (set SHARDPLAN_DRIFT_LEDGER to "
                "a writable path to keep banking pairs)"
            )

    def load(self, gen: Optional[str] = None,
             source: Optional[str] = None,
             tag: Optional[str] = None) -> List[Dict[str, Any]]:
        """All parseable rows, newest last; unreadable lines are skipped
        (the ledger is evidence, never a point of failure). ``tag``
        filters on the entry's tag group (``entry_tag``): pass
        ``"campaign"`` for campaign rows, ``"adhoc"`` for everything
        untagged."""
        rows: List[Dict[str, Any]] = []
        try:
            with open(self.path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rows.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            # missing file, unreadable path, path component that's a
            # file — no evidence is just an empty ledger, never a crash
            return []
        if gen is not None:
            rows = [r for r in rows if r.get("gen") == gen]
        if source is not None:
            rows = [r for r in rows if r.get("source") == source]
        if tag is not None:
            rows = [r for r in rows if entry_tag(r) == tag]
        return rows


def entry_tag(entry: Dict[str, Any]) -> str:
    """The entry's band-bookkeeping group: campaign runs tag their rows
    (``"tag": "campaign"``, tools/autoplan.py --campaign), everything
    historical/ad-hoc is the ``"adhoc"`` group. Spread statistics never
    mix groups: a campaign's lattice legs are deliberately heterogeneous
    (different knob settings price differently — that's the point), so
    pooling them with ad-hoc single-config runs would poison the
    relative-pricing medians both gates rely on."""
    return str(entry.get("tag") or "adhoc")


def by_tag(entries: Sequence[Dict[str, Any]]
           ) -> Dict[str, List[Dict[str, Any]]]:
    """Entries grouped by their :func:`entry_tag`, insertion-ordered."""
    groups: Dict[str, List[Dict[str, Any]]] = {}
    for r in entries:
        groups.setdefault(entry_tag(r), []).append(r)
    return groups


def summarize(entries: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    ratios = sorted(
        r["ratio"] for r in entries if isinstance(r.get("ratio"), (int, float))
    )
    if not ratios:
        return {"n": 0}
    mid = len(ratios) // 2
    median = (ratios[mid] if len(ratios) % 2
              else 0.5 * (ratios[mid - 1] + ratios[mid]))
    return {
        "n": len(ratios),
        "median_ratio": round(median, 4),
        "min_ratio": round(ratios[0], 4),
        "max_ratio": round(ratios[-1], 4),
        "spread": round(ratios[-1] / ratios[0], 4) if ratios[0] > 0 else None,
    }


def check(entries: Sequence[Dict[str, Any]],
          band: Optional[Tuple[float, float]] = None,
          spread_band: float = SPREAD_BAND) -> Tuple[bool, List[str]]:
    """The regression gate: every entry's ratio inside its generation's
    band, and the entries' ratios within ``spread_band`` of each other
    (relative pricing is what ranking rides on). Returns (ok, problems).
    Entries carrying ``peak_ratio`` are additionally held to PEAK_BAND."""
    problems: List[str] = []
    for r in entries:
        ratio = r.get("ratio")
        if not isinstance(ratio, (int, float)):
            problems.append(f"{r.get('source', '?')}: unmeasurable entry "
                            f"(ratio={ratio!r})")
            continue
        # the ONE drifted-pair predicate (shared with the healthwatch
        # live alarm)
        verdict = check_pair(None, None, r.get("gen", ""), ratio=ratio,
                             band=band)
        if not verdict["ok"]:
            lo, hi = verdict["band"]
            problems.append(
                f"{r.get('source', '?')}: predicted/measured step ratio "
                f"{ratio:.3f} outside [{lo:.3g}, {hi:.3g}] "
                f"({r.get('bound', '?')}-bound, gen {r.get('gen', '?')})"
            )
        pk = r.get("peak_ratio")
        if isinstance(pk, (int, float)) and not (
            PEAK_BAND[0] <= pk <= PEAK_BAND[1]
        ):
            problems.append(
                f"{r.get('source', '?')}: predicted/measured HBM peak "
                f"ratio {pk:.3f} outside "
                f"[{PEAK_BAND[0]}, {PEAK_BAND[1]}]"
            )
    # spread is judged PER TAG GROUP: campaign rows and ad-hoc rows keep
    # separate band bookkeeping (a campaign's lattice legs are
    # heterogeneous by design; pooling them with single-config runs
    # would manufacture false spread alarms — or mask real ones)
    for tag, rows in by_tag(entries).items():
        s = summarize(rows)
        if s.get("n", 0) >= 2 and s.get("spread") and (
            s["spread"] > spread_band
        ):
            problems.append(
                f"[{tag}] survivor ratios disagree by {s['spread']:.2f}x "
                f"(> {spread_band}x): relative pricing drifted — the "
                "ranking itself is suspect"
            )
    return not problems, problems


def recalibration_suggestion(entries: Sequence[Dict[str, Any]],
                             hardware=None) -> Optional[str]:
    """With enough same-generation samples whose *median* ratio leaves
    RECAL_BAND, name the binding ``cost/hardware.py`` constant and the
    value that would center the ledger (new = old × median ratio: the
    roofline term is constant-inverse, so scaling the constant by the
    ratio maps the median prediction onto the measurement)."""
    by_gen: Dict[str, List[Dict[str, Any]]] = {}
    for r in entries:
        if isinstance(r.get("ratio"), (int, float)):
            by_gen.setdefault(r.get("gen", "?"), []).append(r)
    for gen, rows in by_gen.items():
        if len(rows) < RECAL_MIN_SAMPLES:
            continue
        s = summarize(rows)
        med = s["median_ratio"]
        if RECAL_BAND[0] <= med <= RECAL_BAND[1]:
            continue
        bounds = [r.get("bound", "compute") for r in rows]
        bound = max(set(bounds), key=bounds.count)
        const = _BOUND_CONSTANT.get(bound, "peak_flops")
        old = None
        if hardware is not None and getattr(hardware, "gen", None) == gen:
            old = getattr(hardware, const, None)
        else:
            from .hardware import gen_defaults

            old = gen_defaults(gen).get(const)
        if not old:
            continue
        new = old * med
        return (
            f"systematic drift on gen '{gen}': median predicted/measured "
            f"{med:.2f} over {len(rows)} {bound}-bound samples — suggest "
            f"cost/hardware.py {const} {old:.3g} -> {new:.3g} "
            "(recalibrate, review, commit; the ledger never edits the "
            "table itself)"
        )
    return None
