"""healthwatch: always-on goodput accounting, anomaly watchdogs, and
flight-recorder postmortems across train + serve.

PR 8's steptrace answers "where did this step's time go" and the PR 7
drift ledger answers "is the cost model honest"; this layer answers the
production questions on top of both: *what fraction of wall-clock was
useful work, is this run healthy right now, and what happened in the
last K steps before it died?* Four pieces, all riding the ONE steptrace
``MetricsRegistry`` (healthwatch enabled implies tracing enabled — the
goodput buckets are classified straight off the engine's own spans):

- **Goodput accounting** (:class:`HealthWatch` + ``SPAN_BUCKET``):
  every wall-clock second since the watch started is classified into
  ``compute`` / ``compile`` / ``stall_on_data`` / ``checkpoint`` /
  ``comm_exposed`` / ``idle``. Buckets come from existing span names
  (``train/device`` → compute, ``train/offload_swap_*`` →
  comm_exposed), the new instrumentation (``train/input_wait`` around
  the data-iterator pull, ``train/checkpoint`` around save_checkpoint,
  dispatch spans annotated ``traced=n`` when a retrace happened →
  compile), and the engine's declared ``analytic_streams()``: the
  statically-priced seconds of *unoverlapped* ici/offload streams are
  carved out of each device span as ``comm_exposed`` (same pricing as
  rule R8 / the plan/* trace spans). ``idle`` is whatever no span
  claimed. The running ``goodput_fraction`` (compute / elapsed) is
  reported in bench tables, ``ServingMetrics.snapshot()``, and as the
  ``health/goodput`` sample through the one monitor bridge.

- **Anomaly watchdogs**: a small rule engine evaluated host-side once
  per step with cheap device-scalar taps (every host read goes through
  :func:`_tap`, which counts into :data:`DEVICE_TAPS` so tests can
  prove the disabled path does ZERO extra transfers). Rules:
  ``nonfinite_loss`` / ``nonfinite_grad``, ``loss_spike`` (EWMA
  z-score), ``grad_explosion`` (EWMA factor), ``step_time_regression``
  (trailing-window median factor), ``plan_drift`` (live drift alarm —
  the shardplan ``est_step_s`` prediction vs the measured trailing
  median, judged by :func:`analysis.cost.drift.check_pair`, the SAME
  band definition the offline ledger uses), ``recompile``
  (trace-counter deltas after warmup), and the serving-side
  ``queue_depth_breach`` / ``ttft_breach``. Each firing emits a
  structured ``health/<rule>`` registry instant + sample and takes the
  rule's configured action: ``log`` | ``dump`` (write a postmortem) |
  ``raise`` (:class:`HealthwatchAnomaly`, after dumping).

- **Flight recorder**: a bounded ring (``ring_steps``) of per-step
  records — spans, tapped metrics, watchdog evaluations — that dumps a
  self-contained postmortem JSON (:data:`POSTMORTEM_SCHEMA`) on a
  watchdog ``dump``/``raise``, SIGTERM, uncaught crash (chained
  ``sys.excepthook``), or explicit ``engine.dump_postmortem(path)``.
  ``tools/healthwatch.py`` renders it (and ``--validate`` gates the
  schema, like ``trace_report``).

- **Exporter** (:class:`MetricsExporter`): a pull-free Prometheus-
  textfile (``*.prom``) or JSON-lines metrics file flushed on an
  interval from the one registry — latest sample per tag across the
  ``train/* serve/* comm/* plan/* health/*`` namespaces, so one scrape
  answers "is it healthy".

Zero overhead when disabled:
engines keep ``healthwatch = None``, no ring deque is allocated, no
registry comes into being for its sake, no device scalar is read
(``DEVICE_TAPS`` stays put), and the compiled step program is
untouched — the loss trajectory is bitwise identical to an engine with
no healthwatch section at all
(tests/test_healthwatch.py). Config gate::

    {"healthwatch": {"enabled": true, "ring_steps": 64,
                     "rules": {"queue_depth_breach": {"threshold": 32,
                                                      "action": "dump"}},
                     "export_path": "health.prom",
                     "export_interval_s": 10.0}}

See docs/observability.md ("healthwatch") for bucket definitions, the
rule schema, and the postmortem format.
"""

from __future__ import annotations

import json
import math
import os
import signal
import sys
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils.logging import log_dist

__all__ = [
    "HealthWatch", "HealthwatchAnomaly", "MetricsExporter",
    "BUCKETS", "DEFAULT_RULES", "POSTMORTEM_SCHEMA", "DEVICE_TAPS",
    "device_taps", "reset",
]

POSTMORTEM_SCHEMA = "healthwatch.postmortem.v1"

#: goodput bucket names, in reporting order; ``idle`` is derived
#: (elapsed minus everything claimed), never charged directly.
BUCKETS = ("compute", "compile", "stall_on_data", "checkpoint",
           "comm_exposed", "idle")

#: span name → goodput bucket. Dispatch spans are handled separately
#: (``traced > 0`` → compile; plain dispatch host time stays idle — it
#: is overhead, not useful work). Device spans are split against the
#: analytic comm-exposed estimate in :meth:`HealthWatch._classify`.
SPAN_BUCKET = {
    "train/device": "compute",
    "serve/device": "compute",
    "train/input_wait": "stall_on_data",
    "train/checkpoint": "checkpoint",
    "train/offload_swap_in": "comm_exposed",
    "train/offload_swap_out": "comm_exposed",
}

_DISPATCH_SPANS = ("train/dispatch", "serve/dispatch",
                   "train/fwd_bwd_dispatch", "train/optimizer_dispatch")

#: module-level count of host←device scalar reads healthwatch performed
#: (one per tapped metric per step). The zero-overhead tests assert it
#: does not move while healthwatch is disabled.
DEVICE_TAPS = 0

_MAX_EVENTS = 256

SEVERITIES = ("info", "warn", "critical")
ACTIONS = ("log", "dump", "raise")

#: the default ruleset; config ``rules`` entries merge over these per
#: rule (unknown keys within a rule are kept — forward-compatible).
#: ``threshold``/``p95_s`` of None leaves a rule armed but inert until
#: the operator supplies a limit.
DEFAULT_RULES: Dict[str, Dict[str, Any]] = {
    "nonfinite_loss": {
        "enabled": True, "severity": "critical", "action": "dump",
    },
    "nonfinite_grad": {
        "enabled": True, "severity": "critical", "action": "dump",
    },
    "loss_spike": {
        "enabled": True, "severity": "warn", "action": "log",
        "zscore": 6.0, "min_samples": 20, "alpha": 0.1,
    },
    "grad_explosion": {
        "enabled": True, "severity": "warn", "action": "log",
        "factor": 10.0, "min_samples": 20, "alpha": 0.1,
    },
    "step_time_regression": {
        "enabled": True, "severity": "warn", "action": "log",
        "factor": 2.0, "min_samples": 8,
    },
    "plan_drift": {
        "enabled": True, "severity": "warn", "action": "log",
        "min_samples": 4, "window": 8,
    },
    "recompile": {
        "enabled": True, "severity": "warn", "action": "log",
        "warmup_steps": 1,
    },
    "queue_depth_breach": {
        "enabled": True, "severity": "warn", "action": "log",
        "threshold": None,
    },
    "ttft_breach": {
        "enabled": True, "severity": "warn", "action": "log",
        "p95_s": None, "window": 32,
    },
    "zero_progress": {
        "enabled": True, "severity": "critical", "action": "log",
        "window": 16,
    },
    # a sync save / snapshot fence exceeding its R8-priced budget by
    # ``factor`` fires: the save is stealing step time the async pipeline
    # (or a faster host path) should hide. ``budget_s`` of None defers to
    # the engine-armed estimate (set_ckpt_budget: snapshot bytes / host_bw)
    "checkpoint_stall": {
        "enabled": True, "severity": "warn", "action": "log",
        "budget_s": None, "factor": 4.0,
    },
}


class HealthwatchAnomaly(RuntimeError):
    """Raised by a watchdog whose action is ``raise`` (after the
    postmortem dumped — evidence first, then the crash)."""


def _tap(x) -> float:
    """ONE host read of a device scalar, counted. Every watchdog input
    crosses here so the zero-overhead test can count transfers."""
    global DEVICE_TAPS
    DEVICE_TAPS += 1
    try:
        import jax

        if isinstance(x, jax.Array):
            x = jax.device_get(x)
    except Exception:  # noqa: BLE001 — jax-less callers pass floats
        pass
    return float(x)


def device_taps() -> int:
    return DEVICE_TAPS


def _finite(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


class _Ewma:
    """Exponentially-weighted mean/variance with a relative std floor
    (a perfectly flat series must not turn any wiggle into z=inf)."""

    __slots__ = ("alpha", "n", "mean", "var")

    def __init__(self, alpha: float = 0.1):
        self.alpha = float(alpha)
        self.n = 0
        self.mean = 0.0
        self.var = 0.0

    def zscore(self, x: float) -> float:
        """z of ``x`` against the state BEFORE updating with it."""
        if self.n == 0:
            return 0.0
        std = math.sqrt(max(self.var, 0.0))
        std = max(std, 0.01 * abs(self.mean), 1e-9)
        return (x - self.mean) / std

    def update(self, x: float) -> None:
        if self.n == 0:
            self.mean = x
            self.var = 0.0
        else:
            d = x - self.mean
            self.mean += self.alpha * d
            self.var = (1.0 - self.alpha) * (self.var + self.alpha * d * d)
        self.n += 1

    def state(self) -> Dict[str, float]:
        return {"n": self.n, "mean": round(self.mean, 6),
                "var": round(self.var, 9)}


def _median(xs) -> Optional[float]:
    xs = sorted(xs)
    if not xs:
        return None
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def _cfg_get(section, key, default):
    if section is None:
        return default
    if isinstance(section, dict):
        return section.get(key, default)
    return getattr(section, key, default)


# ------------------------------------------------------------- exporter
class MetricsExporter:
    """Pull-free metrics file flushed on an interval from the registry:
    latest sample per tag across every namespace, plus whatever extra
    gauges the caller folds in (goodput buckets, watchdog counters).

    ``*.prom`` paths write Prometheus textfile format (rewritten
    atomically each flush — the node-exporter textfile-collector
    contract); anything else appends one JSON object per flush
    (JSON-lines). No threads: :meth:`maybe_flush` is called from the
    step hooks, so flushing is deterministic and test-friendly."""

    def __init__(self, path: str, interval_s: float = 10.0,
                 clock=time.perf_counter):
        self.path = path
        self.interval_s = float(interval_s)
        self.clock = clock
        self.prom = path.endswith(".prom")
        self.flushes = 0
        self._latest: Dict[str, float] = {}
        self._steps: Dict[str, int] = {}
        self._cursor = 0
        self._last_flush: Optional[float] = None

    def collect(self, registry, extra: Optional[Dict[str, float]] = None
                ) -> None:
        if registry is not None:
            # one critical section for read + reclaim: a sample appended
            # between a separate read and reclaim would be deleted
            # uncollected
            with registry._lock:
                new = list(registry.samples[self._cursor:])
                if len(registry.samples) >= registry.max_spans:
                    # reclaim the saturated bounded buffer (everything
                    # drained is folded into _latest below) so an
                    # always-on export never freezes at the cap
                    del registry.samples[:]
                    self._cursor = 0
                else:
                    self._cursor = len(registry.samples)
            for tag, value, step, _t in new:
                self._latest[tag] = value
                if step is not None:
                    self._steps[tag] = step
        for tag, value in (extra or {}).items():
            self._latest[tag] = float(value)

    @staticmethod
    def _prom_name(tag: str) -> str:
        out = "".join(c if c.isalnum() or c == "_" else "_" for c in tag)
        return f"dstpu_{out}"

    def flush(self, registry=None, extra=None) -> str:
        """Collect + write now (best-effort: telemetry must never crash
        the run it watches)."""
        self.collect(registry, extra)
        try:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            if self.prom:
                tmp = self.path + ".tmp"
                with open(tmp, "w") as f:
                    for tag in sorted(self._latest):
                        name = self._prom_name(tag)
                        f.write(f"# TYPE {name} gauge\n")
                        f.write(f"{name} {self._latest[tag]:.9g}\n")
                os.replace(tmp, self.path)
            else:
                with open(self.path, "a") as f:
                    f.write(json.dumps({
                        "ts": round(time.time(), 3),
                        "metrics": {k: round(v, 9)
                                    for k, v in sorted(self._latest.items())},
                        "steps": dict(sorted(self._steps.items())),
                    }) + "\n")
            self.flushes += 1
        except OSError as e:
            log_dist(f"healthwatch: exporter write failed ({self.path}): "
                     f"{e} — flush dropped, run continues")
        self._last_flush = self.clock()
        return self.path

    def maybe_flush(self, registry=None, extra=None, force=False) -> bool:
        now = self.clock()
        if (not force and self._last_flush is not None
                and now - self._last_flush < self.interval_s):
            return False
        self.flush(registry, extra)
        return True


# ---------------------------------------------------------- healthwatch
class HealthWatch:
    """The per-engine health layer (see module docstring). Constructed
    only when the config gate is on — ``engine.healthwatch is None`` IS
    the disabled path, exactly like ``engine.tracer``."""

    def __init__(self, config=None, registry=None, *, source: str = "train",
                 context: Optional[Dict[str, Any]] = None, clock=None):
        self.source = source
        self.registry = registry
        self.clock = (
            clock if clock is not None
            else (registry.clock if registry is not None
                  else time.perf_counter)
        )
        self.ring_steps = int(_cfg_get(config, "ring_steps", 64))
        self.ring: deque = deque(maxlen=self.ring_steps)
        self.rotations = 0  # registry-saturation reclaims (_drain_spans)
        self.rules = self._merge_rules(_cfg_get(config, "rules", None))
        self.events: List[Dict[str, Any]] = []
        self.counters: Dict[str, int] = {}
        self.dump_count = 0
        self.last_postmortem: Optional[str] = None
        self.postmortem_path = (
            _cfg_get(config, "postmortem_path", None)
            or f"healthwatch_postmortem_{source}.json"
        )
        self.context = dict(context or {})
        self.buckets: Dict[str, float] = {
            b: 0.0 for b in BUCKETS if b != "idle"
        }
        self._t_origin = self.clock()
        self._step_t0: Optional[float] = None
        self._span_cursor = (
            len(registry.spans) if registry is not None else 0
        )
        self._loss_ewma = _Ewma(float(self.rules["loss_spike"]["alpha"]))
        self._gnorm_ewma = _Ewma(
            float(self.rules["grad_explosion"]["alpha"])
        )
        self._step_times: deque = deque(maxlen=64)
        self._prediction: Optional[Dict[str, Any]] = None
        self._comm_est_s = 0.0
        # checkpoint accounting: the engine arms the fence budget from the
        # ckpt_snapshot stream's static price; the background writer adds
        # its wall seconds here OUT-OF-BAND (they overlap training, so
        # they must never land in a goodput bucket)
        self._ckpt_budget_s: Optional[float] = None
        self.ckpt_write_s = 0.0
        self._ckpt_write_lock = threading.Lock()
        self._prev_fired: set = set()
        # zero_progress watchdog: token counter at the last serve tick
        # and the current length of the no-progress streak
        self._zp_last_tokens: Optional[int] = None
        self._zp_streak = 0
        self.exporter: Optional[MetricsExporter] = None
        export_path = _cfg_get(config, "export_path", None)
        if export_path:
            self.exporter = MetricsExporter(
                export_path,
                interval_s=float(_cfg_get(config, "export_interval_s", 10.0)),
                clock=self.clock,
            )
        _register(self)
        if bool(_cfg_get(config, "install_signal_handler", True)):
            _install_handlers()

    # ------------------------------------------------------------ rules
    @staticmethod
    def _merge_rules(overrides) -> Dict[str, Dict[str, Any]]:
        rules = {k: dict(v) for k, v in DEFAULT_RULES.items()}
        for name, params in dict(overrides or {}).items():
            if name not in rules:
                raise ValueError(
                    f"healthwatch.rules: unknown rule {name!r} "
                    f"(known: {sorted(rules)})"
                )
            if isinstance(params, bool):
                params = {"enabled": params}
            rules[name].update(dict(params or {}))
        return rules

    # -------------------------------------------------------- prediction
    def set_prediction(self, est_step_s: float, gen: str) -> None:
        """Arm the live drift alarm: the shardplan roofline prediction
        the ``plan_drift`` rule judges the measured trailing median
        against (drift.check_pair — the ledger's band definition)."""
        self._prediction = {"est_step_s": float(est_step_s),
                           "gen": str(gen)}

    def set_comm_estimate_from_streams(self, streams: Dict[str, Any],
                                       hardware=None) -> None:
        """Statically-priced seconds/step of *unoverlapped* ici/offload
        streams (same pricing as rule R8 / the ``plan/*`` spans) —
        carved out of each device span as the ``comm_exposed`` bucket.
        Best-effort: goodput must not die on its accounting line."""
        try:
            from .steptrace import stream_span_args

            total = 0.0
            for stream in (streams or {}).values():
                if stream.get("kind") not in ("ici", "offload"):
                    continue
                if stream.get("overlapped"):
                    continue
                if stream.get("goodput_bucket") == "checkpoint":
                    # sync-save seconds are already charged to the
                    # `checkpoint` bucket by the train/checkpoint span —
                    # carving them from compute would double-count
                    continue
                total += stream_span_args(stream, hardware=hardware)[
                    "predicted_s_per_step"
                ]
            self._comm_est_s = total
        except Exception as e:  # noqa: BLE001
            log_dist(f"healthwatch: comm estimate skipped: {e}")
            self._comm_est_s = 0.0

    def set_ckpt_budget(self, budget_s: float) -> None:
        """Arm the ``checkpoint_stall`` watchdog with the statically
        priced snapshot-fence seconds (ckpt_snapshot stream bytes /
        host_bw). An operator-supplied ``budget_s`` in the rule config
        wins over this estimate."""
        if budget_s and budget_s > 0:
            self._ckpt_budget_s = float(budget_s)

    def add_ckpt_write_s(self, seconds: float) -> None:
        """Background writer seconds — reported via goodput() /
        ``health/ckpt_write_s`` but charged to NO bucket (the write
        overlapped training; only the fence is goodput-visible).
        Called from the writer thread, hence the lock."""
        with self._ckpt_write_lock:
            self.ckpt_write_s += float(seconds)

    # ---------------------------------------------------------- goodput
    def _drain_spans(self) -> List[Dict[str, Any]]:
        reg = self.registry
        if reg is None:
            return []
        with reg._lock:
            spans = reg.spans[self._span_cursor:]
            self._span_cursor = len(reg.spans)
            if len(reg.spans) >= reg.max_spans:
                # the bounded registry saturated: without reclamation an
                # always-on run stops seeing NEW spans after ~max_spans/
                # spans-per-step steps — goodput would decay toward 0 and
                # the export would freeze at stale values. The watch has
                # already copied what it needs (ring + buckets) and a
                # saturated trace is past exportable use, so drop the
                # buffer and let spans flow again. (A second HealthWatch
                # sharing this registry loses the spans between its
                # cursor and the rotation point — one watch per process
                # is the supported shape.)
                del reg.spans[:]
                self._span_cursor = 0
                self.rotations += 1
        return spans

    def _classify(self, spans: List[Dict[str, Any]]) -> None:
        for s in spans:
            dur = max(s["t1"] - s["t0"], 0.0)
            name = s["name"]
            if name in _DISPATCH_SPANS:
                if (s.get("args") or {}).get("traced"):
                    self.buckets["compile"] += dur
                continue  # plain dispatch host time stays idle
            bucket = SPAN_BUCKET.get(name)
            if bucket is None:
                continue
            if bucket == "compute" and self._comm_est_s > 0:
                comm = min(self._comm_est_s, dur)
                self.buckets["comm_exposed"] += comm
                self.buckets["compute"] += dur - comm
            else:
                self.buckets[bucket] += dur

    @property
    def elapsed_s(self) -> float:
        return max(self.clock() - self._t_origin, 0.0)

    def goodput_fraction(self) -> float:
        el = self.elapsed_s
        if el <= 0:
            return 0.0
        # clamped: span clock jitter must not report an impossible >1
        return min(self.buckets["compute"] / el, 1.0)

    def goodput(self) -> Dict[str, Any]:
        el = self.elapsed_s
        accounted = sum(self.buckets.values())
        buckets = {k: round(v, 6) for k, v in self.buckets.items()}
        buckets["idle"] = round(max(el - accounted, 0.0), 6)
        return {
            "elapsed_s": round(el, 6),
            "buckets": buckets,
            "goodput_fraction": round(self.goodput_fraction(), 6),
            # out-of-band: async-save write seconds overlapped training,
            # so they appear beside the buckets, never inside them
            "ckpt_write_s": round(self.ckpt_write_s, 6),
        }

    # ------------------------------------------------------- step hooks
    def on_step_start(self) -> None:
        self._step_t0 = self.clock()

    def _close_step(self) -> float:
        now = self.clock()
        step_s = (now - self._step_t0) if self._step_t0 is not None else 0.0
        self._step_t0 = None
        return step_s

    def _rule(self, name):
        r = self.rules[name]
        return r if r.get("enabled", True) else None

    def _eval(self, evals, name, value, threshold, fired, detail=None):
        entry = {"rule": name, "value": value, "threshold": threshold,
                 "fired": bool(fired)}
        if detail:
            entry["detail"] = detail
        evals.append(entry)
        return entry

    def _make_firer(self, evals, fired):
        """The one firing closure both step hooks share: record the
        evaluation and queue the (severity, action)-stamped event."""

        def fire(name, rule, value, threshold, detail=None):
            ev = self._eval(evals, name, value, threshold, True, detail)
            fired.append({**ev, "severity": rule["severity"],
                          "action": rule["action"]})

        return fire

    @staticmethod
    def _span_dicts(spans):
        return [
            {"name": s["name"],
             "dur_s": round(max(s["t1"] - s["t0"], 0.0), 6),
             **({"args": s["args"]} if s.get("args") else {})}
            for s in spans
        ]

    def _finish_step(self, step, step_s, spans, evals, fired, extra):
        """Shared ring-record tail of both step hooks — ONE place
        defines the flight-recorder record shape, so train and serve
        postmortems can never diverge."""
        rec = {
            "step": int(step),
            "source": self.source,
            "t": round(self.clock() - self._t_origin, 6),
            "step_s": round(step_s, 6),
            **extra,
            "spans": self._span_dicts(spans),
            "watchdog": evals,
        }
        self.ring.append(rec)
        self._step_times.append(step_s)
        self._emit(step, fired, rec)
        return rec

    def on_train_step(self, step: int, loss=None, grad_norm=None,
                      compiled: int = 0) -> Dict[str, Any]:
        """One training step's health tick: drain + classify spans, tap
        the device scalars, evaluate the train ruleset, push the ring
        record, take actions. Called by ``TpuEngine.train_batch`` after
        the step span closed (the device fence already ran, so the taps
        read ready values)."""
        step_s = self._close_step()
        spans = self._drain_spans()
        self._classify(spans)
        lossf = _tap(loss) if loss is not None else None
        gnormf = _tap(grad_norm) if grad_norm is not None else None

        evals: List[Dict[str, Any]] = []
        fired: List[Dict[str, Any]] = []
        fire = self._make_firer(evals, fired)

        r = self._rule("nonfinite_loss")
        if r and lossf is not None:
            if not math.isfinite(lossf):
                fire("nonfinite_loss", r, lossf, None,
                     "loss is not finite")
            else:
                self._eval(evals, "nonfinite_loss", lossf, None, False)
        r = self._rule("nonfinite_grad")
        if r and gnormf is not None:
            if not math.isfinite(gnormf):
                fire("nonfinite_grad", r, gnormf, None,
                     "grad norm is not finite")
            else:
                self._eval(evals, "nonfinite_grad", gnormf, None, False)
        r = self._rule("loss_spike")
        if r and lossf is not None and math.isfinite(lossf):
            z = self._loss_ewma.zscore(lossf)
            armed = self._loss_ewma.n >= int(r["min_samples"])
            if armed and z > float(r["zscore"]):
                fire("loss_spike", r, round(z, 3), float(r["zscore"]),
                     f"loss {lossf:.6g} vs EWMA "
                     f"{self._loss_ewma.mean:.6g}")
            else:
                self._eval(evals, "loss_spike", round(z, 3),
                           float(r["zscore"]), False)
            self._loss_ewma.update(lossf)
        r = self._rule("grad_explosion")
        if r and gnormf is not None and math.isfinite(gnormf):
            mean = self._gnorm_ewma.mean
            armed = self._gnorm_ewma.n >= int(r["min_samples"])
            ratio = gnormf / mean if mean > 0 else 0.0
            if armed and ratio > float(r["factor"]):
                fire("grad_explosion", r, round(ratio, 3),
                     float(r["factor"]),
                     f"grad_norm {gnormf:.6g} vs EWMA {mean:.6g}")
            else:
                self._eval(evals, "grad_explosion", round(ratio, 3),
                           float(r["factor"]), False)
            self._gnorm_ewma.update(gnormf)
        r = self._rule("checkpoint_stall")
        if r:
            ckpt_s = sum(
                max(s["t1"] - s["t0"], 0.0)
                for s in spans
                if s["name"] == "train/checkpoint"
            )
            budget = r.get("budget_s") or self._ckpt_budget_s
            if ckpt_s > 0 and budget:
                limit = float(budget) * float(r.get("factor", 4.0))
                if ckpt_s > limit:
                    fire("checkpoint_stall", r, round(ckpt_s, 6),
                         round(limit, 6),
                         f"checkpoint fence {ckpt_s:.3f}s vs "
                         f"{float(budget):.3f}s priced budget "
                         f"(x{float(r.get('factor', 4.0)):g})")
                else:
                    self._eval(evals, "checkpoint_stall", round(ckpt_s, 6),
                               round(limit, 6), False)
        self._eval_timing_rules(step_s, compiled, step, evals, fire)
        return self._finish_step(step, step_s, spans, evals, fired, {
            "loss": lossf,
            "grad_norm": gnormf,
            "compiled": int(compiled),
        })

    def on_serve_step(self, step: int, metrics=None, compiled: int = 0
                      ) -> Dict[str, Any]:
        """One serving tick's health tick (called by ``ServingEngine``
        after a device step actually ran; idle ticks accrue as idle)."""
        step_s = self._close_step()
        spans = self._drain_spans()
        self._classify(spans)

        evals: List[Dict[str, Any]] = []
        fired: List[Dict[str, Any]] = []
        fire = self._make_firer(evals, fired)

        queue_depth = None
        ttft_p95 = None
        if metrics is not None:
            queue_depth = int(getattr(metrics, "queue_depth", 0))
            r = self._rule("queue_depth_breach")
            if r and r.get("threshold") is not None:
                if queue_depth > int(r["threshold"]):
                    fire("queue_depth_breach", r, queue_depth,
                         int(r["threshold"]),
                         f"{queue_depth} requests queued")
                else:
                    self._eval(evals, "queue_depth_breach", queue_depth,
                               int(r["threshold"]), False)
            r = self._rule("ttft_breach")
            if r and r.get("p95_s") is not None:
                from ..serving.metrics import recent_percentile

                ttft_p95 = recent_percentile(
                    getattr(metrics, "ttft_s", []), 95,
                    window=int(r.get("window", 32)),
                )
                if ttft_p95 is not None and ttft_p95 > float(r["p95_s"]):
                    fire("ttft_breach", r, round(ttft_p95, 6),
                         float(r["p95_s"]))
                elif ttft_p95 is not None:
                    self._eval(evals, "ttft_breach", round(ttft_p95, 6),
                               float(r["p95_s"]), False)
            r = self._rule("zero_progress")
            if r:
                # livelock watchdog (the runtime twin of fleetcheck's
                # LIVELOCK oracle, docs/modelcheck.md): occupied slots
                # whose cumulative token counters — emitted AND
                # scheduled, so a long prefill is progress — freeze for
                # a whole window of consecutive serve ticks
                tokens = (int(getattr(metrics, "tokens_out", 0))
                          + int(getattr(metrics, "scheduled_tokens", 0)))
                occupancy = float(
                    getattr(metrics, "slot_occupancy", 0.0)
                )
                stalled = (self._zp_last_tokens is not None
                           and tokens == self._zp_last_tokens
                           and occupancy > 0.0)
                self._zp_last_tokens = tokens
                self._zp_streak = self._zp_streak + 1 if stalled else 0
                window = int(r.get("window", 16))
                if self._zp_streak >= window:
                    fire("zero_progress", r, self._zp_streak, window,
                         f"{self._zp_streak} consecutive serve ticks "
                         f"with occupied slots and zero token progress "
                         f"(scheduler livelock suspect)")
                    self._zp_streak = 0  # re-arm: fire once per window
                else:
                    self._eval(evals, "zero_progress", self._zp_streak,
                               window, False)
        self._eval_timing_rules(step_s, compiled, step, evals, fire)
        return self._finish_step(step, step_s, spans, evals, fired, {
            "queue_depth": queue_depth,
            "ttft_p95_recent_s": (
                round(ttft_p95, 6) if ttft_p95 is not None else None
            ),
            "compiled": int(compiled),
        })

    def _eval_timing_rules(self, step_s, compiled, step, evals, fire):
        r = self._rule("recompile")
        if r:
            if compiled > 0 and step > int(r["warmup_steps"]):
                fire("recompile", r, int(compiled), 0,
                     f"{compiled} retrace(s) past warmup")
            else:
                self._eval(evals, "recompile", int(compiled), 0, False)
        r = self._rule("step_time_regression")
        if r and len(self._step_times) >= int(r["min_samples"]):
            med = _median(self._step_times)
            if med and med > 0:
                ratio = step_s / med
                if ratio > float(r["factor"]):
                    fire("step_time_regression", r, round(ratio, 3),
                         float(r["factor"]),
                         f"step {step_s:.6g}s vs trailing median "
                         f"{med:.6g}s")
                else:
                    self._eval(evals, "step_time_regression",
                               round(ratio, 3), float(r["factor"]), False)
        r = self._rule("plan_drift")
        if (r and self._prediction is not None
                and len(self._step_times) >= int(r["min_samples"])):
            from ..analysis.cost.drift import check_pair

            window = list(self._step_times)[-int(r.get("window", 8)):]
            med = _median(window)
            verdict = check_pair(
                self._prediction["est_step_s"], med,
                self._prediction["gen"],
            )
            if not verdict["ok"]:
                fire("plan_drift", r, verdict["ratio"],
                     list(verdict["band"]),
                     f"predicted {self._prediction['est_step_s']:.6g}s "
                     f"vs measured median {med:.6g}s "
                     f"(gen {self._prediction['gen']})")
            else:
                self._eval(evals, "plan_drift", verdict["ratio"],
                           list(verdict["band"]), False)

    # ---------------------------------------------------------- actions
    def _emit(self, step, fired, rec) -> None:
        reg = self.registry
        if reg is not None:
            reg.sample("health/goodput", self.goodput_fraction(), step)
        do_raise = None
        for ev in fired:
            rule = ev["rule"]
            self.counters[rule] = self.counters.get(rule, 0) + 1
            event = {
                "rule": rule,
                "severity": ev["severity"],
                "action": ev["action"],
                "step": int(step),
                "source": self.source,
                "value": ev["value"],
                "threshold": ev["threshold"],
                "detail": ev.get("detail"),
                "ts": round(time.time(), 3),
            }
            if len(self.events) < _MAX_EVENTS:
                self.events.append(event)
            if reg is not None:
                reg.instant(f"health/{rule}", "health", args={
                    "severity": ev["severity"], "step": int(step),
                    "value": ev["value"], "detail": ev.get("detail"),
                })
                reg.sample(f"health/{rule}",
                           float(self.counters[rule]), step)
            log_dist(
                f"healthwatch[{self.source}] {ev['severity'].upper()} "
                f"{rule} at step {step}: {ev.get('detail') or ev['value']}"
                f" (action={ev['action']})"
            )
            if ev["action"] == "raise" or (
                ev["action"] == "dump" and rule not in self._prev_fired
            ):
                # dump is debounced per rule: a breach that persists for
                # many consecutive steps writes its evidence ONCE per
                # episode, not once per step (the event/counter still
                # records every firing)
                self.dump_postmortem(reason=f"watchdog:{rule}")
            if ev["action"] == "raise" and do_raise is None:
                do_raise = event
        self._prev_fired = {ev["rule"] for ev in fired}
        if self.exporter is not None:
            self.exporter.maybe_flush(reg, extra=self._export_extra())
        if do_raise is not None:
            raise HealthwatchAnomaly(
                f"healthwatch: {do_raise['rule']} at step "
                f"{do_raise['step']} ({do_raise.get('detail')}); "
                f"postmortem at {self.last_postmortem}"
            )

    def _export_extra(self) -> Dict[str, float]:
        g = self.goodput()
        extra = {"health/goodput": g["goodput_fraction"]}
        for k, v in g["buckets"].items():
            extra[f"health/goodput_{k}_s"] = v
        extra["health/ckpt_write_s"] = g["ckpt_write_s"]
        for rule, n in self.counters.items():
            extra[f"health/{rule}"] = float(n)
        return extra

    # ------------------------------------------------------- postmortem
    def postmortem(self, reason: str = "explicit") -> Dict[str, Any]:
        drift_state: Dict[str, Any] = {"predicted_step_s": None,
                                       "gen": None, "last": None}
        if self._prediction is not None:
            drift_state.update(self._prediction)
            med = _median(list(self._step_times)[-8:])
            if med:
                try:
                    from ..analysis.cost.drift import check_pair

                    drift_state["last"] = check_pair(
                        self._prediction["est_step_s"], med,
                        self._prediction["gen"],
                    )
                except Exception:  # noqa: BLE001
                    pass
        reg = self.registry
        return {
            "schema": POSTMORTEM_SCHEMA,
            "created_ts": round(time.time(), 3),
            "reason": reason,
            "source": self.source,
            "elapsed_s": round(self.elapsed_s, 6),
            "config": self.context.get("config"),
            "plan": self.context.get("plan"),
            "goodput": self.goodput(),
            "drift": drift_state,
            "anomalies": list(self.events),
            "counters": dict(self.counters),
            "steps": list(self.ring),
            "watchdog_state": {
                "loss_ewma": self._loss_ewma.state(),
                "grad_norm_ewma": self._gnorm_ewma.state(),
                "step_time_median_s": _median(self._step_times),
            },
            "registry": (
                {"n_spans": len(reg.spans), "dropped": reg.dropped,
                 "rotations": self.rotations}
                if reg is not None else None
            ),
        }

    def dump_postmortem(self, path: Optional[str] = None,
                        reason: str = "explicit") -> Optional[str]:
        """Write the self-contained postmortem JSON (best-effort: the
        flight recorder must never crash the process it is recording —
        except through a rule whose action is ``raise``)."""
        path = path or self.postmortem_path
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(path, "w") as f:
                json.dump(self.postmortem(reason), f, default=repr)
            self.dump_count += 1
            self.last_postmortem = path
            log_dist(
                f"healthwatch[{self.source}]: postmortem ({reason}) -> "
                f"{path} (render/validate with tools/healthwatch.py)"
            )
            return path
        except OSError as e:
            log_dist(f"healthwatch: postmortem unwritable ({path}): {e}")
            return None

    def close(self) -> None:
        """Final exporter flush + unregister (engine.destroy path)."""
        if self.exporter is not None:
            self.exporter.maybe_flush(self.registry,
                                      extra=self._export_extra(),
                                      force=True)
        _INSTANCES.discard(self)


# ----------------------------------------------- process-level handlers
_INSTANCES: "weakref.WeakSet[HealthWatch]" = weakref.WeakSet()
_HANDLERS_INSTALLED = False
_PREV_SIGTERM = None
_PREV_EXCEPTHOOK = None


def _register(hw: HealthWatch) -> None:
    _INSTANCES.add(hw)


def _dump_all(reason: str) -> None:
    for hw in list(_INSTANCES):
        try:
            hw.dump_postmortem(reason=reason)
        except Exception:  # noqa: BLE001 — evidence is best-effort
            pass


def _on_sigterm(signum, frame):
    _dump_all("sigterm")
    prev = _PREV_SIGTERM
    if callable(prev):
        prev(signum, frame)
    elif prev == signal.SIG_IGN:
        # the process deliberately ignored SIGTERM before healthwatch
        # chained in — keep ignoring it (evidence dumped, nothing more)
        return
    else:
        # default disposition: exit with the conventional 128+signum
        raise SystemExit(128 + int(signum))


def _excepthook(tp, value, tb):
    _dump_all(f"crash:{getattr(tp, '__name__', tp)}")
    hook = _PREV_EXCEPTHOOK or sys.__excepthook__
    hook(tp, value, tb)


def _install_handlers() -> None:
    """Chain a SIGTERM handler + sys.excepthook ONCE per process so a
    preemption or an uncaught crash still leaves a postmortem behind.
    Both chain to whatever was installed before; best-effort (signal
    handlers only install from the main thread)."""
    global _HANDLERS_INSTALLED, _PREV_SIGTERM, _PREV_EXCEPTHOOK
    if _HANDLERS_INSTALLED:
        return
    _HANDLERS_INSTALLED = True
    try:
        if threading.current_thread() is threading.main_thread():
            _PREV_SIGTERM = signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):  # non-main thread / exotic platform
        _PREV_SIGTERM = None
    if sys.excepthook is not _excepthook:
        _PREV_EXCEPTHOOK = sys.excepthook
        sys.excepthook = _excepthook


def reset() -> None:
    """Tests: drop live instances, restore chained handlers, zero the
    tap counter."""
    global _HANDLERS_INSTALLED, _PREV_SIGTERM, _PREV_EXCEPTHOOK
    global DEVICE_TAPS
    for hw in list(_INSTANCES):
        _INSTANCES.discard(hw)
    if _HANDLERS_INSTALLED:
        try:
            if (_PREV_SIGTERM is not None
                    and threading.current_thread()
                    is threading.main_thread()):
                signal.signal(signal.SIGTERM, _PREV_SIGTERM)
        except (ValueError, OSError):
            pass
        if sys.excepthook is _excepthook:
            sys.excepthook = _PREV_EXCEPTHOOK or sys.__excepthook__
    _HANDLERS_INSTALLED = False
    _PREV_SIGTERM = None
    _PREV_EXCEPTHOOK = None
    DEVICE_TAPS = 0
