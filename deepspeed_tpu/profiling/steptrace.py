"""steptrace: structured span tracing + the process-global metrics registry.

The runtime grew four disjoint telemetry islands — the comms logger
(profiling/comm_logger.py), serving metrics (serving/metrics.py), the
wall-clock timers (utils/timer.py) and the shardplan drift ledger
(analysis/cost/drift.py) — none of which could answer "where did this
step's time go, and does it match what shardplan predicted?". This
module is the substrate they all feed into:

- **Spans** are host-side intervals bracketing *dispatches*; nothing
  traces inside a jitted program. A span site is written ONCE, as a
  :class:`Phase`, and feeds two sinks: always a
  ``jax.profiler.TraceAnnotation`` of the span's name and arguments (so
  whenever a profile is being taken the span lies on the profiler's
  clock, on the host plane beside the device's operations; free while
  none is), and the :class:`MetricsRegistry` on ``time.perf_counter``
  when the engine has configured one. The profiler being on is the one
  switch of the first sink, the ``"steptrace"`` section of the second.
- A span that should be charged with device work fences:
  ``Span.end(fence=out)`` blocks on ``out`` first (the discipline
  utils/timer.py's ``block_on`` uses). A fence changes the run, so the one
  site that blocks where the program otherwise would not,
  ``train/device``, exists when, and only when, the registry is on (it is
  a :class:`Phase` like the others, so it is then on the profile too);
  ``serve/device`` wraps a wait the serving turn makes anyway.
- The **MetricsRegistry** is process-global (one trace per process, the
  way ``jax.profiler`` works): engines call :func:`configure` and share
  it, so a serving replay and the comms logger land on one timeline.
- **Namespaces** are the one coherent scheme every backend sees:
  ``train/*`` (engine step phases + step metrics), ``serve/*`` (serving
  step phases, request lifecycles, serving metrics), ``comm/*``
  (collective / analytic-stream accounting), ``plan/*`` (shardplan
  predictions attached to the trace) and ``health/*`` (healthwatch
  goodput + watchdog events — profiling/healthwatch.py).
  :func:`write_events` is the ONE
  monitor bridge — ServingMetrics.write_to and CommsLogger.write_to
  route through it, so TensorBoard/W&B/CSV files share the namespace.
- **Export** is Chrome trace-event JSON (``registry.export(path)``,
  ``engine.trace_export(path)``, ``bench_serve --trace out.json``) —
  loadable in Perfetto / chrome://tracing; ``tools/trace_report.py``
  prints the per-phase table and validates the schema offline.
- Every declared ``engine.analytic_streams()`` stream appears in the
  trace as a ``plan/<name>`` span carrying the shardplan-predicted
  bytes and seconds next to the measured step wall clock
  (:func:`stream_span_args`), turning the whole-step drift ledger into
  a per-component one: rule R8's "this overlap is real" claim becomes
  inspectable per stream.

What a run pays. With no profile being taken and no ``"steptrace"``
section, a span site costs one inactive ``TraceAnnotation`` (about 1 us:
a serving turn opens five, a training step four), no registry exists
(``get_registry() is None``), nothing is stored and nothing is fenced.
The config gate of the registry is the ``"steptrace"`` section
(config.py): ``{"steptrace": {"enabled": true, "max_spans": 100000,
"export_path": "trace.json"}}``. Registry-only, because they are no
intervals of the host's thread: the request trees of
:class:`ServeTracer` (async events keyed by request id), ``plan/*``
predictions, metric samples.

See docs/observability.md for the span model and the Perfetto
walkthrough.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = [
    "MetricsRegistry", "Span", "Phase", "ServeTracer",
    "configure", "get_registry", "reset",
    "write_events", "stream_span_args",
]


class Span:
    """One open host-side interval; ``end()`` (or ``with``-exit) records
    it into the registry. ``end(fence=x)`` blocks on ``x`` first so the
    device work dispatched inside the span is charged to it."""

    __slots__ = ("_reg", "name", "cat", "args", "tid", "t0", "t1", "_open")

    def __init__(self, reg: "MetricsRegistry", name: str, cat: str,
                 args: Optional[Dict[str, Any]], tid,
                 t0: Optional[float] = None):
        self._reg = reg
        self.name = name
        self.cat = cat
        self.args = args
        self.tid = tid
        self.t0 = reg.clock() if t0 is None else t0
        self.t1 = None
        self._open = True

    def annotate(self, **kw) -> None:
        if self.args is None:
            self.args = {}
        self.args.update(kw)

    def end(self, fence=None) -> None:
        if not self._open:
            return
        if fence is not None:
            import jax

            jax.block_until_ready(fence)
        self._open = False
        self.t1 = self._reg.clock()
        self._reg._record(self)

    def cancel(self) -> None:
        """Drop the span unrecorded (an idle serving tick is not a step)."""
        self._open = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Phase:
    """One span site, two sinks: THE way an engine opens a span.

    Opening enters a ``jax.profiler.TraceAnnotation`` named ``name`` with
    ``args`` (on the profiler's host plane whenever a profile is being
    taken, about a microsecond when none is) and, when ``registry`` is a
    :class:`MetricsRegistry`, begins the same interval there; ``registry``
    is None for an engine that configured none. :meth:`annotate` adds
    arguments known only later (``traced=``, ``scheduled_tokens=``) to
    both. Nothing here fences: a site that must block on the device says
    so in its own code (``train/device`` blocks inside its ``with``, and
    is opened only when the registry is on). Sites use it as a ``with``
    block, so a body that raises still closes both sinks and the thread's
    annotations stay nested."""

    __slots__ = ("_ann", "_sp")

    def __init__(self, registry: Optional["MetricsRegistry"], name: str,
                 cat: str = "train", **args):
        # the registry's clock is read before anything is allocated: what
        # opening costs (a collection of garbage, once in a while) belongs
        # inside the span, or a step's children would not cover it
        t0 = registry.clock() if registry is not None else None
        self._ann = TraceAnnotation(name, **args)
        self._ann.__enter__()
        self._sp = (registry.begin(name, cat, args or None, t0)
                    if registry is not None else None)

    @property
    def t0(self) -> Optional[float]:
        """Opening time on the registry's clock (None without one)."""
        return self._sp.t0 if self._sp is not None else None

    @property
    def t1(self) -> Optional[float]:
        return self._sp.t1 if self._sp is not None else None

    def annotate(self, **kw) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**kw)
        if self._sp is not None:
            self._sp.annotate(**kw)

    def _close(self) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def end(self) -> None:
        if self._sp is not None:
            self._sp.end()
        self._close()

    def cancel(self) -> None:
        """Drop the registry's span unrecorded. An annotation cannot be
        taken back once entered: it closes under its name, so a site that
        may cancel is opened only when that is rare and harmless (see
        ``ServingEngine._step_inner``)."""
        if self._sp is not None:
            self._sp.cancel()
        self._close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class MetricsRegistry:
    """Process-global span + metric-event store with Chrome export.

    Bounded: past ``max_spans`` recorded spans (and as many samples) new
    entries are counted in ``dropped`` instead of stored, so a runaway
    loop cannot OOM the host through its own telemetry."""

    def __init__(self, max_spans: int = 100_000, clock=time.perf_counter):
        self.max_spans = int(max_spans)
        self.clock = clock
        self.t_origin = clock()
        self.spans: List[Dict[str, Any]] = []      # finished X events
        self.async_events: List[Dict[str, Any]] = []  # b/e/i request events
        self.samples: List[Tuple[str, float, Optional[int], float]] = []
        self.dropped = 0
        self._lock = threading.Lock()

    # ------------------------------------------------------------- spans
    def begin(self, name: str, cat: str = "train",
              args: Optional[Dict[str, Any]] = None,
              t0: Optional[float] = None) -> Span:
        return Span(self, name, cat, args, threading.get_ident(), t0)

    def span(self, name: str, cat: str = "train",
             args: Optional[Dict[str, Any]] = None) -> Span:
        """Context-manager form: ``with reg.span("train/step"): ...``"""
        return self.begin(name, cat, args)

    def _record(self, span: Span) -> None:
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.dropped += 1
                return
            self.spans.append({
                "name": span.name, "cat": span.cat, "t0": span.t0,
                "t1": span.t1, "tid": span.tid, "args": span.args,
            })

    def add_span(self, name: str, cat: str, t0: float, t1: float,
                 args: Optional[Dict[str, Any]] = None, tid=None) -> None:
        """Retro-record a finished interval (explicit timestamps on this
        registry's clock) — request-scoped chunk spans and ``plan/*``
        prediction spans use this."""
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.dropped += 1
                return
            self.spans.append({
                "name": name, "cat": cat, "t0": float(t0), "t1": float(t1),
                "tid": tid if tid is not None else threading.get_ident(),
                "args": args,
            })

    # ----------------------------------------------- async (request) spans
    def async_begin(self, name: str, cat: str, aid: str,
                    args: Optional[Dict[str, Any]] = None,
                    t: Optional[float] = None) -> None:
        self._async("b", name, cat, aid, args, t)

    def async_end(self, name: str, cat: str, aid: str,
                  t: Optional[float] = None) -> None:
        self._async("e", name, cat, aid, None, t)

    def instant(self, name: str, cat: str, aid: Optional[str] = None,
                args: Optional[Dict[str, Any]] = None,
                t: Optional[float] = None) -> None:
        self._async("i", name, cat, aid, args, t)

    def _async(self, ph, name, cat, aid, args, t) -> None:
        with self._lock:
            if len(self.async_events) >= self.max_spans:
                self.dropped += 1
                return
            self.async_events.append({
                "ph": ph, "name": name, "cat": cat, "id": aid,
                "t": self.clock() if t is None else float(t), "args": args,
            })

    # ------------------------------------------------------ metric events
    def sample(self, tag: str, value: float, step: Optional[int] = None
               ) -> None:
        """One registry metric sample (exported as a Chrome counter
        event). The comms logger's record_streams/record_ring/record_kv
        emit here when attached."""
        with self._lock:
            if len(self.samples) >= self.max_spans:
                self.dropped += 1
                return
            self.samples.append((tag, float(value), step, self.clock()))

    def write_events(self, monitor, events) -> None:
        """THE monitor bridge: record the (tag, value, step) triples as
        registry samples, then forward to the monitor backends (no-op
        monitor=None). ServingMetrics.write_to and CommsLogger.write_to
        route through here so every backend sees one namespace."""
        for tag, value, step in events:
            self.sample(tag, value, step)
        if monitor is not None:
            monitor.write_events(list(events))

    # --------------------------------------------------------- reporting
    def spans_named(self, name: str) -> List[Dict[str, Any]]:
        return [s for s in self.spans if s["name"] == name]

    def mean_dur(self, name: str) -> float:
        xs = self.spans_named(name)
        if not xs:
            return 0.0
        return sum(s["t1"] - s["t0"] for s in xs) / len(xs)

    def plan_span(self, name: str, stream: Dict[str, Any],
                  measured_step_s: Optional[float] = None,
                  hardware=None) -> None:
        """One ``plan/<name>`` span carrying the shardplan prediction for
        a declared analytic stream (bytes + seconds at the hardware
        table's link bandwidth) next to the measured step wall clock —
        the per-component drift ledger entry, inspectable in Perfetto."""
        args = stream_span_args(stream, hardware=hardware)
        if measured_step_s:
            args["measured_step_s"] = round(float(measured_step_s), 6)
            if args["predicted_s_per_step"] > 0:
                args["predicted_over_measured"] = round(
                    args["predicted_s_per_step"] / measured_step_s, 4
                )
        t0 = self.t_origin
        self.add_span(
            f"plan/{name}", "plan", t0,
            t0 + max(args["predicted_s_per_step"], 1e-6), args=args,
            tid="plan",
        )

    # ------------------------------------------------------------ export
    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object (Perfetto / chrome://tracing).
        ``ts`` is µs since the registry's origin."""
        pid = os.getpid()

        def us(t):
            return round((t - self.t_origin) * 1e6, 1)

        events: List[Dict[str, Any]] = []
        for s in self.spans:
            ev = {
                "name": s["name"], "cat": s["cat"], "ph": "X",
                "ts": us(s["t0"]),
                "dur": round(max(s["t1"] - s["t0"], 0.0) * 1e6, 1),
                "pid": pid, "tid": s["tid"],
            }
            if s["args"]:
                ev["args"] = s["args"]
            events.append(ev)
        for a in self.async_events:
            ev = {
                "name": a["name"], "cat": a["cat"], "ph": a["ph"],
                "ts": us(a["t"]), "pid": pid, "tid": "requests",
            }
            if a["id"] is not None:
                ev["id"] = a["id"]
            if a["ph"] == "i":
                ev["s"] = "t"
            if a["args"]:
                ev["args"] = a["args"]
            events.append(ev)
        for tag, value, step, t in self.samples:
            ev = {
                "name": tag, "cat": "metric", "ph": "C", "ts": us(t),
                "pid": pid, "args": {"value": value},
            }
            if step is not None:
                ev["args"]["step"] = step
            events.append(ev)
        events.sort(key=lambda e: e["ts"])
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "tool": "deepspeed_tpu.steptrace",
                "dropped": self.dropped,
            },
        }

    def export(self, path: str) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


# --------------------------------------------------------- global registry
_GLOBAL: Optional[MetricsRegistry] = None


def configure(max_spans: int = 100_000, clock=None) -> MetricsRegistry:
    """Create (or fetch) the process-global registry. Repeated calls
    share ONE registry — engines that enable tracing in the same process
    land on the same timeline; ``max_spans`` only grows (the largest
    requested bound wins)."""
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = MetricsRegistry(
            max_spans=max_spans,
            clock=clock if clock is not None else time.perf_counter,
        )
    else:
        _GLOBAL.max_spans = max(_GLOBAL.max_spans, int(max_spans))
    return _GLOBAL


def get_registry() -> Optional[MetricsRegistry]:
    return _GLOBAL


def reset() -> None:
    """Drop the global registry (tests; a fresh trace per scenario)."""
    global _GLOBAL
    _GLOBAL = None


def write_events(monitor, events) -> None:
    """Module-level monitor bridge: routes through the global registry
    when one exists (so traced runs capture every metric event), else
    straight to the monitor. Safe with monitor=None."""
    reg = _GLOBAL
    if reg is not None:
        reg.write_events(monitor, events)
    elif monitor is not None:
        monitor.write_events(list(events))


def stream_span_args(stream: Dict[str, Any], hardware=None
                     ) -> Dict[str, Any]:
    """Shardplan-prediction args for one ``analytic_streams()`` entry:
    the declared bytes plus the seconds they cost at the hardware
    table's link bandwidth for the stream's kind (offload → host DMA
    link, ici → interconnect, hbm → HBM) — the same pricing rule R8 and
    the cost planner use, so the span's prediction and the planner's
    never drift apart."""
    if hardware is None:
        from ..analysis.cost.hardware import HardwareModel

        hardware = HardwareModel.detect()
    kind = stream.get("kind", "hbm")
    bw = {
        "offload": hardware.host_bw,
        "ici": hardware.ici_bw,
        "hbm": hardware.hbm_bw,
    }.get(kind, hardware.hbm_bw)
    nbytes = int(
        stream.get("per_device_bytes_per_step",
                   stream.get("bytes_per_step", 0))
    )
    return {
        "kind": kind,
        "overlapped": bool(stream.get("overlapped", False)),
        "predicted_bytes_per_step": int(stream.get("bytes_per_step", 0)),
        "predicted_per_device_bytes_per_step": nbytes,
        "predicted_s_per_step": (nbytes / bw) if bw > 0 else 0.0,
        "gen": getattr(hardware, "gen", "?"),
    }


class ServeTracer:
    """Request-scoped span trees for the serving engine, as Chrome async
    events keyed by request id: QUEUED → PREFILL (chunk i nested) →
    DECODE → DONE (or EVICTED anywhere). Driven by the ServingMetrics
    hooks (which already see every lifecycle transition) plus the
    engine's per-chunk callback — timestamps are the REGISTRY's clock,
    not the scheduler's injectable one, so request spans and engine-step
    spans share a timeline even under a virtual replay clock."""

    CAT = "serve.request"

    def __init__(self, registry: MetricsRegistry):
        self.reg = registry
        self._phase: Dict[str, str] = {}   # rid -> open phase name
        self._chunks: Dict[str, int] = {}  # rid -> chunks fed so far

    @staticmethod
    def _rid(state) -> str:
        return str(state.request.request_id)

    def on_submit(self, state) -> None:
        rid = self._rid(state)
        self.reg.async_begin("QUEUED", self.CAT, rid,
                             args={"prompt_len": state.prompt_len})
        self._phase[rid] = "QUEUED"

    def on_admit(self, state) -> None:
        rid = self._rid(state)
        self.reg.async_end("QUEUED", self.CAT, rid)
        self.reg.async_begin(
            "PREFILL", self.CAT, rid,
            args={"cached_tokens": int(getattr(state, "cached_tokens", 0))},
        )
        self._phase[rid] = "PREFILL"

    def on_chunk(self, state, n_tokens: int, t0: float, t1: float) -> None:
        """One scheduled prompt chunk, spanning the engine-step window
        that fed it (explicit timestamps from the step's dispatch+device
        spans)."""
        rid = self._rid(state)
        i = self._chunks.get(rid, 0)
        self._chunks[rid] = i + 1
        self.reg.async_begin(f"PREFILL chunk {i}", self.CAT, rid,
                             args={"tokens": int(n_tokens)}, t=t0)
        self.reg.async_end(f"PREFILL chunk {i}", self.CAT, rid, t=t1)

    def on_spec(self, state, proposed: int, accepted: int) -> None:
        """One speculative verify window (instant event on the request's
        track): how many drafts this slot proposed and how many the
        verifier accepted — the per-request acceptance trace next to the
        ``serve/step`` spans' ``spec_draft_tokens`` annotation."""
        self.reg.instant(
            "SPEC verify", self.CAT, self._rid(state),
            args={"proposed": int(proposed), "accepted": int(accepted)},
        )

    def on_token(self, state) -> None:
        if len(state.tokens) != 1:
            return  # only the FIRST token flips PREFILL -> DECODE
        rid = self._rid(state)
        if self._phase.get(rid) == "PREFILL":
            self.reg.async_end("PREFILL", self.CAT, rid)
        self.reg.async_begin("DECODE", self.CAT, rid)
        self._phase[rid] = "DECODE"

    def on_finish(self, state) -> None:
        rid = self._rid(state)
        if self._phase.get(rid) == "DECODE":
            self.reg.async_end("DECODE", self.CAT, rid)
        self.reg.instant(
            "DONE", self.CAT, rid,
            args={"tokens_out": len(state.tokens)},
        )
        self._phase.pop(rid, None)
        self._chunks.pop(rid, None)

    def on_evict(self, state) -> None:
        rid = self._rid(state)
        phase = self._phase.pop(rid, None)
        if phase is not None:
            self.reg.async_end(phase, self.CAT, rid)
        self.reg.instant(
            "EVICTED", self.CAT, rid,
            args={"reason": state.evict_reason or "unknown"},
        )
        self._chunks.pop(rid, None)
