"""steptrace: unified structured tracing + metrics registry (ISSUE 8).

The tentpole contract: host-side spans bracket dispatches (fencing via
block_until_ready at close), the serving replay produces CLOSED request
span trees (QUEUED→PREFILL chunk i→DECODE→DONE), every declared
analytic stream appears as a plan/* span carrying its shardplan
prediction, export is valid Chrome trace-event JSON
(tools/trace_report.py --validate), and disabled tracing stores
ZERO spans (tests/test_phase_spans.py holds the other sink, the
profiler's trace). Satellites: the timer barrier fence fix and the hardened
drift-ledger append ride along here.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import llama
from deepspeed_tpu.profiling import steptrace
from deepspeed_tpu.serving import Request, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_registry():
    steptrace.reset()
    yield
    steptrace.reset()


def tiny_llama(**kw):
    d = dict(vocab_size=128, max_seq_len=64, hidden_size=32, num_layers=2,
             num_heads=4, num_kv_heads=2, intermediate_size=64)
    d.update(kw)
    return llama("llama-tiny", **d)


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------
def test_registry_spans_nest_and_export_chrome(tmp_path):
    reg = steptrace.MetricsRegistry(max_spans=100)
    with reg.span("train/step", "train", {"step": 1}):
        with reg.span("train/dispatch", "train"):
            pass
    reg.sample("train/loss", 2.5, step=1)
    reg.async_begin("QUEUED", "serve.request", "r0")
    reg.async_end("QUEUED", "serve.request", "r0")
    reg.instant("DONE", "serve.request", "r0")
    out = reg.export(str(tmp_path / "t.json"))
    d = json.load(open(out))
    evs = d["traceEvents"]
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"train/step", "train/dispatch"}
    for e in xs.values():
        assert e["dur"] >= 0 and e["ts"] >= 0
    # the child nests inside the parent on the export timeline
    p, c = xs["train/step"], xs["train/dispatch"]
    assert p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"]
    assert p["args"] == {"step": 1}
    phs = {e["ph"] for e in evs}
    assert {"X", "b", "e", "i", "C"} <= phs


def test_registry_is_bounded_and_counts_drops():
    reg = steptrace.MetricsRegistry(max_spans=3)
    for i in range(5):
        reg.begin(f"s{i}", "train").end()
    assert len(reg.spans) == 3
    assert reg.dropped == 2


def test_disabled_config_gives_no_tracer_and_null_span():
    """The one span entry without a registry (what a disabled or missing
    "steptrace" section gives an engine): every call is accepted, nothing
    is stored anywhere and no registry comes into being."""
    with steptrace.Phase(None, "train/step", step=1) as sp:
        sp.annotate(x=1)
        assert sp.t0 is None and sp.t1 is None
    sp.end()  # closing twice is harmless
    steptrace.Phase(None, "serve/plan", "serve").cancel()
    assert steptrace.get_registry() is None  # nothing configured globally


def test_span_fence_blocks_on_device_value():
    reg = steptrace.MetricsRegistry()
    x = jnp.ones((64, 64))
    sp = reg.begin("train/device", "train")
    y = x @ x
    sp.end(fence=y)  # block_until_ready at close — must not raise
    assert reg.spans[-1]["name"] == "train/device"
    assert reg.spans[-1]["t1"] >= reg.spans[-1]["t0"]


def test_write_events_bridge_records_and_forwards():
    reg = steptrace.configure()

    class FakeMonitor:
        def __init__(self):
            self.events = []

        def write_events(self, evs):
            self.events.extend(evs)

    mon = FakeMonitor()
    steptrace.write_events(mon, [("serve/tokens_out", 3.0, 1)])
    assert mon.events == [("serve/tokens_out", 3.0, 1)]
    assert reg.samples[0][:3] == ("serve/tokens_out", 3.0, 1)
    # registry-less bridge still forwards (and survives monitor=None)
    steptrace.reset()
    steptrace.write_events(mon, [("comm/x_bytes", 1.0, 2)])
    steptrace.write_events(None, [("comm/x_bytes", 1.0, 3)])
    assert mon.events[-1] == ("comm/x_bytes", 1.0, 2)


def test_stream_span_args_price_by_kind():
    class HW:
        gen = "test"
        host_bw, ici_bw, hbm_bw = 10.0, 5.0, 2.0

    a = steptrace.stream_span_args(
        {"kind": "offload", "bytes_per_step": 100,
         "per_device_bytes_per_step": 50, "overlapped": True}, hardware=HW
    )
    assert a["predicted_s_per_step"] == 5.0      # 50 / host_bw
    assert a["predicted_bytes_per_step"] == 100
    assert a["overlapped"] is True
    a = steptrace.stream_span_args({"kind": "hbm", "bytes_per_step": 8},
                                   hardware=HW)
    assert a["predicted_s_per_step"] == 4.0      # 8 / hbm_bw


# ---------------------------------------------------------------------------
# the acceptance replay: traced serving run -> valid trace, closed trees
# ---------------------------------------------------------------------------
def test_traced_serving_replay_valid_closed_annotated(tmp_path):
    eng = deepspeed_tpu.init_inference(
        tiny_llama(), dtype=jnp.float32, max_tokens=64,
        rng=jax.random.PRNGKey(1),
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 64,
    }, steptrace={"enabled": True})
    assert srv.tracer is not None
    r = np.random.RandomState(0)
    for i in range(3):
        srv.submit(Request(request_id=f"r{i}",
                           prompt=r.randint(0, 128, size=(9,)),
                           max_new_tokens=3))
    srv.run_until_idle()
    path = srv.trace_export(str(tmp_path / "serve.json"))
    events = json.load(open(path))["traceEvents"]

    tr = _load_trace_report()
    problems = tr.validate(events)
    assert problems == [], problems

    # every request's span tree is closed: QUEUED..DONE per id, with at
    # least one PREFILL chunk (9-token prompts at budget 8 need two)
    req = [e for e in events if e.get("cat") == "serve.request"]
    ids = {e["id"] for e in req}
    assert ids == {"r0", "r1", "r2"}
    for rid in ids:
        names = [e["name"] for e in req if e["id"] == rid]
        assert "QUEUED" in names and "DONE" in names
        assert "DECODE" in names
        assert any(n.startswith("PREFILL chunk") for n in names)

    # every analytic stream appears as a plan/* span with its prediction
    plan = {e["name"]: e for e in events if e.get("cat") == "plan"}
    for name in srv.analytic_streams():
        e = plan[f"plan/{name}"]
        assert e["args"]["predicted_bytes_per_step"] > 0
        assert e["args"]["predicted_s_per_step"] > 0
        assert e["args"]["measured_step_s"] > 0

    # per-step phase self-times within 10% of the step wall clock is the
    # validate() contract already asserted above; spot-check one step
    xs = [e for e in events if e["ph"] == "X" and e["name"] == "serve/step"]
    assert xs, "no serve/step spans recorded"

    # the report renders (smoke of the CLI's analysis path)
    text = tr.report(events)
    assert "serve/step" in text and "plan/kv_cache" in text
    # and lists what each step held beside its scheduled_tokens (ISSUE 54):
    # step 1 is r0's first chunk, the whole budget of 8 prompt rows
    table = tr.serve_steps(xs := [e for e in events if e["ph"] == "X"])
    assert table[2].split()[:6] == ["step", *tr.STEP_ARGS]
    assert table[3].split()[:6] == ["1", "8", "8", "1", "0", "8"]
    assert len(table) == 3 + srv.metrics.steps
    assert "\n".join(tr.serve_steps(xs, 2)) in tr.report(events, steps=2)
    assert "\n".join(tr.serve_steps(xs)) in text  # 9 steps: all listed


def test_serving_disabled_tracing_allocates_zero_spans():
    eng = deepspeed_tpu.init_inference(
        tiny_llama(), dtype=jnp.float32, max_tokens=64,
        rng=jax.random.PRNGKey(1),
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 64,
    })
    assert srv.tracer is None and srv.metrics.tracer is None
    srv.submit(Request(request_id="r0",
                       prompt=np.arange(4, dtype=np.int64) + 1,
                       max_new_tokens=2))
    srv.run_until_idle()
    assert steptrace.get_registry() is None  # nothing ever configured
    with pytest.raises(RuntimeError, match="steptrace is not enabled"):
        srv.trace_export("/tmp/never.json")


# ---------------------------------------------------------------------------
# train engine: config gate, spans, namespaced monitor events
# ---------------------------------------------------------------------------
def test_train_engine_traced_step_and_namespace(tmp_path, devices8):
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import gpt2

    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16),
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 1,
            "steptrace": {"enabled": True,
                          "export_path": str(tmp_path / "train.json")},
            "csv_monitor": {"enabled": True,
                            "output_path": str(tmp_path / "mon"),
                            "job_name": "j"},
        },
    )
    assert engine.tracer is not None
    data = {"input_ids": np.random.RandomState(0).randint(0, 64,
                                                          size=(8, 16))}
    engine.train_batch(batch=data)
    names = {s["name"] for s in engine.tracer.spans}
    assert {"train/step", "train/batch_prep", "train/dispatch",
            "train/device"} <= names
    # the device span carries real fenced time and nests in the step
    step = engine.tracer.spans_named("train/step")[0]
    for child in ("train/batch_prep", "train/dispatch", "train/device"):
        c = engine.tracer.spans_named(child)[0]
        assert step["t0"] <= c["t0"] and c["t1"] <= step["t1"]
    # monitor events landed under the documented train/* namespace
    job = tmp_path / "mon" / "j"
    assert (job / "train_loss.csv").exists()
    assert (job / "train_lr.csv").exists()
    # export (config export_path default) passes the schema gate
    out = engine.trace_export()
    events = json.load(open(out))["traceEvents"]
    assert _load_trace_report().validate(events) == []


def test_steptrace_config_validation():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError

    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "steptrace": {"enabled": True,
                                         "max_spans": 7}})
    assert cfg.steptrace.enabled and cfg.steptrace.max_spans == 7
    with pytest.raises(DeepSpeedConfigError, match="max_spans"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "steptrace": {"max_spans": 0}})


# ---------------------------------------------------------------------------
# trace_report --validate catches the documented violations
# ---------------------------------------------------------------------------
def test_trace_report_flags_violations(tmp_path):
    tr = _load_trace_report()
    # negative duration
    assert any("negative duration" in p for p in tr.validate([
        {"name": "x", "ph": "X", "ts": 0.0, "dur": -1.0, "tid": 1},
    ]))
    # unclosed request tree: QUEUED begun, never ended, no terminal
    probs = tr.validate([
        {"name": "QUEUED", "ph": "b", "ts": 0.0, "cat": "serve.request",
         "id": "r9"},
    ])
    assert any("unclosed" in p for p in probs)
    assert any("not closed" in p for p in probs)
    # phase-coverage drift: a step whose phases cover less than 90%
    assert any("phase self-times" in p for p in tr.validate([
        {"name": "serve/step", "ph": "X", "ts": 0.0, "dur": 100_000.0,
         "tid": 1},
        {"name": "serve/dispatch", "ph": "X", "ts": 0.0, "dur": 10_000.0,
         "tid": 1},
    ]))
    # CLI round-trip on a valid file
    reg = steptrace.MetricsRegistry()
    reg.begin("train/x", "train").end()
    p = reg.export(str(tmp_path / "ok.json"))
    assert tr.main([p]) == 0
    assert tr.main(["--validate", p]) == 0


# ---------------------------------------------------------------------------
# satellites: timer barrier fix, drift-ledger hardening
# ---------------------------------------------------------------------------
def test_timer_stop_fences_on_block_on_and_warns_on_bare_barrier(
        caplog, monkeypatch):
    import logging

    from deepspeed_tpu.utils import timer as timer_mod
    from deepspeed_tpu.utils.logging import logger as ds_logger

    t = timer_mod._Timer("t")
    t.start()
    x = jnp.ones((32, 32))
    t.stop(barrier=True, block_on=x @ x)  # the actual fence path
    assert t.count == 1 and t.elapsed_total > 0
    # bare barrier=True: host clock only — warns ONCE per process
    monkeypatch.setattr(ds_logger, "propagate", True)  # caplog visibility
    timer_mod._bare_barrier_warned = False
    with caplog.at_level(logging.WARNING):
        t.start()
        t.stop(barrier=True)
        t.start()
        t.stop(barrier=True)
    warns = [r for r in caplog.records if "cannot fence" in r.getMessage()]
    assert len(warns) == 1
    assert t.count == 3


def test_drift_ledger_unwritable_path_warns_not_raises(
        tmp_path, caplog, monkeypatch):
    import logging

    from deepspeed_tpu.analysis.cost.drift import DriftLedger
    from deepspeed_tpu.utils.logging import logger as ds_logger

    monkeypatch.setattr(ds_logger, "propagate", True)  # caplog visibility
    blocker = tmp_path / "blocker"
    blocker.write_text("a file, not a dir")
    # the ledger path's parent is a FILE -> makedirs raises OSError;
    # append must log a warning and continue (read-only CI checkouts)
    ledger = DriftLedger(str(blocker / "perf" / "drift.jsonl"))
    with caplog.at_level(logging.WARNING):
        ledger.append({"ratio": 1.0})  # must NOT raise
    assert any("drift ledger unwritable" in r.getMessage()
               for r in caplog.records)
    assert ledger.load() == []  # nothing written, nothing lost but entry
    # the happy path still writes
    ok = DriftLedger(str(tmp_path / "perf" / "drift.jsonl"))
    ok.append({"ratio": 1.0})
    assert ok.load() == [{"ratio": 1.0}]
