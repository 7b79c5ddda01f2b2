"""Monitor writers + comms logger + flops profiler (SURVEY §2.7)."""

import csv
import os

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.comm import collectives
from deepspeed_tpu.config import DeepSpeedConfig
from deepspeed_tpu.models import llama
from deepspeed_tpu.monitor.monitor import MonitorMaster, csv_monitor
from deepspeed_tpu.profiling.comm_logger import CommsLogger, get_bw
from deepspeed_tpu.profiling.flops_profiler import (
    FlopsProfiler,
    get_model_profile,
)


def test_csv_monitor_writes(tmp_path):
    mon = csv_monitor(str(tmp_path), "job")
    mon.write_events([("Train/loss", 1.5, 1), ("Train/loss", 1.2, 2)])
    mon.close()
    with open(os.path.join(str(tmp_path), "job", "Train_loss.csv")) as f:
        rows = list(csv.reader(f))
    assert rows == [["1", "1.5"], ["2", "1.2"]]


def test_monitor_master_from_config(tmp_path):
    cfg = DeepSpeedConfig(
        {
            "train_batch_size": 8,
            "csv_monitor": {
                "enabled": True,
                "output_path": str(tmp_path),
                "job_name": "j",
            },
        }
    )
    assert cfg.monitor.enabled
    master = MonitorMaster(cfg.monitor)
    assert master.enabled
    master.write_events([("Train/lr", 0.1, 1)])
    assert os.path.exists(os.path.join(str(tmp_path), "j", "Train_lr.csv"))


def test_comms_logger_records_shard_map_ops():
    logger = CommsLogger()
    x = jnp.ones((8, 4), jnp.float32)
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    f = jax.shard_map(
        lambda a: collectives.all_reduce(a, "dp"),
        mesh=mesh,
        in_specs=P("dp"),
        out_specs=P(),
    )
    jax.jit(f)(x)
    logger.stop()
    assert logger.counts["all_reduce"] == 1
    # bytes recorded at trace time: per-shard payload
    assert logger.bytes["all_reduce"] == 2 * 4 * 4


def test_comms_logger_offload_stream_accounting():
    """The bucketed ZeRO-offload DMA stream is not a collective — the
    engine reports it per step; the logger must aggregate bytes, expose
    the in-flight (slots × slice) peak, and render an offload line in
    the summary."""
    logger = CommsLogger()
    try:
        logger.record_offload(100, 100, slots=2, slot_bytes=10, steps=3)
        assert logger.offload_steps == 3
        assert logger.offload_bytes_in == 300
        assert logger.offload_bytes_out == 300
        assert logger.offload_bytes_in_flight == 20
        s = logger.summary(duration_s=1.0)
        assert "offload stream" in s
        assert "2 slot(s)" in s
        # no offload recorded → no offload line
        assert "offload stream" not in CommsLogger().summary(duration_s=1.0)
    finally:
        logger.stop()
    # overlap-ratio arithmetic: (serial - overlapped) / dma, clamped [0,1]
    assert CommsLogger.offload_overlap_ratio(4.0, 3.0, 2.0) == 0.5
    assert CommsLogger.offload_overlap_ratio(4.0, 4.5, 2.0) == 0.0
    assert CommsLogger.offload_overlap_ratio(4.0, 1.0, 2.0) == 1.0
    assert CommsLogger.offload_overlap_ratio(4.0, 3.0, 0.0) == 0.0


def test_get_bw_formulas():
    alg, bus = get_bw("all_reduce", 1e9, 1.0, 4)
    assert abs(alg - 8.0) < 1e-9
    assert abs(bus - 8.0 * 1.5) < 1e-9  # 2(n-1)/n = 1.5
    alg, bus = get_bw("all_gather", 1e9, 1.0, 4)
    assert abs(bus - 8.0 * 0.75) < 1e-9


def test_flops_profiler_analytic():
    model = llama(
        "llama-tiny",
        vocab_size=512,
        max_seq_len=64,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=128,
    )
    flops, macs, params = get_model_profile(model, batch=2, seq=32)
    assert flops > 0 and macs == flops / 2
    assert params == model.num_params()
    # dominated by matmuls: flops ≈ 2 * tokens * params for tiny seq
    approx = 2 * 2 * 32 * params
    assert 0.5 < flops / approx < 3.0


def test_flops_profiler_xla_cost_and_report(tmp_path):
    model = llama(
        "llama-tiny",
        vocab_size=128,
        max_seq_len=32,
        hidden_size=32,
        num_layers=1,
        num_heads=2,
        num_kv_heads=2,
        intermediate_size=64,
    )
    prof = FlopsProfiler(model)
    prof.start_profile()
    root = prof.profile_model(batch=1, seq=16)
    prof.stop_profile()
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 16), jnp.int32)
    cost = prof.profile_compiled(lambda p, x: model.apply(p, x), params, ids)
    assert cost["flops"] > 0
    out = prof.print_model_profile(output_file=str(tmp_path / "prof.txt"))
    assert "lm_head" in out and "attention" in out
    assert os.path.exists(tmp_path / "prof.txt")
    assert prof.get_total_flops() == root.flops


def test_profile_step_writes_trace(tmp_path, devices8):
    """engine.profile_step dumps an xprof trace artifact (SURVEY §2.7
    tracing/debug; r2 verdict: no jax.profiler integration existed)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    import numpy as np
    from deepspeed_tpu.models import gpt2

    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16),
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 1000,
        },
    )
    data = {"input_ids": np.random.RandomState(0).randint(0, 64, size=(8, 16))}
    trace_dir = str(tmp_path / "trace")
    loss, out_dir = engine.profile_step(batch=data, trace_dir=trace_dir)
    assert np.isfinite(float(loss))
    files = [
        os.path.join(dp, f)
        for dp, _, fs in os.walk(out_dir)
        for f in fs
    ]
    assert files, "no trace artifact written"


def test_native_tfevents_writer_roundtrip(tmp_path):
    """The torch-free tfevents writer produces records TensorBoard can read:
    verify TFRecord framing (masked CRC32C) and the scalar payload."""
    import struct

    from deepspeed_tpu.monitor.tfevents import TfEventsWriter, _masked_crc

    w = TfEventsWriter(str(tmp_path))
    w.add_scalar("Train/loss", 2.5, 7)
    w.add_scalar("Train/lr", 1e-4, 7)
    w.close()

    files = [f for f in os.listdir(tmp_path) if f.startswith("events.out.tfevents")]
    assert len(files) == 1
    raw = open(os.path.join(tmp_path, files[0]), "rb").read()

    records = []
    off = 0
    while off < len(raw):
        (length,) = struct.unpack_from("<Q", raw, off)
        (hcrc,) = struct.unpack_from("<I", raw, off + 8)
        header = raw[off : off + 8]
        assert hcrc == _masked_crc(header)
        payload = raw[off + 12 : off + 12 + length]
        (pcrc,) = struct.unpack_from("<I", raw, off + 12 + length)
        assert pcrc == _masked_crc(payload)
        records.append(payload)
        off += 12 + length + 4
    assert len(records) == 3  # version event + 2 scalars
    assert b"brain.Event:2" in records[0]
    assert b"Train/loss" in records[1]
    # float 2.5 little-endian appears in the first scalar record
    assert struct.pack("<f", 2.5) in records[1]

    # if the real tensorboard reader is importable, cross-check with it
    try:
        from tensorboard.backend.event_processing.event_file_loader import (
            EventFileLoader,
        )
    except Exception:
        return
    events = list(EventFileLoader(os.path.join(tmp_path, files[0])).Load())
    scalars = {}
    for e in events:
        for v in e.summary.value:
            # loaders may migrate simple_value → scalar tensor proto
            scalars[v.tag] = (
                v.tensor.float_val[0]
                if v.HasField("tensor") and v.tensor.float_val
                else v.simple_value
            )
    assert abs(scalars["Train/loss"] - 2.5) < 1e-6
    assert scalars["Train/lr"] > 0


def test_monitor_bridge_csv_roundtrip_serve_namespace(tmp_path):
    """ISSUE 8 satellite: registry events from a traced serving replay
    land in the CSV backend under the documented ``serve/*`` names with
    monotone steps — ServingMetrics.write_to routes through the
    steptrace registry's single ``write_events`` bridge."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama as _llama
    from deepspeed_tpu.profiling import steptrace
    from deepspeed_tpu.serving import Request, ServingEngine

    steptrace.reset()
    try:
        model = _llama(
            "llama-tiny", vocab_size=128, max_seq_len=64, hidden_size=32,
            num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=64,
        )
        eng = deepspeed_tpu.init_inference(
            model, dtype=jnp.float32, max_tokens=64,
            rng=jax.random.PRNGKey(0),
        )
        srv = ServingEngine(engine=eng, serving={
            "max_slots": 2, "token_budget": 8, "max_tokens": 64,
        }, steptrace={"enabled": True})
        mon = csv_monitor(str(tmp_path), "serve_job")
        r = np.random.RandomState(0)
        for i in range(2):
            srv.submit(Request(request_id=f"r{i}",
                               prompt=r.randint(0, 128, size=(5,)),
                               max_new_tokens=2))
        while srv.scheduler.has_work:
            srv.step()
            srv.metrics.write_to(mon, step=srv.metrics.steps)
        mon.close()

        job = os.path.join(str(tmp_path), "serve_job")
        files = sorted(os.listdir(job))
        # documented serve/* namespace (tag / -> filename _), nothing
        # under the legacy Serving/ prefix
        assert all(f.startswith("serve_") for f in files)
        for key in ("serve_tokens_out", "serve_steps", "serve_ttft_p50_s"):
            assert f"{key}.csv" in files
        with open(os.path.join(job, "serve_steps.csv")) as f:
            rows = [(int(a), float(b)) for a, b in csv.reader(f)]
        steps = [a for a, _ in rows]
        assert steps == sorted(steps) and len(set(steps)) == len(steps), \
            "steps must be strictly monotone"
        assert [b for _, b in rows] == [float(s) for s in steps]
        # the bridge ALSO recorded every event into the registry
        reg = steptrace.get_registry()
        assert any(t.startswith("serve/") for t, *_ in reg.samples)
    finally:
        steptrace.reset()


def test_overlap_ratio_is_the_single_hardened_path():
    """ISSUE 4 satellite: the generic ``overlap_ratio`` IS the primary
    (one hardened zero/NaN/None path); ``offload_overlap_ratio`` is the
    same function under its legacy name, so the two can never drift."""
    assert CommsLogger.overlap_ratio is CommsLogger.offload_overlap_ratio
    r = CommsLogger.overlap_ratio
    # the generic name carries the full degenerate-input hardening
    assert r(4.0, 3.0, 2.0) == 0.5
    assert r(4.0, 1.0, 2.0) == 1.0           # clamped at fully-hidden
    assert r(4.0, 3.0, 0.0) == 0.0           # zero-byte stream
    assert r(float("nan"), 3.0, 2.0) == 0.0  # failed A/B leg
    assert r(None, 3.0, 2.0) == 0.0          # type junk
    assert r("x", 3.0, 2.0) == 0.0


def test_record_streams_shared_intake():
    """engine.analytic_streams() → comm_logger.record_streams: ONE
    accounting path for offload + ring streams; planner-only (assumed)
    streams are never recorded."""
    logger = CommsLogger()
    try:
        logger.record_streams({
            "offload": {
                "kind": "offload", "bytes_in": 100, "bytes_out": 60,
                "slots": 2, "slot_bytes": 10, "overlapped": True,
            },
            "tp_ring": {"kind": "ici", "bytes_per_step": 7, "overlapped": True},
            "ghost": {
                "kind": "offload", "bytes_in": 999, "bytes_out": 999,
                "assumed": True,  # CPU lint mesh pricing — planner-only
            },
        }, steps=3)
    finally:
        logger.stop()
    assert logger.offload_steps == 3
    assert logger.offload_bytes_in == 300 and logger.offload_bytes_out == 180
    assert logger.offload_bytes_in_flight == 20
    assert logger.ring_steps == 3 and logger.ring_bytes == 21


def test_offload_overlap_ratio_degenerate_inputs():
    """ISSUE 2 satellite: zero-duration / empty offload streams and failed
    A/B legs must report 0.0 overlap, never raise."""
    r = CommsLogger.offload_overlap_ratio
    assert r(0.0, 0.0, 0.0) == 0.0          # empty stream, nothing timed
    assert r(4.0, 3.0, 0.0) == 0.0          # zero-byte stream → no DMA
    assert r(0.0, 3.0, 2.0) == 0.0          # unmeasured serial leg
    assert r(4.0, 0.0, 2.0) == 0.0          # unmeasured overlapped leg
    assert r(-1.0, 3.0, 2.0) == 0.0         # negative wall time
    assert r(float("nan"), 3.0, 2.0) == 0.0  # failed A/B leg
    assert r(float("inf"), 3.0, 2.0) == 0.0
    assert r(None, 3.0, 2.0) == 0.0          # type junk survives too
    # the happy path is untouched by the guards
    assert r(4.0, 3.0, 2.0) == 0.5
    # empty-stream summary stays empty (no division by zero steps)
    logger = CommsLogger()
    try:
        assert logger.offload_summary(duration_s=0.0) == ""
        logger.record_offload(0, 0, slots=0, slot_bytes=0, steps=1)
        assert "0.00 GiB/step" in logger.offload_summary(duration_s=0.0)
    finally:
        logger.stop()
