"""Benchmark: training throughput (tokens/sec/chip) + MFU on real TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric matches BASELINE.json ("tokens/sec/chip + MFU"): value is
tokens/sec/chip; MFU is reported alongside in the same JSON object.

Model-FLOPs formula (causal decoder, fwd+bwd = 3x fwd):
  fwd flops/token = 2*N_params + 2 * L * S * d_attnio  (causal QK^T+AV ≈
  2 * 2 * S/2 * (H*hd) mults per token per layer)

MFU accounting is honest: activation_checkpointing.policy is "none" (a 410M
model at this batch fits HBM without remat), so device flops == model flops
and the 3x-fwd formula matches what actually runs. vs_baseline compares
against the best verified record in RECORDS.json next to this script.
"""

import json
import os
import sys
import time

import numpy as np

REPO_DIR = os.path.dirname(os.path.abspath(__file__))


def peak_flops_per_chip() -> float:
    """bf16 peak for the local chip generation — delegates to the
    planner's hardware table (analysis/cost/hardware.py) so bench MFU
    and plan rooflines price the same machine from one table."""
    from deepspeed_tpu.analysis.cost import HardwareModel

    return HardwareModel.detect().peak_flops


def smoke_mode() -> bool:
    """BENCH_SMOKE=1 → CPU end-to-end validation. Self-contained: forces the
    CPU platform so the smoke runs anywhere, chip or no chip. Must be called
    before any jax backend init."""
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    if smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")
    return smoke


def tp_overlap_ab_mode() -> bool:
    """BENCH_TP_OVERLAP_AB=1 → CPU-mesh A/B of the decomposed collective
    matmul (tensor_parallel.overlap_comm). Like smoke mode it forces the
    CPU platform (and an 8-device host mesh so tp=2 × dp=4 exists); must
    run before any jax backend init."""
    return _force_cpu_mesh_mode("BENCH_TP_OVERLAP_AB")


def run_tp_overlap_ab():
    """Serial (GSPMD-inserted collectives) vs overlapped (decomposed ring)
    TP step on the CPU mesh. Prints ONE JSON line with both step times,
    the comm_logger ring-bytes/step figure and the overlap ratio.

    This is an end-to-end *validation* A/B — CPU step times say nothing
    about ICI overlap, so the knob stays default-off and no perf record is
    banked; the on-chip A/B recipe is in docs/collective_matmul.md."""
    import jax

    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import llama

    B, S = 8, 256
    model = llama(
        "llama-tiny", vocab_size=512, max_seq_len=S, hidden_size=128,
        num_layers=2, num_heads=8, num_kv_heads=4, head_dim=16,
        intermediate_size=512,
    )
    data = {
        "input_ids": np.random.RandomState(0).randint(0, 512, size=(B, S))
    }

    def leg(tp_section):
        comm.destroy_process_group()
        cfg = make_ds_config(B, {"stage": 0}, "none", B // 4, {},
                             tp=tp_section)
        cfg["comms_logger"] = {"enabled": True}
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        engine.train_batch(batch=data)  # compile
        if engine.comm_logger is not None:
            # drop the compile step's ring record so the Gbps line really
            # covers the timed window only
            engine.comm_logger.ring_steps = 0
            engine.comm_logger.ring_bytes = 0
        t0 = time.perf_counter()
        n = 5
        for _ in range(n):
            engine.train_batch(batch=data)
        jax.block_until_ready(engine.state.params)
        dt = (time.perf_counter() - t0) / n
        stream = engine.tp_overlap_stream
        # Gbps over the TIMED window only — the logger's own elapsed spans
        # compile/setup and would read ~0 (offload_summary callers ditto)
        ring_line = (
            engine.comm_logger.ring_summary(duration_s=n * dt)
            if engine.comm_logger
            else ""
        )
        engine.destroy()
        return dt, stream, ring_line

    dt_serial, _, _ = leg({"tp_size": 2})
    dt_overlap, stream, ring_line = leg(overlap_tp_section(2))
    print(ring_line)
    return _ab_result(
        "tp_overlap A/B (CPU-mesh validation, not a perf record; "
        "knob default-off pending on-chip A/B)",
        dt_serial, dt_overlap, (stream or {}).get("bytes_per_step", 0),
    )


def moe_a2a_ab_mode() -> bool:
    """BENCH_MOE_A2A_AB=1 → CPU-mesh A/B of the decomposed MoE all-to-all
    (moe.overlap_a2a). Forces the CPU platform + an 8-device host mesh
    (dp=2 × ep=4); must run before any jax backend init."""
    return _force_cpu_mesh_mode("BENCH_MOE_A2A_AB")


def z3_prefetch_ab_mode() -> bool:
    """BENCH_Z3_PREFETCH_AB=1 → CPU-mesh A/B of the ZeRO-3 one-layer-ahead
    parameter prefetch (zero_optimization.stage3_layer_prefetch)."""
    return _force_cpu_mesh_mode("BENCH_Z3_PREFETCH_AB")


def _force_cpu_mesh_mode(env: str) -> bool:
    on = bool(os.environ.get(env))
    if on:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            )
        import jax

        jax.config.update("jax_platforms", "cpu")
    return on


def _ab_result(metric, dt_serial, dt_overlap, stream_bytes, extra=None):
    """The shared serial-vs-overlapped A/B JSON line: step times, the
    analytic stream MiB/step, the wire-seconds estimate at the configured
    ICI bandwidth and the overlap ratio (meaningful on-chip; on the CPU
    mesh it exercises the accounting path end-to-end — same protocol as
    run_tp_overlap_ab, so no perf record is banked)."""
    from deepspeed_tpu.profiling.comm_logger import CommsLogger

    bw = float(os.environ.get("BENCH_ICI_BW_GBS", 45)) * 1e9
    wire_s = stream_bytes / bw if bw > 0 else 0.0
    result = {
        "metric": metric,
        "value": round(dt_overlap, 4),
        "unit": "s/step (overlapped leg)",
        "vs_baseline": 1.0,
        "step_s_serial": round(dt_serial, 4),
        "step_s_overlap": round(dt_overlap, 4),
        "ring_mib_per_step": round(stream_bytes / 2**20, 3),
        "est_ring_wire_s": round(wire_s, 6),
        "overlap_ratio": round(
            CommsLogger.overlap_ratio(dt_serial, dt_overlap, wire_s), 4
        ),
    }
    result.update(extra or {})
    print(json.dumps(result))
    return result


def _timed_leg(engine, data, n: int = 5):
    """Compile + time n steps; returns per-step seconds with the ring
    accounting reset so the logged window covers the timed steps only."""
    import jax

    engine.train_batch(batch=data)  # compile
    if engine.comm_logger is not None:
        engine.comm_logger.ring_steps = 0
        engine.comm_logger.ring_bytes = 0
    t0 = time.perf_counter()
    for _ in range(n):
        engine.train_batch(batch=data)
    jax.block_until_ready(engine.state.params)
    return (time.perf_counter() - t0) / n


def run_moe_a2a_ab():
    """Serial (GSPMD-inserted exchange) vs overlapped (decomposed ring)
    MoE step on the CPU mesh — an end-to-end *validation* A/B printing
    ONE JSON line with step times, the analytic a2a MiB/step and the
    overlap ratio; the knob stays default-off and the on-chip recipe is
    docs/overlap.md."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import mixtral

    B, S = 8, 128
    model = mixtral(
        "mixtral-tiny", vocab_size=512, max_seq_len=S, num_experts=4,
    )
    data = {
        "input_ids": np.random.RandomState(0).randint(0, 512, size=(B, S))
    }

    def leg(overlap):
        comm.destroy_process_group()
        cfg = make_ds_config(B, {"stage": 0}, "none", B // 2, {})
        cfg["moe"] = moe_overlap_section(ep_size=4)
        cfg["moe"]["overlap_a2a"]["enabled"] = overlap
        cfg["comms_logger"] = {"enabled": True}
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        dt = _timed_leg(engine, data)
        stream = engine.analytic_streams().get("moe_a2a") or {}
        ring_line = (
            engine.comm_logger.ring_summary(duration_s=5 * dt)
            if engine.comm_logger else ""
        )
        engine.destroy()
        return dt, stream, ring_line

    dt_serial, _, _ = leg(False)
    dt_overlap, stream, ring_line = leg(True)
    print(ring_line)
    return _ab_result(
        "moe_a2a A/B (CPU-mesh validation, not a perf record; knob "
        "default-off pending on-chip A/B)",
        dt_serial, dt_overlap, stream.get("bytes_per_step", 0),
        extra={"capacity": stream.get("capacity")},
    )


def qgz_ab_mode() -> bool:
    """BENCH_QGZ_AB=1 → CPU-mesh A/B of the wire-codec ZeRO collectives
    (zero_optimization.grad_wire / param_wire — comm/wires.py qgZ/qwZ)."""
    return _force_cpu_mesh_mode("BENCH_QGZ_AB")


def run_qgz_ab():
    """Full-width (fp32 wires) vs quantized (int8 grad + param wires)
    stage-3 step on the CPU mesh — serial-vs-quantized validation A/B
    printing ONE JSON line with both step times, the analytic wire
    MiB/step (grad_wire + param_wire + codec-priced zero3_prefetch
    streams) and the LOSS DELTA vs the full-width leg after the timed
    steps (the codec's end-to-end error evidence; bounds are
    property-tested per codec in tests/test_wires.py). CPU step times
    say nothing about ICI, so the knobs stay default-off and no perf
    record is banked; the on-chip recipe is docs/wires.md."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import llama

    B, S = 8, 128
    model = llama(
        "llama-tiny", vocab_size=512, max_seq_len=S, hidden_size=128,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
        intermediate_size=512,
    )
    data = {
        "input_ids": np.random.RandomState(0).randint(0, 512, size=(B, S))
    }

    def leg(grad_wire, param_wire):
        comm.destroy_process_group()
        zero = {"stage": 3, "stage3_param_persistence_threshold": 1000,
                "grad_wire": grad_wire, "param_wire": param_wire}
        cfg = make_ds_config(B, zero, "none", 1, {})
        cfg["comms_logger"] = {"enabled": True}
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        dt = _timed_leg(engine, data)
        loss = float(engine.train_batch(batch=data))
        streams = engine.analytic_streams()
        wire_bytes = sum(
            streams[k]["bytes_per_step"]
            for k in ("grad_wire", "param_wire", "zero3_prefetch")
            if k in streams
        )
        engine.destroy()
        return dt, loss, wire_bytes

    dt_serial, loss_full, _ = leg("fp32", "fp32")
    dt_q, loss_q, wire_bytes = leg("int8", "int8")
    return _ab_result(
        "qgZ/qwZ wire A/B (CPU-mesh validation, not a perf record; "
        "knobs default-off pending on-chip A/B)",
        dt_serial, dt_q, wire_bytes,
        extra={
            "loss_fullwidth": round(loss_full, 6),
            "loss_quantized": round(loss_q, 6),
            "loss_delta_rel": round(
                abs(loss_q - loss_full) / max(abs(loss_full), 1e-9), 6
            ),
        },
    )


def run_z3_prefetch_ab():
    """Plain stage 3 (all-gather-on-use) vs one-layer-ahead prefetch on
    the CPU mesh — same validation protocol as run_moe_a2a_ab."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import llama

    B, S = 8, 128
    model = llama(
        "llama-tiny", vocab_size=512, max_seq_len=S, hidden_size=128,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
        intermediate_size=512,
    )
    data = {
        "input_ids": np.random.RandomState(0).randint(0, 512, size=(B, S))
    }

    def leg(prefetch):
        comm.destroy_process_group()
        zero = {"stage": 3, "stage3_param_persistence_threshold": 1000,
                "stage3_layer_prefetch": prefetch}
        cfg = make_ds_config(B, zero, "none", 1, {})
        cfg["comms_logger"] = {"enabled": True}
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        dt = _timed_leg(engine, data)
        stream = engine.analytic_streams().get("zero3_prefetch") or {}
        engine.destroy()
        return dt, stream

    dt_serial, _ = leg(False)
    dt_overlap, stream = leg(True)
    return _ab_result(
        "zero3_prefetch A/B (CPU-mesh validation, not a perf record; "
        "knob default-off pending on-chip A/B)",
        dt_serial, dt_overlap, stream.get("bytes_per_step", 0),
        extra={"slots": stream.get("slots"),
               "passes": stream.get("passes")},
    )


def ckpt_ab_mode() -> bool:
    """BENCH_CKPT_AB=1 → CPU-mesh A/B of the async checkpoint snapshot
    pipeline (checkpoint.async_save — runtime/ckpt)."""
    return _force_cpu_mesh_mode("BENCH_CKPT_AB")


def run_ckpt_ab():
    """Sync vs async ``save_checkpoint`` every K steps on the CPU mesh.
    Prints ONE JSON line with the no-save baseline step time, both
    saving legs' step times (the async fence should sit within noise of
    the baseline while the sync leg eats the full serialize+write on
    the step) and the analytic ckpt_snapshot MiB/step. Same CPU-mesh
    validation protocol as run_moe_a2a_ab — no perf record is banked;
    exactness of the async path is tests/test_ckpt.py's job."""
    import shutil
    import tempfile

    import jax

    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import llama

    B, S, K, N = 8, 128, 2, 6
    model = llama(
        "llama-tiny", vocab_size=512, max_seq_len=S, hidden_size=128,
        num_layers=4, num_heads=8, num_kv_heads=4, head_dim=16,
        intermediate_size=512,
    )
    data = {
        "input_ids": np.random.RandomState(0).randint(0, 512, size=(B, S))
    }

    def leg(save, async_save):
        comm.destroy_process_group()
        zero = {"stage": 3, "stage3_param_persistence_threshold": 1000}
        cfg = make_ds_config(B, zero, "none", 1, {})
        cfg["checkpoint"] = {
            "async_save": async_save,
            "save_interval_steps": K if save else 0,
            "keep_last": 2,
            "on_preempt": "none",
        }
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        save_dir = tempfile.mkdtemp(prefix="dstpu_ckpt_ab_")
        engine.train_batch(batch=data)  # compile
        t0 = time.perf_counter()
        for i in range(N):
            engine.train_batch(batch=data)
            if save and (i + 1) % K == 0:
                engine.save_checkpoint(save_dir)
        jax.block_until_ready(engine.state.params)
        dt = (time.perf_counter() - t0) / N
        stream = engine.analytic_streams().get("ckpt_snapshot") or {}
        engine.destroy()  # drains the background writer
        shutil.rmtree(save_dir, ignore_errors=True)
        return dt, stream

    dt_base, _ = leg(False, False)
    dt_sync, _ = leg(True, False)
    dt_async, stream = leg(True, True)
    return _ab_result(
        "ckpt async-save A/B (CPU-mesh validation, not a perf record)",
        dt_sync, dt_async, stream.get("bytes_per_step", 0),
        extra={
            "step_s_nosave": round(dt_base, 4),
            "snapshot_mib": round(
                stream.get("snapshot_bytes", 0) / 2**20, 3
            ),
            "save_interval_steps": K,
        },
    )


# Campaign-callable A/B legs: each runs its own CPU-mesh serial-vs-variant
# measurement and RETURNS the JSON-line dict it prints, so autoplan
# --campaign (and tests) can invoke the exact CLI protocol
# programmatically instead of scraping stdout. Keys match the campaign's
# knob-axis names in deepspeed_tpu/autotuning/campaign.py.
AB_LEGS = {
    "tp_overlap": run_tp_overlap_ab,
    "moe_a2a": run_moe_a2a_ab,
    "qgz_wires": run_qgz_ab,
    "z3_prefetch": run_z3_prefetch_ab,
    "ckpt_async": run_ckpt_ab,
}


def model_tag() -> str:
    """BENCH_MODEL selects the bench model size: "410m" (default) or
    "1b" — the ZeRO-3 + pinned-host-offload leg BASELINE.json's "7B-70B"
    metric line demands at least one datapoint toward."""
    return os.environ.get("BENCH_MODEL", "410m").lower()


def bench_dims(smoke: bool):
    """(B, S) of the bench batch, computable without touching jax — the
    sweep parent needs the grid geometry while the model only ever
    compiles inside per-point child processes.

    BENCH_SEQ overrides the sequence length (long-context variant for the
    watcher's 8k leg); the global batch shrinks to hold the token count
    at the default 16384/step so records stay comparable."""
    if smoke:
        return (4, 256)
    seq = int(os.environ.get("BENCH_SEQ", 2048))
    return (max(16384 // seq, 1), seq)


def bench_model(smoke: bool, tag: str = None):
    """The benchmark model: ONE definition shared by bench.py, the
    operator sweep (tools/sweep_train.py) and the shardlint gate
    (tools/shardlint.py --all-examples) so "best sweep config" and "the
    linted leg" always refer to the model the bench reports.

    head_dim=128 matches the MXU lane width (hd=64 runs the attention
    matmuls at half MXU utilization: measured 1.6x slower end-to-end)."""
    from deepspeed_tpu.models import llama

    B, S = bench_dims(smoke)
    if tag is None:
        tag = model_tag()
    if not smoke and tag == "1b":
        # ~1.4B params: bf16 weights+grads ~5.6 GB fit the 16 GB v5e, the
        # fp32 adam m/v + master (~17 GB) do NOT — precisely the shape
        # ZeRO-3 + pinned_host optimizer offload exists for
        model = llama(
            "llama3-1b",
            vocab_size=32768,
            max_seq_len=S,
            hidden_size=2048,
            num_layers=22,
            num_heads=16,
            num_kv_heads=8,
            head_dim=128,
            intermediate_size=8192,
        )
    else:
        model = llama(
            "llama-tiny",
            vocab_size=1024 if smoke else 32768,
            max_seq_len=S,
            hidden_size=128 if smoke else 1024,
            num_layers=2 if smoke else 24,
            num_heads=8,
            num_kv_heads=4,
            head_dim=16 if smoke else 128,
            intermediate_size=512 if smoke else 4096,
        )
    return model, B, S


def bench_model_and_data(smoke: bool):
    """(model, data, B, S) — bench_model plus the fixed random batch."""
    model, B, S = bench_model(smoke)
    data = {
        "input_ids": np.random.RandomState(0).randint(
            0, model.config.vocab_size, size=(B, S)
        )
    }
    return model, data, B, S


def make_ds_config(B, zero, pol, micro, tk, tp=None):
    """ONE config builder for the ladder, the offload A/B rebuild AND the
    shardlint bench legs — separate inline dicts would silently drift
    apart as keys are added. ``tp`` optionally adds a tensor_parallel
    section (the overlap A/B and its shardlint leg)."""
    cfg = {
        "train_batch_size": B,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": zero,
        "gradient_clipping": 1.0,
        "steps_per_print": 1000,
        "activation_checkpointing": {"policy": pol},
        "tpu_kernels": tk,
    }
    if tp:
        cfg["tensor_parallel"] = tp
    return cfg


def overlap_tp_section(tp_size: int = 2, *, bidirectional: bool = True,
                       chunks: int = 2, quantized_hops: bool = False):
    """The tensor_parallel section the overlap A/B and shardlint legs
    share (decomposed collective matmul; parallel/tensor_overlap.py)."""
    return {
        "tp_size": tp_size,
        "overlap_comm": {
            "enabled": True,
            "chunks": chunks,
            "bidirectional": bidirectional,
            "quantized_hops": quantized_hops,
        },
    }


def moe_overlap_section(ep_size: int = 2, *, chunks: int = 2,
                        bidirectional: bool = True):
    """The moe section the a2a-overlap A/B and shardlint legs share
    (decomposed MoE all-to-all; parallel/a2a_overlap.py)."""
    return {
        "enabled": True,
        "ep_size": ep_size,
        "num_experts": 4,
        "overlap_a2a": {
            "enabled": True,
            "chunks": chunks,
            "bidirectional": bidirectional,
        },
    }


def lint_targets(dp: int):
    """(name, model, ds_config) for the bench legs shardlint gates (the
    acceptance surface of ISSUE 2): the 410m leg and the 1.5B ZeRO-3 +
    pinned-host-offload leg, serial and double-buffered, plus the ISSUE-10
    overlap legs (decomposed MoE a2a on an ep mesh; stage-3 one-layer
    prefetch) whose declared streams rule R8 must statically confirm fit
    the compute window. Models are config shells only — shardlint traces
    them abstractly, nothing is materialized, so the 1.4B leg lints in
    seconds on CPU."""
    from deepspeed_tpu.models import mixtral

    model_410m, B, _S = bench_model(smoke=False, tag="410m")
    model_1b, _B1, _S1 = bench_model(smoke=False, tag="1b")
    B = -(-B // dp) * dp  # same dp-divisibility round-up as main()
    micro = max(B // dp, 1)
    tiles = {"flash_block_q": 512, "flash_block_k": 1024}
    offload = {"stage": 3, "offload_optimizer": {"device": "cpu"},
               "offload_param": {"device": "cpu"}}
    moe_model = mixtral(
        "mixtral-tiny", vocab_size=2048, max_seq_len=256, num_layers=4,
        num_experts=4,
    )
    # the moe leg shapes its own batch: the lint mesh splits the 8
    # devices dp=4 × ep=2, so 16 = micro 2 × dp 4 × accum 2
    moe_cfg = make_ds_config(16, {"stage": 1}, "none", 2, {})
    moe_cfg["moe"] = moe_overlap_section()
    z3_cfg = make_ds_config(
        B,
        {"stage": 3, "stage3_param_persistence_threshold": 10**5,
         "stage3_layer_prefetch": True},
        "none", micro, {},
    )
    return [
        ("bench-410m", model_410m,
         make_ds_config(B, {"stage": 0}, "none", micro, {})),
        ("bench-410m-tp-overlap", model_410m,
         make_ds_config(B, {"stage": 0}, "none", micro, {},
                        tp=overlap_tp_section())),
        ("bench-moe-a2a", moe_model, moe_cfg),
        ("bench-410m-z3-prefetch", model_410m, z3_cfg),
        # the 1.5B pair stays LAST: the lint speed budget test times the
        # biggest target via lint_targets()[-1]
        ("bench-1b-offload", model_1b,
         make_ds_config(B, dict(offload), "dots_flash", 1, tiles)),
        ("bench-1b-offload-db", model_1b,
         make_ds_config(B, dict(offload, offload_double_buffer=True),
                        "dots_flash", 1, tiles)),
    ]


def autotune_rung_targets(dp: int):
    """(name, model, ds_config) for representative autotuner ladder
    rungs, appended to ``shardlint --all-examples`` (ISSUE 7): the
    planner-driven search measures only statically-clean rungs, so the
    rungs themselves must stay lintable. Two rungs that differ from the
    bench legs already gated: a mid-ladder ZeRO-2 remat rung and the
    deepest ladder rung (stage 3 + cpu offload at max remat, the phase-0
    escalation endpoint)."""
    model_410m, B, _S = bench_model(smoke=False, tag="410m")
    B = -(-B // dp) * dp
    micro = max(B // dp, 1)
    return [
        ("autotune-rung-z2-dots_flash", model_410m,
         make_ds_config(B, {"stage": 2}, "dots_flash", micro, {})),
        ("autotune-rung-z3off-full", model_410m,
         make_ds_config(B, {"stage": 3,
                            "offload_optimizer": {"device": "cpu"}},
                        "full", 1, {})),
    ]


def time_chained_steps(engine, data, chain: int = 5, trials: int = 3) -> float:
    """Median per-step seconds over chained-dispatch trials (one compile,
    one readback per trial — the steady-state shape the records compare)."""
    import time as _time

    staged = engine.prepare_batch(data)
    engine.train_batch_chain(batch=staged, steps=chain)  # compile the chain
    float(engine.state.step)  # settle before the timed region
    samples = []
    for _ in range(trials):
        t0 = _time.perf_counter()
        engine.train_batch_chain(batch=staged, steps=chain)
        # force a host read of the new state so the steps are actually done
        float(engine.state.step)
        samples.append((_time.perf_counter() - t0) / chain)
    return float(np.median(samples))  # median: the host's clock is noisy


def offload_report(engine, step_s: float):
    """Offload-stream accounting for the bucketed ZeRO-offload leg: bytes
    streamed per step, in-flight buffer bytes, and the DMA wall estimate at
    the host-link bandwidth (BENCH_HOST_BW_GBS, GB/s) — the denominator of
    the overlap ratio the A/B computes. None when nothing streams."""
    off = getattr(engine, "offload_stream", None)
    if not off:
        return None
    bw = float(os.environ.get("BENCH_HOST_BW_GBS", 32)) * 1e9  # bytes/s
    total = off["bytes_in"] + off["bytes_out"]
    # a zero/negative bandwidth override (or an empty stream) must not
    # kill the bench on its accounting line; 0s DMA reads as "nothing to
    # hide" downstream (offload_overlap_ratio guards the same way)
    dma_s = total / bw if bw > 0 else 0.0
    return {
        "gib_per_step": round(total / 2**30, 2),
        "in_flight_mib": round(off["slots"] * off["slot_bytes"] / 2**20, 1),
        "double_buffer": bool(off["double_buffer"]),
        "est_dma_s": round(dma_s, 4),
        # DMA wall as a fraction of the measured step — serial measured
        # ~43% at 1.5B (docs/xprof_r5_1b_offload.md)
        "est_dma_frac_of_step": round(min(dma_s / max(step_s, 1e-9), 1.0), 4),
    }


def plan_summary(engine, name: str, measured_step_s=None,
                 bank_drift=True):
    """The analysis/cost planner's budget for the running engine — same
    table `tools/shardplan.py` and `shardlint --report` print, so every
    BENCH run banks the predicted-vs-measured step pair (the planner's
    roofline vs the wall clock) into the persistent drift ledger
    (perf/drift.jsonl; analysis/cost/drift.py). Systematic drift
    surfaces here as a recalibration suggestion for cost/hardware.py.
    Best-effort: a bench number must never die on its accounting line."""
    try:
        from deepspeed_tpu.analysis import format_plan_table, plan_engine

        plan = plan_engine(engine, source=name)
        print(format_plan_table([plan]), file=sys.stderr)
        out = {
            "est_step_s": round(plan.est_step_s, 4),
            "peak_hbm_gib": round(plan.peak_hbm_bytes / 2**30, 2),
            "ici_gib_per_step": round(
                sum(plan.ici_bytes.values()) / 2**30, 3
            ),
        }
        if measured_step_s:
            out["vs_measured"] = round(plan.est_step_s / measured_step_s, 4)
        if measured_step_s and bank_drift:
            try:
                from deepspeed_tpu.analysis.cost import drift

                ledger = drift.DriftLedger(
                    os.path.join(REPO_DIR, "perf", "drift.jsonl")
                )
                entry = drift.make_entry(plan, measured_step_s, source=name)
                ledger.append(entry)
                # the ONE drifted-pair predicate (shared with the ledger
                # gate and the healthwatch live alarm — ISSUE 11)
                verdict = drift.check_pair(
                    None, None, plan.hardware.gen, ratio=entry["ratio"]
                )
                out["drift"] = {
                    "ratio": entry["ratio"],
                    "band": [round(b, 4) for b in verdict["band"]],
                    "ok": verdict["ok"],
                }
                recal = drift.recalibration_suggestion(
                    ledger.load(gen=plan.hardware.gen)
                )
                if recal:
                    out["drift"]["recalibration"] = recal
                    print(f"bench: {recal}", file=sys.stderr)
            except Exception as e:  # noqa: BLE001 — ledger is evidence,
                # never a reason to lose the bench number
                print(f"bench: drift ledger skipped: {e}", file=sys.stderr)
        return out
    except Exception as e:  # noqa: BLE001
        print(f"bench: plan_summary failed: "
              f"{(str(e).splitlines() or [repr(e)])[0][:160]}",
              file=sys.stderr)
        return None


def trace_phase_table(engine, data, tag: str):
    """steptrace phase breakdown for the bench leg (ISSUE 8 satellite):
    runs AFTER the timed measurement — the span fences (block_until_ready
    at span close) would otherwise serialize async dispatch and perturb
    the banked number — traces two steps, exports the Chrome trace next
    to the drift ledger (perf/trace_<tag>.json) and prints the per-phase
    table beside the plan table. Best-effort: a bench number must never
    die on its accounting line. Returns the export path or None."""
    try:
        tr = engine.enable_tracing()
        for _ in range(2):
            engine.train_batch(batch=data)
        os.makedirs(os.path.join(REPO_DIR, "perf"), exist_ok=True)
        path = engine.trace_export(
            os.path.join(REPO_DIR, "perf", f"trace_{tag}.json")
        )
        print(tr.phase_table(prefix="train/"), file=sys.stderr)
        print(f"bench: steptrace trace -> {path} "
              f"(tools/trace_report.py)", file=sys.stderr)
        phases = {
            name: round(tr.mean_dur(name), 4)
            for name in sorted({s["name"] for s in tr.spans})
            if name.startswith("train/")
        }
        return {"trace": path, "phase_mean_s": phases}
    except Exception as e:  # noqa: BLE001
        print(f"bench: steptrace phase table skipped: "
              f"{(str(e).splitlines() or [repr(e)])[0][:160]}",
              file=sys.stderr)
        return None


def healthwatch_goodput(engine, data, predicted_step_s=None):
    """Goodput-accounting column for the BENCH record (ISSUE 11): enable
    healthwatch post-measurement (its device-scalar taps would otherwise
    perturb the banked number), run 2 watched steps, report the bucket
    split + running goodput fraction — and, when the plan table already
    priced this engine, arm the live drift alarm with its prediction so
    the plan_drift watchdog exercises end-to-end. Best-effort: a bench
    number must never die on its accounting line."""
    try:
        # plan_drift must actually evaluate inside this 2-step window:
        # its default min_samples (4) would silently skip it
        hw = engine.enable_healthwatch(
            install_signal_handler=False,
            rules={"plan_drift": {"min_samples": 2, "window": 2}},
        )
        if predicted_step_s:
            from deepspeed_tpu.analysis.cost import HardwareModel

            hw.set_prediction(predicted_step_s, HardwareModel.detect().gen)
        for _ in range(2):
            engine.train_batch(batch=data)
        g = hw.goodput()
        print(
            f"bench: goodput {g['goodput_fraction']:.4f} over "
            f"{g['elapsed_s']:.2f}s — " + ", ".join(
                f"{k}={v:.3f}s" for k, v in g["buckets"].items()
            ),
            file=sys.stderr,
        )
        col = {"goodput": g["goodput_fraction"], "buckets": g["buckets"]}
        if hw.events:
            col["anomalies"] = [e["rule"] for e in hw.events]
        return col
    except Exception as e:  # noqa: BLE001
        print(f"bench: healthwatch goodput skipped: "
              f"{(str(e).splitlines() or [repr(e)])[0][:160]}",
              file=sys.stderr)
        return None


def load_sweep_seed(dp: int, B: int):
    """The committed sweep winner (SWEEP_BEST.json, written by
    tools/sweep_train.py) becomes the ladder's first rung — on the 16GB
    v5e the static ladder's top rungs are known-doomed OOM compiles."""
    try:
        with open(os.path.join(REPO_DIR, "SWEEP_BEST.json")) as f:
            rec = (json.load(f) or {}).get("best") or {}
        micro, pol = int(rec["micro_batch"]), str(rec["remat_policy"])
        if not (1 <= micro <= max(B // dp, 1)) or B % (micro * dp):
            return None  # stale sweep from another shape; ignore
        tk = {}
        if rec.get("flash_block_q") or rec.get("flash_block_k"):
            tk = {"flash_block_q": int(rec.get("flash_block_q", 0)),
                  "flash_block_k": int(rec.get("flash_block_k", 0))}
        if rec.get("flash_block_q_bwd") or rec.get("flash_block_k_bwd"):
            tk["flash_block_q_bwd"] = int(rec.get("flash_block_q_bwd", 0))
            tk["flash_block_k_bwd"] = int(rec.get("flash_block_k_bwd", 0))
        return (pol, micro, tk)
    except Exception:
        return None


def main():
    import jax

    if tp_overlap_ab_mode():
        return run_tp_overlap_ab()
    if moe_a2a_ab_mode():
        return run_moe_a2a_ab()
    if z3_prefetch_ab_mode():
        return run_z3_prefetch_ab()
    if qgz_ab_mode():
        return run_qgz_ab()
    if ckpt_ab_mode():
        return run_ckpt_ab()
    smoke = smoke_mode()
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import deepspeed_tpu
    model, data, B, S = bench_model_and_data(smoke)
    cfg = model.config

    # least-recompute config that fits HBM: "none" keeps device flops ==
    # model flops (honest MFU); the ladder degrades on OOM instead of dying.
    # Measured on the 16GB v5e: smaller micro-batch with zero recompute
    # beats full batch with attn_mlp recompute, so the ladder prefers
    # shrinking micro (grad-accum scan) before adding recompute.
    policy = os.environ.get("BENCH_REMAT", "")
    # per-device micro-batch bounds: the batch triangle requires
    # B == micro * accum * dp, so the largest valid micro is B // dp
    dp = max(len(jax.devices()), 1)
    if B % dp:
        # a BENCH_SEQ-shrunk batch must still divide the device count or
        # every ladder rung fails the batch triangle; regenerate the data
        # at the rounded-up size (same seed → same leading rows)
        B = -(-B // dp) * dp
        data = {
            "input_ids": np.random.RandomState(0).randint(
                0, model.config.vocab_size, size=(B, S)
            )
        }
    mb_full = max(B // dp, 1)
    mb_half = max(mb_full // 2, 1)
    kernels_on = {}  # engine defaults (flash + fused CE auto-on for TPU)
    conservative = {"fused_ce": False}  # plain dense-logits loss path
    big = not smoke and model_tag() == "1b"
    zero_section = (
        # fp32 master params AND adam m/v live in pinned host memory; the
        # bucketed per-layer update scan (runtime/bucketed_opt.py) streams
        # one layer of each through HBM per tick — the whole-tree update
        # OOM'd at 19.6G/15.7G. BENCH_OFFLOAD_DB=1 turns on the
        # double-buffered layer stream (offload_double_buffer knob);
        # BENCH_OFFLOAD_AB=1 additionally times the other setting and
        # reports the DMA-vs-compute overlap ratio.
        {"stage": 3, "offload_optimizer": {"device": "cpu"},
         "offload_param": {"device": "cpu"},
         "offload_double_buffer": bool(os.environ.get("BENCH_OFFLOAD_DB"))}
        if big
        else {"stage": 0}
    )
    seed = None if (policy or smoke or big) else load_sweep_seed(dp, B)
    if big:
        # fp32 optimizer state lives in pinned host memory; remat is
        # mandatory and micro shrinks until weights+grads+activations fit.
        # The 410m sweep's winning flash tiles transfer (same S, hd).
        tiles = {"flash_block_q": 512, "flash_block_k": 1024}
        # BENCH_MICRO pins the micro-batch for a single-rung probe
        # (diagnosing which big-model rung a compile crash is in)
        mb_pin = int(os.environ.get("BENCH_MICRO", 0))
        # measured ladder order (perf/bench_1b*.json): dots_flash@mb1 =
        # 4,609 tok/s > full@mb4 4,460 > full@mb8 4,319 > full@mb2 4,335.
        # Larger micro does NOT amortize the offload tax — the optimizer
        # update (and its ~24 GB host DMA) runs once per global step under
        # accumulation regardless. dots_flash at mb>=2 crashes the remote
        # compile helper at 1.5B shapes, so mb1 leads.
        ladder = (
            [(policy, mb_pin or mb_half, tiles)]
            if policy
            else [
                ("dots_flash", 1, tiles),
                ("full", max(mb_full // 2, 1), tiles),
                ("full", 1, kernels_on),
                ("full", 1, conservative),
            ]
        )
    elif policy:
        ladder = [(policy, mb_full, kernels_on)]
    else:
        ladder = [
            ("none", mb_full, kernels_on), ("dots_flash", mb_full, kernels_on),
            ("dots_flash", mb_half, kernels_on),
            ("dots_saveable", mb_half, kernels_on),
            ("attn_mlp", mb_full, kernels_on), ("full", mb_full, kernels_on),
            # last resort: heavy remat at reduced micro, then everything
            # conservative — a number must come out of this script
            ("attn_mlp", mb_half, kernels_on), ("full", mb_half, kernels_on),
            ("full", mb_half, conservative),
        ]
    if seed is not None:
        ladder = [seed] + [r for r in ladder if r[:2] != seed[:2]]
    if os.environ.get("BENCH_FUSED_ADAM"):
        # A/B knob for the optimizer elementwise tail (xprof r4: optax
        # update + clip ≈ 5% of step): same ladder, Pallas fused adam on
        ladder = [(pol, mb, {**tk, "fused_adam": True})
                  for pol, mb, tk in ladder]
    def ds_config(zero, pol, micro, tk):
        return make_ds_config(B, zero, pol, micro, tk)

    engine = None
    last_err = None
    for pol, micro, tk in ladder:
        try:
            engine, *_ = deepspeed_tpu.initialize(
                model=model, config=ds_config(zero_section, pol, micro, tk)
            )
            engine.train_batch(batch=data)  # compile
            policy = f"{pol}@mb{micro}" + (
                "" if tk.get("fused_ce", True) else "+safe"
            ) + ("+fadam" if tk.get("fused_adam") else "")
            break
        except Exception as e:  # noqa: BLE001 — any rung failure, try the next:
            # a missing BENCH record costs more than a degraded one; the
            # stderr note keeps the failure visible
            last_err = e
            first_line = (str(e).splitlines() or [repr(e)])[0]
            print(f"bench: rung ({pol}, mb{micro}) failed: {first_line[:160]}",
                  file=sys.stderr)
            if engine is not None:
                try:
                    engine.destroy()
                except Exception:
                    pass
            engine = None
            continue
    if engine is None:
        raise RuntimeError("no bench configuration ran") from last_err
    # The scanned chain (engine.train_batch_chain) compiles 5 steps into ONE
    # program — one dispatch, one readback per trial; per-step launch
    # overhead vanishes from the measurement (and from a real steady-state
    # training loop). The batch is staged on device ONCE: a real input
    # pipeline prefetches, it does not upload before each dispatch.
    dt = time_chained_steps(engine, data)
    offload = offload_report(engine, dt)
    # price the MEASURED engine before any A/B rebuild swaps it out.
    # Smoke runs skip the drift ledger: the tiny validation model is
    # dispatch-dominated, its ratio would only pollute the evidence.
    plan = plan_summary(engine, f"bench-{model_tag()}", measured_step_s=dt,
                        bank_drift=not smoke)
    # phase breakdown rides along with the plan table (traced steps run
    # after the timed window, so the fences cannot touch the record)
    steptrace_col = trace_phase_table(engine, data, model_tag())
    # goodput accounting + the live drift alarm ride the same
    # post-measurement window (ISSUE 11)
    health_col = healthwatch_goodput(
        engine, data,
        predicted_step_s=(plan or {}).get("est_step_s"),
    )
    if offload is not None and os.environ.get("BENCH_OFFLOAD_AB") and big:
        # A/B the double-buffer knob in the same window: rebuild the
        # engine (the 1.5B state doesn't fit twice) with the knob flipped
        # and report how much of the offload DMA the pipelined scan hides
        from deepspeed_tpu.profiling.comm_logger import CommsLogger

        db_first = bool(zero_section.get("offload_double_buffer"))
        engine.destroy()
        other_zero = dict(zero_section,
                          offload_double_buffer=not db_first)
        try:
            engine, *_ = deepspeed_tpu.initialize(
                model=model, config=ds_config(other_zero, pol, micro, tk)
            )
            engine.train_batch(batch=data)  # compile
            dt_other = time_chained_steps(engine, data)
        except Exception as e:  # noqa: BLE001 — the flipped setting may
            # OOM (double buffering costs an extra layer slice on an
            # already-tight leg); the A side's valid measurement must
            # still be banked
            offload["ab_error"] = (str(e).splitlines() or [repr(e)])[0][:160]
            print(f"bench: offload A/B flipped-knob rung failed: "
                  f"{offload['ab_error']}", file=sys.stderr)
        else:
            dt_serial, dt_db = (dt_other, dt) if db_first else (dt, dt_other)
            offload["step_s_serial"] = round(dt_serial, 4)
            offload["step_s_double_buffer"] = round(dt_db, 4)
            offload["overlap_ratio"] = round(
                CommsLogger.offload_overlap_ratio(
                    dt_serial, dt_db, offload["est_dma_s"]
                ), 4,
            )

    tokens_per_step = B * S
    tok_per_sec = tokens_per_step / dt
    n_params = model.num_params()
    attn_flops_per_token = 2 * 2 * cfg.num_layers * (S / 2) * cfg.num_heads * cfg.hd
    fwd_flops_per_token = 2 * n_params + attn_flops_per_token
    # fwd + bwd = 3x fwd MODEL flops (the standard MFU convention: remat
    # recompute is not useful work). With remat_policy "none" device flops
    # equal model flops; a degraded ladder policy runs more device flops
    # for the same MFU-counted work — the reported policy says which.
    model_flops = 3 * fwd_flops_per_token * tokens_per_step
    mfu = model_flops / dt / peak_flops_per_chip()

    # ---- one ratchet, one record file (VERDICT r4 #9) -----------------------
    # RECORDS.json (committed) holds the best *bench-verified* number per
    # comparability class; perf/history.jsonl (append-only) keeps every raw
    # measurement. The ratchet compares only within the class — seq8192 or
    # the 1b leg never report phantom regressions against the seq2048
    # record, and a sweep-only number can never become the baseline.
    cls = f"train_{model_tag()}_seq{S}" + (
        "_fadam" if os.environ.get("BENCH_FUSED_ADAM") else ""
    )
    baseline = None
    if not smoke:
        baseline = best_prior(cls)
    vs = tok_per_sec / baseline if baseline else 1.0
    if smoke:
        # CPU validation run: TPU-peak MFU and real-TPU priors are
        # meaningless here — don't feed a ratchet false regressions
        vs, mfu = 1.0, 0.0

    result = {
        "metric": (
            "SMOKE-MODE bench validation (not a perf record)"
            if smoke
            else (f"llama-{model_tag()} train tokens/sec/chip "
                  f"(bf16, seq{S}, MFU attached)")
        ),
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(vs, 4),
        "mfu": round(mfu, 4),
        "step_time_s": round(dt, 4),
        "params_m": round(n_params / 1e6, 1),
        "remat_policy": policy + (
            "+dbuf" if offload and offload["double_buffer"] else ""
        ),
    }
    if offload is not None:
        result["offload"] = offload
    if plan is not None:
        result["plan"] = plan
    if steptrace_col is not None:
        # the BENCH record's phase-breakdown column (ISSUE 8): per-phase
        # mean seconds from the traced post-measurement steps
        result["steptrace"] = steptrace_col
    if health_col is not None:
        # the goodput column (ISSUE 11): wall-clock bucket split +
        # running goodput fraction from the watched post-measurement
        # steps (see docs/observability.md "healthwatch")
        result["healthwatch"] = health_col
    if not smoke:
        note = bank_record(cls, result)
        if note:
            result["record_note"] = note
    print(json.dumps(result))


def best_prior(cls: str) -> float | None:
    """The ratchet baseline for a comparability class: the best verified
    record in RECORDS.json."""
    try:
        with open(os.path.join(REPO_DIR, "RECORDS.json")) as f:
            rec = (json.load(f) or {}).get(cls) or {}
    except (OSError, ValueError):
        return None
    v = rec.get("value")
    return float(v) if isinstance(v, (int, float)) else None


def bank_record(cls: str, result: dict) -> str:
    """Append the raw measurement to perf/history.jsonl and promote it to
    RECORDS.json only if it beats the class's standing verified record —
    a slower re-run can never silently displace a better number, and the
    displacement (either way) is logged in the history."""
    os.makedirs(os.path.join(REPO_DIR, "perf"), exist_ok=True)
    entry = {**result, "ts": round(time.time(), 1), "class": cls,
             "source": "bench"}
    with open(os.path.join(REPO_DIR, "perf", "history.jsonl"), "a") as f:
        f.write(json.dumps(entry) + "\n")
    path = os.path.join(REPO_DIR, "RECORDS.json")
    try:
        with open(path) as f:
            records = json.load(f) or {}
    except FileNotFoundError:
        records = {}
    except Exception as e:
        # an UNREADABLE file must not become an empty dict: the rewrite
        # below would wipe every other class's verified record. Preserve
        # the evidence and refuse the ratchet update (the measurement is
        # still in history.jsonl).
        return (f"RECORDS.json unreadable ({e}); record NOT banked — "
                "repair the file (raw measurement kept in history.jsonl)")
    prev = records.get(cls) or {}
    prev_v = prev.get("value")
    if isinstance(prev_v, (int, float)) and result["value"] <= prev_v:
        return (f"prior verified record stands: {prev_v} tok/s "
                f"({prev.get('remat_policy', '?')}, ts {prev.get('ts', '?')})")
    records[cls] = {
        k: result[k]
        for k in ("value", "unit", "mfu", "step_time_s", "params_m",
                  "remat_policy")
        if k in result
    }
    records[cls].update(ts=entry["ts"], verified=True, source="bench")
    # atomic replace: a kill mid-write must not truncate the record file
    # (a parse failure would silently reset every class's ratchet)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(records, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return ""


if __name__ == "__main__":
    main()
