"""Launcher, elasticity, curriculum, random-LTD, PLD (SURVEY §2.7)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.data_pipeline.curriculum_scheduler import CurriculumScheduler
from deepspeed_tpu.data_pipeline.random_ltd import (
    RandomLTDScheduler,
    gather_tokens,
    random_ltd_layer,
    sample_token_subset,
    scatter_tokens,
)
from deepspeed_tpu.elasticity import compute_elastic_config, get_compatible_gpus
from deepspeed_tpu.launcher.runner import (
    build_launch_env,
    build_ssh_command,
    main as launcher_main,
    parse_hostfile,
    parse_inclusion_exclusion,
)
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.progressive_layer_drop import (
    ProgressiveLayerDrop,
    layer_keep_probs,
)


# ---------------------------------------------------------------- launcher
def test_parse_hostfile():
    text = """
    # my cluster
    host1 slots=4
    host2 slots=8
    host3
    """
    res = parse_hostfile(text, is_text=True)
    assert res == {"host1": 4, "host2": 8, "host3": 1}
    with pytest.raises(ValueError):
        parse_hostfile("h slots=1\nh slots=2", is_text=True)


def test_include_exclude():
    res = {"a": 4, "b": 4, "c": 4}
    assert list(parse_inclusion_exclusion(res, include_str="a@c")) == ["a", "c"]
    assert list(parse_inclusion_exclusion(res, exclude_str="b")) == ["a", "c"]
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(res, include_str="zzz")
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(res, exclude_str="a@b@c")


def test_launch_env_and_ssh_command():
    env = build_launch_env("host1", 29500, 4, 2, base_env={"PYTHONPATH": "/x"})
    assert env["DSTPU_COORDINATOR"] == "host1:29500"
    assert env["DSTPU_PROCESS_ID"] == "2"
    cmd = build_ssh_command("host2", env, ["python", "train.py"])
    assert cmd[0] == "ssh" and "host2" in cmd
    assert "DSTPU_COORDINATOR=host1:29500" in cmd[-1]
    assert "python train.py" in cmd[-1]


def test_launcher_dry_run(tmp_path, capsys):
    hf = tmp_path / "hosts"
    hf.write_text("h1 slots=4\nh2 slots=4\n")
    rc = launcher_main(
        ["--hostfile", str(hf), "--dry_run", "train.py", "--flag"]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "[h1 rank 0]" in out and "[h2 rank 1]" in out


# --------------------------------------------------------------- elasticity
def test_get_compatible_gpus():
    gpus, batch = get_compatible_gpus(
        micro_batches=[2, 4], max_train_batch_size=64, min_gpus=1, max_gpus=16
    )
    assert batch <= 64
    for g in gpus:
        assert any(batch % (mb * g) == 0 for mb in [2, 4])


def test_compute_elastic_config():
    ds = {
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": 100,
            "micro_batch_sizes": [2, 4],
            "min_gpus": 1,
            "max_gpus": 8,
        }
    }
    batch, valid, micro = compute_elastic_config(ds, world_size=4)
    assert 4 in valid and batch % (micro * 4) == 0 and micro in (2, 4)
    with pytest.raises(ValueError):
        compute_elastic_config({"elasticity": {"enabled": False}})


# --------------------------------------------------------------- curriculum
def test_curriculum_schedules():
    cs = CurriculumScheduler(
        {
            "curriculum_type": "seqlen",
            "min_difficulty": 8,
            "max_difficulty": 64,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 100, "difficulty_step": 8},
        }
    )
    assert cs.get_difficulty(0) == 8
    assert cs.get_difficulty(100) == 64
    mid = cs.get_difficulty(50)
    assert 8 <= mid <= 64 and mid % 8 == 0

    disc = CurriculumScheduler(
        {
            "curriculum_type": "seqlen",
            "min_difficulty": 8,
            "max_difficulty": 64,
            "schedule_type": "fixed_discrete",
            "schedule_config": {"difficulty": [8, 32, 64], "max_step": [10, 20, 30]},
        }
    )
    assert disc.get_difficulty(5) == 8
    assert disc.get_difficulty(15) == 32
    assert disc.get_difficulty(999) == 64


def test_curriculum_engine_truncates_seq():
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=64, max_seq_len=32, hidden_size=32,
                   num_layers=2, num_heads=2),
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "data_efficiency": {
                "enabled": True,
                "data_sampling": {
                    "curriculum_learning": {
                        "enabled": True,
                        "curriculum_type": "seqlen",
                        "min_difficulty": 8,
                        "max_difficulty": 32,
                        "schedule_type": "fixed_linear",
                        "schedule_config": {"total_curriculum_step": 4,
                                            "difficulty_step": 8},
                    }
                },
            },
            "steps_per_print": 100,
        },
        topology=MeshTopology(dims=ParallelDims(dp=8)),
    )
    assert engine.curriculum is not None
    r = np.random.RandomState(0)
    for _ in range(5):
        loss = engine.train_batch(batch={"input_ids": r.randint(0, 64, size=(8, 32))})
        assert np.isfinite(float(loss))
    assert engine.curriculum.current_difficulty == 32


# --------------------------------------------------------------- random-LTD
def test_gather_scatter_roundtrip():
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 16, 4), jnp.float32)
    idx = sample_token_subset(jax.random.PRNGKey(0), 2, 16, 8)
    assert idx.shape == (2, 8)
    # sorted, unique
    assert all(np.all(np.diff(np.asarray(idx)[b]) > 0) for b in range(2))
    kept = gather_tokens(x, idx)
    back = scatter_tokens(x, kept, idx)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_random_ltd_layer_identity_for_dropped():
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(2, 16, 4), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    out = random_ltd_layer(lambda xx, pp: xx * 2.0, x, pos, keep=8,
                           rng=jax.random.PRNGKey(1))
    doubled = np.isclose(np.asarray(out), 2 * np.asarray(x)).all(-1)
    same = np.isclose(np.asarray(out), np.asarray(x)).all(-1)
    assert doubled.sum() == 2 * 8  # exactly keep tokens processed per row
    assert (doubled | same).all()


def test_random_ltd_scheduler():
    class C:
        random_ltd_schedule = {"min_value": 64, "max_value": 512,
                               "total_layer_drop_step": 100, "seq_step": 64}
        total_layer_num = 12
        random_ltd_layer_id = [1, 2, 3]

    s = RandomLTDScheduler(C())
    assert s.get_seq_len(0) == 64
    assert s.get_seq_len(100) == 512
    assert s.get_seq_len(50) % 64 == 0


# ---------------------------------------------------------------------- PLD
def test_pld_theta_schedule():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    assert float(pld.get_theta(0)) == pytest.approx(1.0)
    assert float(pld.get_theta(10_000)) == pytest.approx(0.5, abs=1e-3)
    probs = layer_keep_probs(jnp.asarray(0.5), 4)
    np.testing.assert_allclose(np.asarray(probs), [1.0, 0.875, 0.75, 0.625])


def test_pld_engine_trains():
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16, hidden_size=32,
                   num_layers=4, num_heads=2),
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "progressive_layer_drop": {"enabled": True, "theta": 0.5,
                                       "gamma": 0.01},
            "steps_per_print": 100,
        },
        topology=MeshTopology(dims=ParallelDims(dp=8)),
    )
    assert engine.pld is not None
    r = np.random.RandomState(0)
    for _ in range(3):
        loss = engine.train_batch(batch={"input_ids": r.randint(0, 64, size=(8, 16))})
        assert np.isfinite(float(loss))


def test_wall_clock_breakdown_times_steps(devices8, caplog):
    """wall_clock_breakdown=True populates the engine's timer registry and
    logs a breakdown line at steps_per_print (r3: flag was parsed, unused)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import gpt2

    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16),
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "wall_clock_breakdown": True,
            "steps_per_print": 2,
        },
    )
    data = {"input_ids": np.random.RandomState(0).randint(0, 64, size=(8, 16))}
    for _ in range(3):  # step 2 logs + resets; step 3 leaves counts visible
        engine.train_batch(batch=data)
    names = set(engine.timers.timers)
    assert {"batch_prep", "step_dispatch", "step_device"} <= names
    assert engine.timers("step_device").count >= 1


def test_launcher_failure_propagation():
    """One dead rank must take the job down (reference pdsh-runner job
    control): the launcher terminates surviving hosts instead of hanging."""
    import subprocess
    import sys
    import time

    from deepspeed_tpu.launcher.runner import wait_and_propagate

    t0 = time.monotonic()
    procs = [
        subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"]),
        subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"]),
    ]
    rc = wait_and_propagate(procs, poll_s=0.1)
    assert rc == 3
    assert all(p.poll() is not None for p in procs)
    assert time.monotonic() - t0 < 30  # did not wait for the sleeper


def test_launcher_all_success():
    import subprocess
    import sys

    from deepspeed_tpu.launcher.runner import wait_and_propagate

    procs = [
        subprocess.Popen([sys.executable, "-c", "pass"]) for _ in range(2)
    ]
    assert wait_and_propagate(procs, poll_s=0.05) == 0


def test_zero_memory_estimators():
    """ZeRO stage memory math (reference: estimate_zero{2,3}_..._mem_needs):
    sharding divides exactly the states each stage shards."""
    from deepspeed_tpu.utils import (
        estimate_zero2_model_states_mem_needs,
        estimate_zero3_model_states_mem_needs,
        estimate_zero_model_states_mem_needs,
    )

    n, dp = 1_000_000, 8
    s0 = estimate_zero_model_states_mem_needs(n, stage=0, data_shards=dp)
    s1 = estimate_zero_model_states_mem_needs(n, stage=1, data_shards=dp)
    s2 = estimate_zero2_model_states_mem_needs(n, dp)
    s3 = estimate_zero3_model_states_mem_needs(n, dp)
    # stage 0: 2 + 4 + 12 bytes/param all resident
    assert s0["device_bytes"] == n * 18
    # stage 1 shards the 12B optimizer states
    assert s1["device_bytes"] == n * (2 + 4 + 12 / dp)
    # stage 2 also shards fp32 grads
    assert s2["device_bytes"] == n * (2 + 4 / dp + 12 / dp)
    # stage 3 shards everything
    assert abs(s3["device_bytes"] - n * 18 / dp) < 1
    # offload moves the sharded states to host
    s3o = estimate_zero3_model_states_mem_needs(
        n, dp, offload_optimizer=True, offload_params=True
    )
    assert s3o["host_bytes"] == s3o["host_gb"] * (1 << 30)
    assert s3o["device_bytes"] == n * 4 / dp  # only sharded grads stay


def test_see_memory_usage_runs():
    from deepspeed_tpu.utils import see_memory_usage

    out = see_memory_usage("unit-test", force=True)
    assert "bytes_in_use" in out and "host_rss" in out
    assert see_memory_usage("skipped", force=False) == {}


def test_memory_breakdown_config_wired(devices8, monkeypatch):
    """ds_config memory_breakdown must actually report (r1 advisor bug
    class: config parses then silently ignored)."""
    import deepspeed_tpu
    import deepspeed_tpu.utils.memory as mem
    from deepspeed_tpu.models import gpt2

    calls = []
    monkeypatch.setattr(
        mem, "see_memory_usage",
        lambda msg="", force=True: calls.append(msg) or {},
    )
    model = gpt2("gpt2-tiny", vocab_size=128, max_seq_len=32, hidden_size=32,
                 num_layers=1, num_heads=2, intermediate_size=64)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_batch_size": 8, "steps_per_print": 1,
                "memory_breakdown": True,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
    )
    assert any("init" in c for c in calls)
    engine.train_batch(
        batch={"input_ids": np.random.RandomState(0).randint(0, 128, size=(8, 32))}
    )
    assert any(c.startswith("step") for c in calls)


def test_checkpointing_user_api():
    """deepspeed.checkpointing parity: configure() + checkpoint(fn, *args)
    runs fn under the selected remat policy with identical values/grads."""
    import deepspeed_tpu
    from deepspeed_tpu import checkpointing

    w = jnp.asarray(np.random.RandomState(0).randn(16, 16).astype(np.float32))
    x = jnp.asarray(np.random.RandomState(1).randn(4, 16).astype(np.float32))

    def f(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    try:
        checkpointing.configure(policy="dots_saveable")
        val = checkpointing.checkpoint(f, w, x)
        np.testing.assert_allclose(float(val), float(f(w, x)), rtol=1e-6)
        g1 = jax.grad(lambda w: checkpointing.checkpoint(f, w, x))(w)
        g2 = jax.grad(lambda w: f(w, x))(w)
        # remat replays the saved-dots policy in the backward, so the grad
        # is FP-reassociated vs the plain path — atol floors the near-zero
        # elements whose relative error is meaningless
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-5, atol=1e-6)

        # ds_config + checkpoint_in_cpu routing
        checkpointing.configure(
            deepspeed_config={"train_batch_size": 8,
                              "activation_checkpointing": {"policy": "attn_mlp"}}
        )
        assert checkpointing._config["policy"] == "attn_mlp"
        # section default "none" must not make checkpoint() an identity
        checkpointing.configure(
            deepspeed_config={"train_batch_size": 8,
                              "activation_checkpointing": {}}
        )
        assert checkpointing._config["policy"] == "full"
        # reference-style cpu_checkpointing key routes to offload_host
        checkpointing.configure(
            deepspeed_config={
                "train_batch_size": 8,
                "activation_checkpointing": {"cpu_checkpointing": True},
            }
        )
        assert checkpointing._config["policy"] in ("offload_host", "full")
        checkpointing.configure(checkpoint_in_cpu=True)
        assert checkpointing._config["policy"] in ("offload_host", "full")
        import pytest as _pytest

        with _pytest.raises(KeyError):
            checkpointing.configure(policy="not-a-policy")
        # rng tracker stubs exist (Megatron-style call sites)
        with checkpointing.get_cuda_rng_tracker().fork():
            pass
        assert checkpointing.is_configured()
    finally:
        checkpointing.reset()
    assert not checkpointing.is_configured()


def test_throughput_timer_wired_into_engine(devices8, monkeypatch):
    """The engine tracks samples/sec and surfaces it in the step log
    (reference: ThroughputTimer in the step loop)."""
    import deepspeed_tpu
    import deepspeed_tpu.runtime.engine as eng_mod
    from deepspeed_tpu.models import gpt2

    lines = []
    monkeypatch.setattr(
        eng_mod, "log_dist", lambda msg, *a, **k: lines.append(msg)
    )
    model = gpt2("gpt2-tiny", vocab_size=128, max_seq_len=32, hidden_size=32,
                 num_layers=1, num_heads=2, intermediate_size=64)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_batch_size": 8, "steps_per_print": 3,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
    )
    batch = {"input_ids": np.random.RandomState(0).randint(0, 128, size=(8, 32))}
    for _ in range(6):
        engine.train_batch(batch=batch)
    assert engine.tput.step_count == 6
    assert engine.tput.avg_samples_per_sec > 0
    assert any("samples/sec=" in m for m in lines)  # step-6 log line


def test_get_accelerator_surface():
    """deepspeed.accelerator parity: device identity, memory stats,
    synchronize, functional rng seeding."""
    import jax

    from deepspeed_tpu import get_accelerator

    acc = get_accelerator()
    assert acc is get_accelerator()  # singleton
    assert acc.is_available() and acc.device_count() >= 1
    assert acc.device_name().lower() in ("cpu", "tpu")
    assert acc.device_name(0).endswith(":0")
    assert acc.communication_backend_name() == "xla"
    # memory stats are ints (0 on backends without allocator stats)
    assert isinstance(acc.memory_allocated(), int)
    assert acc.available_memory() >= 0
    acc.synchronize()  # must not raise
    key = acc.manual_seed(7)
    assert (jax.random.key_data(key) == jax.random.key_data(
        jax.random.PRNGKey(7))).all()
    x = jax.numpy.ones((2,))
    assert acc.on_accelerator(x) and not acc.on_accelerator([1, 2])
    assert acc.is_bf16_supported()


def test_accelerator_bad_index_raises():
    from deepspeed_tpu import get_accelerator

    acc = get_accelerator()
    with pytest.raises(ValueError, match="out of range"):
        acc.memory_allocated(acc.device_count() + 3)
    with pytest.raises(ValueError, match="out of range"):
        acc.synchronize(-1)
