"""Engine tests. Model: reference tests/unit/runtime/test_ds_initialize.py +
half_precision tests. The ZeRO oracle: all stages are the same optimizer, so
trajectories must match bitwise-close across stages."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as comm
from deepspeed_tpu.models import gpt2, llama

BASE_CFG = {
    "train_batch_size": 16,
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    "bf16": {"enabled": True},
    "gradient_clipping": 1.0,
    "steps_per_print": 100,
}


def _model():
    return gpt2("gpt2-tiny", vocab_size=128, max_seq_len=16)


def _data(n=16, seed=0):
    return {"input_ids": np.random.RandomState(seed).randint(0, 128, size=(n, 16))}


_TRAJECTORIES = {}  # the config's JSON -> the losses of its longest run


def _run_steps(cfg, steps=3, seed=0, model=None, vary_data=False):
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=model or _model(), config=dict(cfg), rng=jax.random.PRNGKey(42)
    )
    losses = []
    for i in range(steps):
        step_seed = seed + i if vary_data else seed
        losses.append(
            float(engine.train_batch(batch=_data(cfg["train_batch_size"], step_seed)))
        )
    if model is None and seed == 0 and not vary_data:
        key = json.dumps(cfg, sort_keys=True)
        if len(losses) > len(_TRAJECTORIES.get(key, ())):
            _TRAJECTORIES[key] = losses
    return losses, engine


def _trajectory(cfg, steps=3):
    """The first ``steps`` losses of ``_run_steps(cfg)``: the run is seeded
    (weights, batch), so where another test of the file has made it, its
    losses are these."""
    seen = _TRAJECTORIES.get(json.dumps(cfg, sort_keys=True), ())
    return seen[:steps] if len(seen) >= steps else _run_steps(cfg, steps)[0]


def test_initialize_returns_tuple(devices8):
    engine, opt, loader, sched = deepspeed_tpu.initialize(
        model=_model(), config=dict(BASE_CFG), training_data=_data(64)
    )
    assert engine is opt
    assert len(loader) == 4  # 64 / 16
    assert callable(sched)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_train(stage, devices8):
    cfg = dict(BASE_CFG, zero_optimization={"stage": stage})
    losses, engine = _run_steps(cfg, steps=4)
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(losses))


def test_zero_stage_equivalence_oracle(devices8):
    """ZeRO-0/1/2/3 are the same math — trajectories must agree."""
    trajectories = {}
    for stage in [0, 1, 2, 3]:
        cfg = dict(BASE_CFG, zero_optimization={"stage": stage})
        trajectories[stage] = _trajectory(cfg, steps=3)
    for stage in [1, 2, 3]:
        np.testing.assert_allclose(
            trajectories[0], trajectories[stage], rtol=2e-2,
            err_msg=f"stage {stage} diverged from DDP",
        )


def test_zero3_params_actually_sharded(devices8):
    cfg = dict(BASE_CFG, zero_optimization={"stage": 3, "stage3_param_persistence_threshold": 0})
    _, engine = _run_steps(cfg, steps=1)
    wq = engine.state.params["layers"]["attn"]["wq"]
    assert "dp" in str(wq.sharding.spec)


def test_grad_accumulation_invariance(devices8):
    """accum=1 vs accum=4 on the same global batch → same trajectory."""
    cfg1 = dict(BASE_CFG, train_batch_size=64, gradient_accumulation_steps=1)
    cfg4 = dict(BASE_CFG, train_batch_size=64, gradient_accumulation_steps=4)
    del cfg1["train_micro_batch_size_per_gpu"], cfg4["train_micro_batch_size_per_gpu"]
    l1, _ = _run_steps(cfg1, steps=3)
    l4, _ = _run_steps(cfg4, steps=3)
    np.testing.assert_allclose(l1, l4, rtol=2e-2)


def test_fp16_runs_with_loss_scaling(devices8):
    cfg = dict(BASE_CFG)
    cfg.pop("bf16")
    cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    losses, engine = _run_steps(cfg, steps=3)
    assert all(np.isfinite(losses))
    assert engine.loss_scale >= 1.0


def test_gradient_clipping_bounds_update(devices8):
    cfg = dict(BASE_CFG, gradient_clipping=1e-4)
    _, engine = _run_steps(cfg, steps=2)
    assert float(engine._metrics["grad_norm"]) >= 0


def test_imperative_forward_backward_step(devices8):
    cfg = dict(BASE_CFG, train_batch_size=32, gradient_accumulation_steps=2)
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(model=_model(), config=cfg)
    # 2 microbatches of 16 (= micro 2 * dp 8), update applied at the boundary
    mb = _data(16)
    loss0 = engine(mb)
    engine.backward(loss0)
    assert engine.step() is None  # not at boundary yet
    loss1 = engine(_data(16, seed=1))
    engine.backward(loss1)
    final = engine.step()
    assert final is not None
    assert engine.global_steps == 1


def test_eval_batch_no_state_change(devices8):
    _, engine = _run_steps(dict(BASE_CFG), steps=1)
    step_before = int(engine.state.step)
    loss = engine.eval_batch(batch=_data(16))
    assert np.isfinite(float(loss))
    assert int(engine.state.step) == step_before


def test_wrong_batch_size_raises(devices8):
    _, engine = _run_steps(dict(BASE_CFG), steps=1)
    with pytest.raises(ValueError, match="train_batch_size"):
        engine.train_batch(batch=_data(12))


def test_tp_engine_trains(devices8):
    cfg = dict(BASE_CFG, tensor_parallel={"tp_size": 2})
    losses, engine = _run_steps(cfg, steps=3)
    assert engine.topology.tp_size == 2
    assert losses[-1] < losses[0]
    wq = engine.state.params["layers"]["attn"]["wq"]
    assert "tp" in str(wq.sharding.spec)


def test_tp_matches_dp_trajectory(devices8):
    l_dp = _trajectory(dict(BASE_CFG), steps=3)
    l_tp = _trajectory(dict(BASE_CFG, tensor_parallel={"tp_size": 2}), steps=3)
    np.testing.assert_allclose(l_dp, l_tp, rtol=2e-2)


def test_hpz_fsdp_subaxis(devices8):
    cfg = dict(
        BASE_CFG,
        zero_optimization={
            "stage": 3,
            "zero_hpz_partition_size": 2,
            "stage3_param_persistence_threshold": 0,
        },
    )
    losses, engine = _run_steps(cfg, steps=2)
    assert engine.topology.fsdp_size == 2
    wq = engine.state.params["layers"]["attn"]["wq"]
    spec = str(wq.sharding.spec)
    assert "fsdp" in spec and "'dp'" not in spec  # params shard only on sub-axis
    assert losses[-1] < losses[0]


def test_initialize_from_args_namespace(devices8, tmp_path):
    """Reference CLI pattern: deepspeed.initialize(args) where
    args.deepspeed_config points at a ds_config.json file."""
    import argparse
    import json

    cfg_path = tmp_path / "ds_config.json"
    cfg_path.write_text(json.dumps(BASE_CFG))
    args = argparse.Namespace(deepspeed_config=str(cfg_path), local_rank=0)
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(args=args, model=_model())
    loss = engine.train_batch(batch=_data())
    assert np.isfinite(float(loss))


def test_engine_module_train_eval_parity_shims():
    """DeepSpeedEngine nn.Module-ish surface: module/train/eval/zero_grad."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    model = gpt2("gpt2-tiny", vocab_size=128, max_seq_len=32, hidden_size=32,
                 num_layers=1, num_heads=2, intermediate_size=64)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
    )
    assert engine.module is model
    assert engine.training
    assert engine.eval() is engine and not engine.training
    assert engine.train() is engine and engine.training
    engine.zero_grad()  # documented no-op


def test_prepare_batch_staged_matches_host_path(devices8):
    """prepare_batch pre-stages a batch on device; repeated train_batch
    calls skip the per-step upload and produce a bit-identical trajectory
    to the host-dict path (the bench/tuner steady-state fast path)."""
    cfg = dict(BASE_CFG, train_batch_size=16,
               train_micro_batch_size_per_gpu=1,
               gradient_accumulation_steps=2)
    comm.destroy_process_group()
    e1, *_ = deepspeed_tpu.initialize(
        model=_model(), config=dict(cfg), rng=jax.random.PRNGKey(42)
    )
    host_losses = [float(e1.train_batch(batch=_data(16))) for _ in range(3)]

    comm.destroy_process_group()
    e2, *_ = deepspeed_tpu.initialize(
        model=_model(), config=dict(cfg), rng=jax.random.PRNGKey(42)
    )
    staged = e2.prepare_batch(_data(16))
    # staged fields are device arrays in the [accum, micro, ...] layout;
    # re-preparing them is a pass-through (same objects, no copy)
    again = e2._prepare_batch(staged)
    for k in staged:
        assert again[k] is staged[k], k
    staged_losses = [float(e2.train_batch(batch=staged)) for _ in range(3)]
    np.testing.assert_allclose(host_losses, staged_losses, rtol=0, atol=0)


def test_train_batch_chain_bitmatches_sequential(devices8):
    """A scanned N-step chain (one dispatch) must be bit-identical to the
    same N steps dispatched one train_batch call at a time: the chain
    carries the rng and splits per step exactly as next_rng() does."""
    cfg = dict(BASE_CFG, train_batch_size=16,
               train_micro_batch_size_per_gpu=1,
               gradient_accumulation_steps=2)
    comm.destroy_process_group()
    e1, *_ = deepspeed_tpu.initialize(
        model=_model(), config=dict(cfg), rng=jax.random.PRNGKey(7)
    )
    seq_losses = [float(e1.train_batch(batch=_data(16))) for _ in range(4)]

    comm.destroy_process_group()
    e2, *_ = deepspeed_tpu.initialize(
        model=_model(), config=dict(cfg), rng=jax.random.PRNGKey(7)
    )
    chain_losses = np.asarray(e2.train_batch_chain(batch=_data(16), steps=4))
    assert chain_losses.shape == (4,)
    np.testing.assert_allclose(seq_losses, chain_losses, rtol=0, atol=0)
    assert e2.global_steps == e1.global_steps == 4
    # final states identical too (params trajectory, not just losses)
    for a, b in zip(jax.tree.leaves(e1.state.params),
                    jax.tree.leaves(e2.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # stacked metrics exposed; last step mirrors train_batch's metrics slot
    assert e2.last_chain_metrics["loss"].shape == (4,)


def test_train_batch_chain_data_iter_stacked(devices8):
    """data_iter chains upload N distinct batches as one stacked transfer;
    trajectory matches feeding the same batches sequentially."""
    cfg = dict(BASE_CFG, train_batch_size=16,
               train_micro_batch_size_per_gpu=1,
               gradient_accumulation_steps=2)
    batches = [_data(16, seed=s) for s in (1, 2, 3)]

    comm.destroy_process_group()
    e1, *_ = deepspeed_tpu.initialize(
        model=_model(), config=dict(cfg), rng=jax.random.PRNGKey(9)
    )
    seq = [float(e1.train_batch(batch=dict(b))) for b in batches]

    comm.destroy_process_group()
    e2, *_ = deepspeed_tpu.initialize(
        model=_model(), config=dict(cfg), rng=jax.random.PRNGKey(9)
    )
    chain = np.asarray(
        e2.train_batch_chain(data_iter=iter([dict(b) for b in batches]),
                             steps=3)
    )
    np.testing.assert_allclose(seq, chain, rtol=0, atol=0)


def test_train_batch_chain_falls_back_per_step(devices8):
    """Host-coupled features (random-LTD) disqualify the scanned chain;
    the call still works via per-step dispatch and returns stacked losses."""
    cfg = dict(BASE_CFG, train_batch_size=16,
               train_micro_batch_size_per_gpu=1,
               gradient_accumulation_steps=2,
               data_efficiency={
                   "enabled": True,
                   "data_routing": {
                       "enabled": True,
                       "random_ltd": {
                           "enabled": True,
                           "total_layer_num": 2,
                           "random_ltd_layer_num": 1,
                           "random_ltd_layer_id": [0],
                           "model_mask_name": None,
                           "model_type": "decoder",
                           "hidden_state_order": "batch_seq_dim",
                           "random_ltd_schedule": {
                               "min_value": 8,
                               "max_value": 16,
                               "schedule_type": "fixed_linear",
                               "schedule_config": {
                                   "require_steps": 10, "seq_per_step": 8,
                               },
                           },
                       },
                   },
               })
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=_model(), config=dict(cfg), rng=jax.random.PRNGKey(3)
    )
    if engine.random_ltd is None:
        pytest.skip("random-LTD config shape changed; fallback gate untested")
    losses = np.asarray(engine.train_batch_chain(batch=_data(16), steps=2))
    assert losses.shape == (2,)
    assert engine.last_chain_metrics is None  # fallback path
    assert engine.global_steps == 2


BUCKETED_BASE = {
    "train_batch_size": 8,
    "optimizer": {"type": "adamw",
                  "params": {"lr": 1e-2, "weight_decay": 0.01}},
    "gradient_clipping": 1.0,
}


def _bucketed_run(zero):
    """(losses, the parameter leaves after every step, the engine) of four
    steps on four batches under ``zero_optimization`` ``zero``."""
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=_model(), config={**BUCKETED_BASE, "zero_optimization": zero},
        rng=jax.random.PRNGKey(42))
    losses, leaves = [], []
    for i in range(4):
        losses.append(float(engine.train_batch(batch=_data(8, i))))
        leaves.append([np.asarray(a) for a in
                       jax.tree_util.tree_leaves(engine.state.params)])
    return losses, leaves, engine


@functools.cache
def _bucketed_oracle(name):
    """The two runs that three tests compare theirs against, made once: the
    plain whole-tree update and the serial bucketed scan. Their engines are
    read after, never stepped."""
    return _bucketed_run({
        "plain": {"stage": 3},
        "serial": {"stage": 3, "offload_optimizer": {"device": "cpu"}},
    }[name])


def test_bucketed_offload_update_matches_plain(devices8):
    """CPU-offloaded optimizer state steps per-layer inside a lax.scan
    (runtime/bucketed_opt.py, VERDICT r4 #2's enabler): the scanned update
    must be numerically identical to the whole-tree optax update, and the
    bucketed state must checkpoint/resume."""
    plain_losses, plain_at, plain = _bucketed_oracle("plain")
    # (its own engine: the checkpoint below steps it on)
    buck_losses, buck_at, buck = _bucketed_run(
        {"stage": 3, "offload_optimizer": {"device": "cpu"}})
    assert buck._bucketed_opt is not None
    assert plain._bucketed_opt is None
    np.testing.assert_allclose(plain_losses, buck_losses, rtol=1e-6)
    # params: atol covers degenerate near-zero leaves (k-bias) where
    # sqrt(v) ~ adam eps makes the update chaotic in summation order —
    # verified leaf-by-leaf: all diffs are O(1e-7) except such leaves
    for a, b in zip(plain_at[-1], buck_at[-1]):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    # the bucketed {"rest", "layers"} state round-trips a checkpoint
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        buck.save_checkpoint(d)
        l_next = float(buck.train_batch(batch=_data(8, seed=99)))
        buck.load_checkpoint(d)
        l_again = float(buck.train_batch(batch=_data(8, seed=99)))
    np.testing.assert_allclose(l_next, l_again, rtol=1e-6)


@pytest.mark.parametrize("buckets", ["in_order", "reordered"])
def test_bucketed_double_buffer_matches_serial_and_plain(devices8, buckets,
                                                         monkeypatch):
    """The double-buffered layer stream (zero_optimization.
    offload_double_buffer) runs the same per-layer math in the same order
    as the serial bucketed scan: the CPU-mesh oracle the knob is held to
    before it may ever default on.

    What is compared where (ROADMAP D10, as PR 32 measured it): the two
    scan bodies contract their FMAs differently, a 1-ulp difference that
    Adam amplifies from step 3 on where a gradient is near zero (the key
    bias, to which softmax is blind). So the parameters after steps 1 and 2
    and every loss are held at ``rtol=1e-6``, and the 4-step trajectory at
    the bound the plain whole-tree update is held to. ``reordered`` is the
    same comparison with the buckets streamed against the wrong layers'
    gradients, which it has to refuse."""
    from deepspeed_tpu.runtime.bucketed_opt import BucketedOptimizer

    plain_losses, plain_at, plain = _bucketed_oracle("plain")
    off = {"stage": 3, "offload_optimizer": {"device": "cpu"}}
    serial_losses, serial_at, serial = _bucketed_oracle("serial")
    if buckets == "reordered":
        in_order = BucketedOptimizer._scan_double_buffered
        monkeypatch.setattr(
            BucketedOptimizer, "_scan_double_buffered",
            lambda self, g_layers, *rest: in_order(
                self, jax.tree.map(lambda g: g[::-1], g_layers), *rest))
    db_losses, db_at, db = _bucketed_run(dict(off, offload_double_buffer=True))
    assert db._bucketed_opt is not None and db._bucketed_opt.double_buffer
    assert serial._bucketed_opt is not None and plain._bucketed_opt is None
    assert not serial._bucketed_opt.double_buffer
    # CPU meshes have no memory kinds: nothing streams, nothing recorded
    assert db.offload_stream is None

    def close(got, want, **tol):
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, **tol)

    checks = [
        # double-buffered == serial bucketed, leaf by leaf
        lambda: np.testing.assert_allclose(db_losses, serial_losses,
                                           rtol=1e-6),
        lambda: [close(db_at[step], serial_at[step], rtol=1e-6, atol=1e-7)
                 for step in (0, 1)],
        lambda: close(db_at[-1], serial_at[-1], rtol=1e-3, atol=1e-4),
        # == the plain whole-tree update at f32 tolerance
        lambda: np.testing.assert_allclose(db_losses, plain_losses,
                                           rtol=1e-6),
        lambda: close(db_at[-1], plain_at[-1], rtol=1e-3, atol=1e-4),
    ]
    for check in checks:
        if buckets == "reordered":  # every one of them sees it
            with pytest.raises(AssertionError):
                check()
        else:
            check()


def test_bucketed_survives_layer_dim_dp_sharded(devices8):
    """ADVICE r5 → ISSUE 2 fix: when L is the largest dp-divisible dim
    (tiny hidden sizes), add_data_axes shards the stacked leaves' dim 0.
    The PR-1 gate disabled bucketing for that shape; now _apply_update
    re-puts the scanned groups to their resting shardings after the layer
    scan, so bucketing stays ON, the trajectory matches the whole-tree
    update, and the chain's carry closure holds (shardlint R2 proves the
    same statically — tests/test_shardlint_suite.py)."""

    def _sharded_model():
        return gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16,
                    hidden_size=12, num_layers=8, num_heads=2,
                    intermediate_size=12)

    base = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    }
    zero = {"stage": 3, "stage3_param_persistence_threshold": 0}
    plain_losses, plain = _run_steps(
        {**base, "zero_optimization": dict(zero)},
        steps=3, vary_data=True, model=_sharded_model(),
    )
    off_losses, off = _run_steps(
        {**base, "zero_optimization": dict(
            zero, offload_optimizer={"device": "cpu"})},
        steps=3, vary_data=True, model=_sharded_model(),
    )
    # sanity: this config really produces a dim-0 (dp)-sharded stacked leaf
    assert any(
        tuple(spec) and tuple(spec)[0] is not None
        for spec in jax.tree_util.tree_leaves(
            off.param_specs["layers"],
            is_leaf=lambda x: hasattr(x, "index"),
        )
    )
    assert off._bucketed_opt is not None  # the gate is gone
    assert plain._bucketed_opt is None
    np.testing.assert_allclose(plain_losses, off_losses, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(plain.state.params),
                    jax.tree_util.tree_leaves(off.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
    # the closure in anger: a scanned 2-step chain must run AND return the
    # stacked leaves to their resting shardings
    off.train_batch_chain(batch=_data(8, seed=77), steps=2)
    for leaf, want in zip(
        jax.tree_util.tree_leaves(off.state.params["layers"]),
        jax.tree_util.tree_leaves(off.param_shardings["layers"]),
    ):
        assert leaf.sharding.spec == want.spec, (leaf.sharding, want)
    loss = float(off.train_batch(
        batch={"input_ids": np.random.RandomState(0).randint(
            0, 64, size=(8, 16))}))
    assert np.isfinite(loss)


def test_bucketed_step_with_placement_hooks_matches_plain(devices8):
    """The bucketed update with per-slice placement hooks installed (the
    TPU-offload configuration) is numerically identical to the hookless
    path CPU meshes take."""
    import optax

    from deepspeed_tpu.runtime.bucketed_opt import BucketedOptimizer

    r = np.random.RandomState(0)
    params = {
        "layers": {"w": jnp.asarray(r.randn(5, 8, 8), jnp.float32),
                   "b": jnp.asarray(r.randn(5, 8), jnp.float32)},
        "embed": jnp.asarray(r.randn(16, 8), jnp.float32),
    }
    grads = jax.tree.map(lambda x: jnp.asarray(
        np.random.RandomState(1).randn(*x.shape), jnp.float32), params)
    opt = BucketedOptimizer(optax.adamw(1e-2))
    st = jax.jit(opt.init)(params)
    ident = (lambda t: t, lambda t: t)
    p_scan, s_scan = jax.jit(opt.step)(grads, st, params)
    p_pipe, s_pipe = jax.jit(
        lambda g, s, p: opt.step(g, s, p, state_put=ident, param_put=ident)
    )(grads, st, params)
    for a, b in zip(jax.tree_util.tree_leaves((p_scan, s_scan)),
                    jax.tree_util.tree_leaves((p_pipe, s_pipe))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-7)


def test_bucketed_double_buffer_step_bitmatches_serial_scan():
    """Unit oracle for the software-pipelined step: with and without
    placement hooks, the two-slot rotating-buffer scan must produce
    exactly the serial scan's params and state (same math, same layer
    order — only the schedule differs)."""
    import optax

    from deepspeed_tpu.runtime.bucketed_opt import BucketedOptimizer

    r = np.random.RandomState(0)
    params = {
        "layers": {"w": jnp.asarray(r.randn(6, 8, 8), jnp.float32),
                   "b": jnp.asarray(r.randn(6, 8), jnp.float32)},
        "embed": jnp.asarray(r.randn(16, 8), jnp.float32),
    }
    grads = jax.tree.map(lambda x: jnp.asarray(
        np.random.RandomState(1).randn(*x.shape), jnp.float32), params)
    serial = BucketedOptimizer(optax.adamw(1e-2))
    pipelined = BucketedOptimizer(optax.adamw(1e-2), double_buffer=True)
    st = jax.jit(serial.init)(params)
    ident = (lambda t: t, lambda t: t)
    want = jax.jit(serial.step)(grads, st, params)
    for hooks in (None, ident):
        got = jax.jit(
            lambda g, s, p, h=hooks: pipelined.step(
                g, s, p, state_put=h, param_put=h
            )
        )(grads, st, params)
        for a, b in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
