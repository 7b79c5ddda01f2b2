"""Pipeline parallelism (SURVEY §2.3): pipelined output == sequential
output; pipeline engine training parity vs the plain engine.

Model: DeepSpeed tests/unit/runtime/pipe/ (pipeline output equivalence).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.models.transformer import apply_layer_stack, make_lm_batch
from deepspeed_tpu.runtime.pipe import (
    LayerSpec,
    PipelineModule,
    pipelined_stack,
)
from deepspeed_tpu.runtime.pipe.module import (
    partition_balanced,
    partition_uniform,
)


def tiny_model(num_layers=4):
    return gpt2(
        "gpt2-tiny",
        vocab_size=128,
        max_seq_len=16,
        hidden_size=32,
        num_layers=num_layers,
        num_heads=2,
    )


def test_partition_helpers():
    assert partition_uniform(8, 4) == [0, 2, 4, 6, 8]
    assert partition_uniform(7, 3) == [0, 3, 5, 7]
    # balanced: heavy head layer gets its own part
    bounds = partition_balanced([10, 1, 1, 1, 1], 2)
    assert bounds[0] == 0 and bounds[-1] == 5
    assert bounds[1] == 1  # the 10-weight layer alone


def test_pipelined_stack_matches_sequential():
    model = tiny_model(num_layers=4)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    topo = MeshTopology(dims=ParallelDims(pp=4, dp=2))

    M, mb, S = 4, 2, 8
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 128, size=(M, mb, S)))
    x = params["embed"]["tok"][ids]  # [M, mb, S, D]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (M, mb, S))

    # sequential reference: each microbatch through the full stack
    ref = []
    for m in range(M):
        y, _ = apply_layer_stack(
            cfg, params["layers"], x[m], positions[m], None, None, False, None
        )
        ref.append(y)
    ref = jnp.stack(ref)

    got, aux = jax.jit(
        lambda layers, xx, pp: pipelined_stack(
            cfg, layers, xx, pp, None, topo, False, None, None
        )
    )(params["layers"], x, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)
    assert float(aux) == 0.0


def test_pipelined_stack_grads_match_sequential():
    model = tiny_model(num_layers=2)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(1), dtype=jnp.float32)
    topo = MeshTopology(dims=ParallelDims(pp=2, dp=4))
    M, mb, S = 2, 2, 8
    r = np.random.RandomState(1)
    ids = jnp.asarray(r.randint(0, 128, size=(M, mb, S)))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (M, mb, S))

    def pipe_loss(layers):
        x = params["embed"]["tok"][ids]
        y, _ = pipelined_stack(cfg, layers, x, positions, None, topo, False, None, None)
        return jnp.sum(y**2)

    def seq_loss(layers):
        x = params["embed"]["tok"][ids]
        total = 0.0
        for m in range(M):
            y, _ = apply_layer_stack(cfg, layers, x[m], positions[m], None, None, False, None)
            total = total + jnp.sum(y**2)
        return total

    g_pipe = jax.jit(jax.grad(pipe_loss))(params["layers"])
    g_seq = jax.jit(jax.grad(seq_loss))(params["layers"])
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe), jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("tick_chunk", [2, 3])
def test_pipelined_stack_tick_chunk_exact(tick_chunk):
    """The 1f1b chunked-remat schedule (VERDICT r4 #6) is numerically the
    SAME program: outputs and grads match the unchunked scan bit-for-bit,
    including a chunk that doesn't divide the tick count."""
    model = tiny_model(num_layers=2)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(2), dtype=jnp.float32)
    topo = MeshTopology(dims=ParallelDims(pp=2, dp=4))
    M, mb, S = 4, 2, 8
    r = np.random.RandomState(2)
    ids = jnp.asarray(r.randint(0, 128, size=(M, mb, S)))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (M, mb, S))

    def loss(layers, chunk):
        x = params["embed"]["tok"][ids]
        y, _ = pipelined_stack(cfg, layers, x, positions, None, topo, True,
                               None, "full", tick_chunk=chunk)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    v0, g0 = jax.jit(jax.value_and_grad(lambda l: loss(l, None)))(
        params["layers"])
    v1, g1 = jax.jit(jax.value_and_grad(lambda l: loss(l, tick_chunk)))(
        params["layers"])
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_pipelined_stack_tick_chunk_bounds_stash_growth():
    """Memory contract of the 1f1b schedule: the per-microbatch growth of
    compiled temp memory (XLA's own accounting — where grad-of-scan stashes
    residuals) is strictly below the unchunked scan's (measured 2 boundary
    activations per tick: tools/pipe_memory.py, docs/pipe_memory.md)."""
    model = tiny_model(num_layers=2)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    topo = MeshTopology(dims=ParallelDims(pp=2, dp=4))
    mb, S, D = 2, 16, 32

    def temp_bytes(M, chunk):
        r = np.random.RandomState(0)
        x = jnp.asarray(r.randn(M, mb, S, D), jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (M, mb, S))

        def loss(layers):
            y, _ = pipelined_stack(cfg, layers, x, positions, None, topo,
                                   True, None, "full", tick_chunk=chunk)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        c = jax.jit(jax.grad(loss)).lower(params["layers"]).compile()
        return int(c.memory_analysis().temp_size_in_bytes)

    grow_plain = temp_bytes(24, None) - temp_bytes(8, None)
    grow_chunk = temp_bytes(24, 5) - temp_bytes(8, 3)
    assert grow_chunk < grow_plain, (grow_chunk, grow_plain)


def make_engines():
    """(pipeline pp=2 dp=2, dense dp=2) engines with identical init seeds."""
    base_cfg = {
        "train_batch_size": 8,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "gradient_clipping": 1.0,
        "steps_per_print": 100,
    }
    dense, *_ = deepspeed_tpu.initialize(
        model=tiny_model(),
        config=dict(base_cfg),
        topology=MeshTopology(dims=ParallelDims(dp=2), devices=jax.devices()[:2]),
        rng=jax.random.PRNGKey(3),
    )
    pipe_cfg = dict(base_cfg)
    pipe_cfg["pipeline"] = {"stages": 2}
    piped, *_ = deepspeed_tpu.initialize(
        model=tiny_model(),
        config=pipe_cfg,
        topology=MeshTopology(
            dims=ParallelDims(pp=2, dp=2), devices=jax.devices()[:4]
        ),
        rng=jax.random.PRNGKey(3),
    )
    return piped, dense


def test_pipeline_engine_parity_with_dense():
    piped, dense = make_engines()
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

    assert isinstance(piped, PipelineEngine)
    r = np.random.RandomState(0)
    for i in range(3):
        batch = {"input_ids": r.randint(0, 128, size=(8, 16))}
        if i == 1:
            # ragged padding: per-microbatch CE normalization must match the
            # dense engine's mean-over-microbatches semantics
            labels = np.asarray(
                make_lm_batch(jnp.asarray(batch["input_ids"]))["labels"]
            ).copy()
            labels[:3, 5:] = -100
            batch["labels"] = labels
        lp = float(piped.train_batch(batch=dict(batch)))
        ld = float(dense.train_batch(batch=dict(batch)))
        assert abs(lp - ld) < 2e-3, f"step {i}: pipeline {lp} vs dense {ld}"
    # params stay in lockstep after 3 optimizer steps
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(piped.state.params)),
        jax.tree_util.tree_leaves(jax.device_get(dense.state.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


def test_pipelined_stack_segment_ids():
    """Packed sequences: segment mask must ride the pipeline with its mb."""
    model = tiny_model(num_layers=2)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(2), dtype=jnp.float32)
    topo = MeshTopology(dims=ParallelDims(pp=2, dp=4))
    M, mb, S = 2, 2, 8
    r = np.random.RandomState(2)
    ids = jnp.asarray(r.randint(0, 128, size=(M, mb, S)))
    x = params["embed"]["tok"][ids]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (M, mb, S))
    seg = jnp.asarray(r.randint(0, 2, size=(M, mb, S)).cumsum(-1))

    ref = jnp.stack([
        apply_layer_stack(cfg, params["layers"], x[m], positions[m], seg[m],
                          None, False, None)[0]
        for m in range(M)
    ])
    got, _ = jax.jit(
        lambda layers, xx, pp, ss: pipelined_stack(
            cfg, layers, xx, pp, ss, topo, False, None, None
        )
    )(params["layers"], x, positions, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_pipeline_module_api():
    model = tiny_model()
    pm = PipelineModule(model=model, num_stages=2)
    assert pm.stage_owner(0) == 0 and pm.stage_owner(3) == 1
    topo = MeshTopology(dims=ParallelDims(pp=2, dp=4))
    specs = pm.partition_specs(topo)
    # stacked layer dim 0 picks up the pp axis
    assert specs["layers"]["attn"]["wq"][0] == "pp"
    assert "pp" not in (specs["embed"]["tok"][0] or ())

    with pytest.raises(ValueError):
        PipelineModule(model=tiny_model(3), num_stages=2)

    ls = LayerSpec(tiny_model, 4)
    pm2 = PipelineModule(layers=[ls], num_stages=2)
    assert pm2.config.num_layers == 4


def test_zero2_plus_pipeline_rejected():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError

    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig(
            {
                "train_batch_size": 8,
                "zero_optimization": {"stage": 2},
                "pipeline": {"stages": 2},
            }
        )


def test_pipeline_with_flash_kernel(devices8):
    """The flash kernel nests inside the pipeline's manual shard_map (r3:
    previously crashed with a mesh mismatch on real-TPU default config)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.comm import MeshTopology, ParallelDims
    from deepspeed_tpu.models import llama

    def run(flash):
        comm.destroy_process_group()
        topo = MeshTopology(ParallelDims(dp=2, pp=2, tp=2), devices=jax.devices())
        comm.set_topology(topo)
        model = llama(
            "llama-tiny", vocab_size=512, max_seq_len=128, hidden_size=64,
            num_layers=4, num_heads=4, num_kv_heads=4, intermediate_size=176,
        )
        engine, *_ = deepspeed_tpu.initialize(
            model=model, topology=topo,
            config={
                "train_batch_size": 8,
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
                "pipeline": {"stages": 2},
                "tpu_kernels": {"flash_attention": flash},
            },
            rng=jax.random.PRNGKey(0),
        )
        data = {
            "input_ids": np.random.RandomState(0).randint(0, 512, size=(8, 128))
        }
        return float(engine.train_batch(batch=data))

    l_flash = run(True)
    l_xla = run(False)
    assert abs(l_flash - l_xla) < 2e-3, (l_flash, l_xla)
