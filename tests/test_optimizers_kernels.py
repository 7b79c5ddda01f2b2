"""Optimizers (incl. 1-bit family) + Pallas rmsnorm kernels
(SURVEY §2.1, §2.4). Kernels run interpret=True on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.config import OptimizerConfig
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.ops.onebit import scale_by_onebit_adam
from deepspeed_tpu.ops.pallas.rmsnorm import rmsnorm as pallas_rmsnorm
from deepspeed_tpu.runtime.lr_schedules import build_schedule
from deepspeed_tpu.runtime.optimizers import build_optimizer


def _opt_cfg(name, **params):
    cfg = OptimizerConfig.__new__(OptimizerConfig)
    cfg.type = name
    cfg.params = {"lr": 1e-3, **params}
    return cfg


@pytest.mark.parametrize(
    "name", ["adamw", "lion", "adagrad", "lamb", "sgd", "onebitadam",
             "zerooneadam", "onebitlamb"]
)
def test_all_optimizers_step(name):
    cfg = _opt_cfg(name, momentum=0.9, freeze_step=2)
    sched = build_schedule(None, {}, 1e-3)
    tx = build_optimizer(cfg, sched)
    params = {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}
    state = tx.init(params)
    for i in range(4):
        grads = jax.tree.map(lambda p: jnp.full_like(p, 0.1 * (i + 1)), params)
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    assert all(bool(jnp.isfinite(l).all()) for l in jax.tree_util.tree_leaves(params))
    assert float(params["w"][0, 0]) != 1.0  # moved


def test_onebit_adam_matches_adam_before_freeze():
    """Warmup phase is exact Adam (reference parity)."""
    onebit = scale_by_onebit_adam(freeze_step=1000)
    adam = optax.scale_by_adam()
    params = {"w": jnp.ones((8,))}
    s1, s2 = onebit.init(params), adam.init(params)
    r = np.random.RandomState(0)
    for _ in range(5):
        g = {"w": jnp.asarray(r.randn(8), jnp.float32)}
        u1, s1 = onebit.update(g, s1, params)
        u2, s2 = adam.update(g, s2, params)
        np.testing.assert_allclose(np.asarray(u1["w"]), np.asarray(u2["w"]),
                                   rtol=1e-5, atol=1e-6)


def test_onebit_adam_compressed_phase_freezes_variance():
    onebit = scale_by_onebit_adam(freeze_step=2)
    params = {"w": jnp.ones((8,))}
    s = onebit.init(params)
    r = np.random.RandomState(1)
    for _ in range(3):
        g = {"w": jnp.asarray(r.randn(8), jnp.float32)}
        _, s = onebit.update(g, s, params)
    nu_frozen = np.asarray(s.nu["w"])
    for _ in range(3):
        g = {"w": jnp.asarray(r.randn(8), jnp.float32)}
        u, s = onebit.update(g, s, params)
    np.testing.assert_array_equal(np.asarray(s.nu["w"]), nu_frozen)
    assert np.isfinite(np.asarray(u["w"])).all()


def test_onebit_engine_trains():
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16, hidden_size=32,
                   num_layers=2, num_heads=2),
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-3, "freeze_step": 2}},
            "zero_optimization": {"stage": 1},
            "steps_per_print": 100,
        },
        topology=MeshTopology(dims=ParallelDims(dp=8)),
    )
    r = np.random.RandomState(0)
    for _ in range(4):
        loss = engine.train_batch(
            batch={"input_ids": r.randint(0, 64, size=(8, 16))}
        )
        assert np.isfinite(float(loss))


def test_pallas_rmsnorm_uneven_rows():
    """Rows not a multiple of the block: padding must not corrupt dscale."""
    r = np.random.RandomState(4)
    x = jnp.asarray(r.randn(300, 128).astype(np.float32))  # 300 % 256 != 0
    scale = jnp.asarray(r.randn(128).astype(np.float32))
    ref = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale
    got = pallas_rmsnorm(x, scale, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda s: jnp.sum(pallas_rmsnorm(x, s, 1e-5) ** 2))(scale)
    g2 = jax.grad(lambda s: jnp.sum((x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * s) ** 2))(scale)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-4)


def test_pallas_rmsnorm_fwd_bwd():
    r = np.random.RandomState(3)
    x = jnp.asarray(r.randn(4, 16, 128).astype(np.float32))
    scale = jnp.asarray(r.randn(128).astype(np.float32))

    def ref_fn(x, s):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return jnp.sum((x * jax.lax.rsqrt(var + 1e-5) * s) ** 2)

    def pallas_fn(x, s):
        return jnp.sum(pallas_rmsnorm(x, s, 1e-5) ** 2)

    np.testing.assert_allclose(float(pallas_fn(x, scale)), float(ref_fn(x, scale)),
                               rtol=1e-5)
    g1 = jax.grad(pallas_fn, argnums=(0, 1))(x, scale)
    g2 = jax.grad(ref_fn, argnums=(0, 1))(x, scale)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_pallas_layernorm_fwd_bwd():
    from deepspeed_tpu.ops.pallas.layernorm import layernorm as pallas_layernorm

    r = np.random.RandomState(5)
    x = jnp.asarray(r.randn(4, 16, 128).astype(np.float32))
    scale = jnp.asarray(r.randn(128).astype(np.float32))
    bias = jnp.asarray(r.randn(128).astype(np.float32))

    def ref_fn(x, s, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return jnp.sum(((x - mean) * jax.lax.rsqrt(var + 1e-5) * s + b) ** 2)

    def pallas_fn(x, s, b):
        return jnp.sum(pallas_layernorm(x, s, b, 1e-5) ** 2)

    np.testing.assert_allclose(
        float(pallas_fn(x, scale, bias)), float(ref_fn(x, scale, bias)), rtol=1e-5
    )
    g1 = jax.grad(pallas_fn, argnums=(0, 1, 2))(x, scale, bias)
    g2 = jax.grad(ref_fn, argnums=(0, 1, 2))(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_pallas_layernorm_uneven_rows():
    """Rows not a multiple of the block: padding must not corrupt dscale/dbias."""
    from deepspeed_tpu.ops.pallas.layernorm import layernorm as pallas_layernorm

    r = np.random.RandomState(6)
    x = jnp.asarray(r.randn(300, 128).astype(np.float32))
    scale = jnp.asarray(r.randn(128).astype(np.float32))
    bias = jnp.asarray(r.randn(128).astype(np.float32))

    def ref(x, s, b):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-5) * s + b

    np.testing.assert_allclose(
        np.asarray(pallas_layernorm(x, scale, bias, 1e-5)),
        np.asarray(ref(x, scale, bias)), rtol=1e-5, atol=1e-5,
    )
    g1 = jax.grad(
        lambda s, b: jnp.sum(pallas_layernorm(x, s, b, 1e-5) ** 2), argnums=(0, 1)
    )(scale, bias)
    g2 = jax.grad(
        lambda s, b: jnp.sum(ref(x, s, b) ** 2), argnums=(0, 1)
    )(scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_bloom_trains_with_fused_layernorm():
    """BLOOM (layernorm family) trains with tpu_kernels.fused_rmsnorm on —
    the knob routes layernorm through the Pallas kernel via the same scope."""
    import deepspeed_tpu
    from deepspeed_tpu.models import bloom

    model = bloom(
        "bloom-tiny", vocab_size=256, max_seq_len=64, hidden_size=64,
        num_layers=2, num_heads=4, intermediate_size=128,
    )
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
            "zero_optimization": {"stage": 0},
            "tpu_kernels": {"fused_rmsnorm": True},
        },
    )
    batch = {"input_ids": np.random.RandomState(0).randint(0, 256, size=(8, 64))}
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
