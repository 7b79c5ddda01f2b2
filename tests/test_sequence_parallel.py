"""Sequence parallelism (SURVEY §2.3): ring attention == dense reference;
Ulysses engine loss parity with a dp-only run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import llama
from deepspeed_tpu.models.sharding import use_topology
from deepspeed_tpu.ops.attention import xla_attention
from deepspeed_tpu.parallel.sequence import (
    ring_attention,
    set_sp_mode,
    ulysses_attention,
)


def rand_qkv(B=2, S=32, H=4, KV=4, hd=8, seed=0):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, S, H, hd), jnp.float32)
    k = jnp.asarray(r.randn(B, S, KV, hd), jnp.float32)
    v = jnp.asarray(r.randn(B, S, KV, hd), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_ring_attention_matches_dense(kv_heads):
    q, k, v = rand_qkv(KV=kv_heads)
    topo = MeshTopology(dims=ParallelDims(sp=4, dp=2))
    ref = xla_attention(q, k, v, causal=True)
    got = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, causal=True, topo=topo)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_attention_non_causal():
    q, k, v = rand_qkv(seed=1)
    topo = MeshTopology(dims=ParallelDims(sp=8))
    ref = xla_attention(q, k, v, causal=False)
    got = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, causal=False, topo=topo)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_attention_segment_ids():
    q, k, v = rand_qkv(seed=2)
    r = np.random.RandomState(2)
    seg = jnp.asarray(np.cumsum(r.rand(2, 32) < 0.2, axis=1))
    topo = MeshTopology(dims=ParallelDims(sp=4, dp=2))
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    got = jax.jit(
        lambda a, b, c, s: ring_attention(a, b, c, causal=True, segment_ids=s, topo=topo)
    )(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ulysses_matches_dense():
    q, k, v = rand_qkv(seed=3)
    topo = MeshTopology(dims=ParallelDims(sp=4, dp=2))
    ref = xla_attention(q, k, v, causal=True)
    with use_topology(topo):
        got = jax.jit(lambda a, b, c: ulysses_attention(a, b, c, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def tiny_llama():
    return llama(
        "llama-tiny", vocab_size=128, max_seq_len=32, hidden_size=32,
        num_layers=2, num_heads=4, num_kv_heads=4, intermediate_size=64,
    )


@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_sp_engine_parity_with_dp(mode):
    """Same data/seed: sp=4 engine loss tracks the dp-only engine loss."""
    cfg = {
        "train_batch_size": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 100,
    }
    dense, *_ = deepspeed_tpu.initialize(
        model=tiny_llama(), config=dict(cfg),
        topology=MeshTopology(dims=ParallelDims(dp=2), devices=jax.devices()[:2]),
        rng=jax.random.PRNGKey(5),
    )
    sp_cfg = dict(cfg)
    sp_cfg["sequence_parallel"] = {"sp_size": 4, "mode": mode}
    sp_eng, *_ = deepspeed_tpu.initialize(
        model=tiny_llama(), config=sp_cfg,
        topology=MeshTopology(dims=ParallelDims(dp=2, sp=4)),
        rng=jax.random.PRNGKey(5),
    )
    r = np.random.RandomState(0)
    try:
        for i in range(2):
            batch = {"input_ids": r.randint(0, 128, size=(4, 32))}
            ld = float(dense.train_batch(batch=dict(batch)))
            ls = float(sp_eng.train_batch(batch=dict(batch)))
            assert abs(ld - ls) < 2e-3, f"step {i}: dense {ld} vs sp/{mode} {ls}"
    finally:
        set_sp_mode("ulysses")


def test_ring_attention_alibi():
    """ALiBi slopes applied from global positions inside the ring (r3: the
    ring path no longer falls back to ulysses for BLOOM-style models)."""
    from deepspeed_tpu.models.transformer import alibi_slopes

    q, k, v = rand_qkv(seed=3)
    slopes = jnp.asarray(alibi_slopes(4))
    topo = MeshTopology(dims=ParallelDims(sp=4, dp=2))
    ref = xla_attention(q, k, v, causal=True, alibi_slopes=slopes)
    got = jax.jit(
        lambda a, b, c: ring_attention(
            a, b, c, causal=True, alibi_slopes=slopes, topo=topo
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "kv_heads,dims,expect_ax",
    [
        # kv % (sp*tp) != 0 but kv % tp == 0 → tp-only shard, sp replicates
        (2, ParallelDims(sp=2, tp=2), "tp"),
        # tp=1: only the sp axis is live and kv % sp == 0 → sp shard
        (2, ParallelDims(dp=4, sp=2), "sp"),
        # kv=2 can't shard over sp=4 at all → fully replicated KV
        (2, ParallelDims(dp=2, sp=4), None),
        # MQA under sp*tp: nothing divides → replicated KV
        (1, ParallelDims(sp=2, tp=2), None),
    ],
)
def test_ulysses_gqa_small_kv_matches_dense(kv_heads, dims, expect_ax):
    """GQA with kv_heads < sp*tp: the KV constraint falls back to whatever
    axes divide (or replication) and results stay exact vs dense."""
    from deepspeed_tpu.models.sharding import use_topology
    from deepspeed_tpu.parallel.sequence import _kv_head_axes

    q, k, v = rand_qkv(KV=kv_heads, seed=7)
    topo = MeshTopology(dims=dims)
    ref = xla_attention(q, k, v, causal=True)
    with use_topology(topo):
        assert _kv_head_axes(kv_heads) == expect_ax
        got = jax.jit(
            lambda a, b, c: ulysses_attention(a, b, c, causal=True)
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
