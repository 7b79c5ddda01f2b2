"""Ring flash attention (SURVEY §2.3 long-context): the Pallas flash kernel
composed around the sp ring with global position offsets, vs the dense
single-device oracle — forward and grads, causal/GQA/ALiBi/segments."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.ops.attention import attention_impl, xla_attention
from deepspeed_tpu.parallel.sequence import ring_attention

B, S, HD = 1, 512, 64


def rand_qkv(H=4, KV=2, seed=0):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, S, H, HD), jnp.float32)
    k = jnp.asarray(r.randn(B, S, KV, HD), jnp.float32)
    v = jnp.asarray(r.randn(B, S, KV, HD), jnp.float32)
    return q, k, v


def ring_flash(q, k, v, topo, **kw):
    with attention_impl("flash"):
        return ring_attention(q, k, v, topo=topo, **kw)


@pytest.mark.parametrize("sp,causal", [(4, True), (4, False), (2, True)])
def test_ring_flash_matches_dense(sp, causal):
    q, k, v = rand_qkv()
    topo = MeshTopology(dims=ParallelDims(sp=sp, dp=8 // sp))
    ref = xla_attention(q, k, v, causal=causal)
    got = jax.jit(
        lambda a, b, c: ring_flash(a, b, c, topo, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_grads_match_dense():
    q, k, v = rand_qkv(seed=1)
    topo = MeshTopology(dims=ParallelDims(sp=4, dp=2))

    def loss_ring(q, k, v):
        return jnp.sum(ring_flash(q, k, v, topo, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name}",
        )


def test_ring_flash_alibi_global_positions():
    q, k, v = rand_qkv(seed=2)
    slopes = np.geomspace(1.0, 0.125, q.shape[2]).astype(np.float32)
    topo = MeshTopology(dims=ParallelDims(sp=4, dp=2))
    ref = xla_attention(q, k, v, causal=True, alibi_slopes=slopes)
    got = jax.jit(
        lambda a, b, c: ring_flash(a, b, c, topo, causal=True,
                                   alibi_slopes=slopes)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_segment_ids_cross_chunk():
    q, k, v = rand_qkv(seed=3)
    r = np.random.RandomState(3)
    # segments crossing the chunk boundaries: the visiting kv block's ids
    # differ from the local q block's ids
    seg = jnp.asarray(np.cumsum(r.rand(B, S) < 0.02, axis=1))
    topo = MeshTopology(dims=ParallelDims(sp=4, dp=2))
    ref = xla_attention(q, k, v, causal=True, segment_ids=seg)
    got = jax.jit(
        lambda a, b, c, s: ring_flash(a, b, c, topo, causal=True,
                                      segment_ids=s)
    )(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_small_chunks_keep_dense_ring():
    """S_loc below the kernel tile keeps the (still-correct) dense ring."""
    r = np.random.RandomState(4)
    q = jnp.asarray(r.randn(1, 64, 4, 64), jnp.float32)
    topo = MeshTopology(dims=ParallelDims(sp=8))
    ref = xla_attention(q, q, q, causal=True)
    got = jax.jit(lambda a: ring_flash(a, a, a, topo, causal=True))(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_flash_bwd_tiles_scope():
    """Scoped bwd tile overrides reach the ring path's dq/dkv kernels:
    sp=2 gives S_loc=256, so fwd tiles pinned at 128 and bwd tiles at 256
    genuinely differ — grads must match the default-tile run."""
    from deepspeed_tpu.ops.pallas.flash_attention import block_sizes_scope

    q, k, v = rand_qkv(seed=7)
    topo = MeshTopology(dims=ParallelDims(sp=2, dp=4))

    def loss(q, k, v):
        return jnp.sum(ring_flash(q, k, v, topo, causal=True) ** 2)

    g_base = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    with block_sizes_scope(128, 128, 256, 256):
        g_scoped = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for gb, gs, name in zip(g_base, g_scoped, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gb), np.asarray(gs), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name}",
        )
