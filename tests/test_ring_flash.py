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


@pytest.mark.parametrize("alibi", [False, True], ids=["plain", "alibi"])
def test_hop_offsets_cover_every_kind_of_tile(alibi):
    """One ring hop, by hand: 3 x 3 tiles of 128 with the queries 64
    positions ahead of the keys. Tile (0,0) is crossed mid-tile, (0,2) is
    invisible, (1,0) lies wholly below the diagonal — the offsets are traced,
    so the kernel places each tile itself. Forward (out, lse) and dq/dk/dv
    against the dense computation at global positions."""
    from deepspeed_tpu.models.transformer import alibi_slopes as make_slopes
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    Bh, H, S_loc, D, blk = 1, 2, 384, 64, 128
    qoff, koff = 64, 0
    r = np.random.RandomState(5)
    q, k, v, do = (jnp.asarray(r.randn(Bh, H, S_loc, D), jnp.float32)
                   for _ in range(4))
    slopes = jnp.asarray(make_slopes(H), jnp.float32) if alibi else None
    scale = 1.0 / D ** 0.5

    def sees(qi, ki):  # [blk, blk]: which queries of the tile see which keys
        return (qi * blk + qoff + np.arange(blk)[:, None]
                >= ki * blk + koff + np.arange(blk)[None, :])

    assert sees(0, 0).any() and not sees(0, 0).all()  # crossed mid-tile
    assert not sees(0, 2).any()                       # invisible
    assert sees(1, 0).all()                           # wholly below
    assert not fa._block_visible(0, 2, blk, blk, qoff, koff)

    def dense(q, k, v):
        qpos = qoff + jnp.arange(S_loc)[:, None]
        kpos = koff + jnp.arange(S_loc)[None, :]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if alibi:
            s = s - slopes[None, :, None, None] * jnp.abs(qpos - kpos)
        s = jnp.where(qpos >= kpos, s, fa.NEG_INF)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v), lse

    kw = dict(causal=True, scale=scale, block_q=blk, block_k=blk,
              interpret=True)

    @jax.jit
    def hop(q, k, v, do, offsets):
        out, lse = fa._flash_fwd(q, k, v, None, None, slopes, None, offsets,
                                 **kw)
        dq, dk, dv, _ = fa._flash_bwd(q, k, v, out, lse, do, None, None,
                                      slopes, None, offsets, **kw)
        return out, lse[..., 0], dq, dk, dv

    got = hop(q, k, v, do, jnp.asarray([[qoff, koff]], jnp.int32))
    (out_ref, lse_ref), vjp = jax.vjp(dense, q, k, v)
    want = (out_ref, lse_ref, *vjp((do, jnp.zeros_like(lse_ref))))
    for g, w, name in zip(got, want, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5, err_msg=name)
