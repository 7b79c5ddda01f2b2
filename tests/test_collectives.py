"""Collective op tests on the virtual 8-device mesh.

Oracle: numpy reference reductions (model: reference tests/unit/comm/test_dist.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
import deepspeed_tpu.comm as comm
from deepspeed_tpu.comm import collectives as col
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims


def _mesh1d():
    return MeshTopology(ParallelDims()).mesh  # dp=8


def test_all_reduce_matches_numpy(devices8):
    mesh = _mesh1d()
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

    f = jax.shard_map(
        lambda a: col.all_reduce(a, "dp"), mesh=mesh, in_specs=P("dp"), out_specs=P("dp")
    )
    out = jax.jit(f)(x)
    expected = np.tile(np.asarray(x).sum(axis=0, keepdims=True), (8, 1))
    np.testing.assert_allclose(np.asarray(out), expected)


def test_reduce_scatter_all_gather_roundtrip(devices8):
    mesh = _mesh1d()
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)

    def body(a):
        # a: [1, 16] per shard. rs over flattened vector of 16 -> 2 each, ag back.
        v = a.reshape(16)
        shard = col.reduce_scatter(v, "dp")  # [2]
        full = col.all_gather(shard, "dp")  # [16]
        return full.reshape(1, 16)

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    expected = np.tile(np.asarray(x).sum(axis=0, keepdims=True), (8, 1))
    np.testing.assert_allclose(np.asarray(out), expected)


def test_broadcast_from_src(devices8):
    mesh = _mesh1d()
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) + 1.0

    out = jax.jit(
        jax.shard_map(
            lambda a: col.broadcast(a, "dp", src=3),
            mesh=mesh,
            in_specs=P("dp"),
            out_specs=P("dp"),
        )
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 4.0))


def test_all_to_all_transpose(devices8):
    mesh = _mesh1d()
    # Each rank holds a row of 8 blocks; all_to_all swaps block-owner axis.
    x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)

    def body(a):
        v = a.reshape(8)  # row i
        swapped = col.all_to_all(v, "dp", split_axis=0, concat_axis=0)  # column i
        return swapped.reshape(1, 8)

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).T)


def test_send_forward_shifts(devices8):
    mesh = _mesh1d()
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)

    out = jax.jit(
        jax.shard_map(
            lambda a: col.send_forward(a, "dp", 8),
            mesh=mesh,
            in_specs=P("dp"),
            out_specs=P("dp"),
        )
    )(x)
    expected = np.concatenate([[0.0], np.arange(7)]).reshape(8, 1)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_comm_hook_records_ops(devices8):
    mesh = _mesh1d()
    records = []
    col.register_comm_hook(lambda op, axis, nbytes: records.append((op, axis, nbytes)))
    x = jnp.ones((8, 4), jnp.float32)
    jax.jit(
        jax.shard_map(lambda a: col.all_reduce(a, "dp"), mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    )(x)
    assert ("all_reduce", "dp", 16) in records  # 1x4 f32 per-shard view


def test_comm_module_api(devices8):
    topo = comm.init_distributed(dims=ParallelDims(tp=2))
    assert comm.get_world_size() == 8
    assert comm.get_world_size("tp") == 2
    assert comm.get_rank() == 0
    assert comm.is_initialized()


def test_permute_contract_rejects_malformed_rings(devices8):
    """permute() enforces the shardlint-R3 ring/chain contract at
    construction time (ISSUE 3 satellite): the decomposed-matmul rings are
    lint-guaranteed the moment they trace, not only when shardlint later
    walks the jaxpr."""
    import pytest

    mesh = _mesh1d()

    def run(perm, **kw):
        f = jax.shard_map(
            lambda a: col.permute(a, "dp", perm, **kw),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        )
        return jax.jit(f)(jnp.arange(8.0))

    # legal: full ring, neighbor chain (the pipeline hop), empty perm
    run([(i, (i + 1) % 8) for i in range(8)])
    run([(i, i + 1) for i in range(7)])
    run([])
    # illegal shapes raise at trace time with the lint wording
    for perm in (
        [(0, 9)],                              # out of range
        [(0, 1), (0, 2)],                      # duplicate source
        [(0, 1), (2, 1)],                      # duplicate destination
        [(3, 3)],                              # self-loop
        [(0, 1), (1, 0), (2, 3), (3, 2)],      # disjoint sub-rings
        [(0, 1), (1, 0)],                      # partial ring
    ):
        with pytest.raises(ValueError, match="malformed ppermute"):
            run(perm)
    # validate=False bypasses (lint remains the backstop — the corpus
    # keeps the hazard class detectable)
    run([(0, 1), (1, 0)], validate=False)


def test_send_wrappers_satisfy_the_permute_contract(devices8):
    """send_forward/backward (wrap and no-wrap) ride the validated path —
    their perms are exactly the chain/ring shapes the contract allows."""
    mesh = _mesh1d()
    for fn in (col.send_forward, col.send_backward):
        for wrap in (False, True):
            f = jax.shard_map(
                lambda a, _fn=fn, _w=wrap: _fn(a, "dp", 8, wrap=_w),
                mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            )
            jax.jit(f)(jnp.arange(8.0))
