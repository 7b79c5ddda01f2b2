"""Decomposed collective matmul (ISSUE 3): CPU-mesh oracles prove the
ring forms match the pure-XLA reference path — BITWISE for the unquantized
unidirectional rings — plus engine/inference integration and the
overlap_comm config surface.

Kept inside the tier-1 budget: every oracle runs one small jitted program
per form; the heavyweight parameter grid lives in a handful of cases
(odd/even tp, uneven chunks) rather than a cross-product.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as comm
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.models import llama
from deepspeed_tpu.models.sharding import use_topology
from deepspeed_tpu.parallel import tensor_overlap as to

pytestmark = pytest.mark.tp_overlap


def topo_for(tp: int) -> MeshTopology:
    """tp over the smallest device subset that also keeps a dp axis when
    possible; odd tp sizes use a truncated device list (8 has no odd
    divisor > 1)."""
    if 8 % tp == 0:
        return MeshTopology(dims=ParallelDims(tp=tp, dp=8 // tp))
    return MeshTopology(
        dims=ParallelDims(tp=tp, dp=1), devices=jax.devices()[:tp]
    )


def rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


# ----------------------------------------------------------------- oracles
@pytest.mark.parametrize("tp", [2, 4, 3])  # odd AND even ring sizes
def test_allgather_matmul_bitwise_vs_reference(tp, devices8):
    topo = topo_for(tp)
    dp = topo.dp_size
    B, S, K, N = 2 * dp, 12 * tp, 24, 8 * tp
    x, w = rand((B, S, K)), rand((K, N), seed=1)
    # the plain einsum, one tp column block of w at a time — the columns a
    # shard owns. Row blocks of a dot are independent, but XLA:CPU (jaxlib
    # 0.9) picks its dot kernel from the column count, and the N=32 and N=8
    # kernels sum over K in different orders (both as close to float64):
    # whole-w einsum vs the reference differs at tp=4, per block it is exact
    n = N // tp
    dense = jnp.concatenate(
        [jnp.einsum("bsk,kn->bsn", x, w[:, i * n:(i + 1) * n])
         for i in range(tp)], axis=-1,
    )
    ref = jax.jit(
        lambda a, b: to.allgather_matmul(a, b, topo, reference=True)
    )(x, w)
    ring = jax.jit(lambda a, b: to.allgather_matmul(a, b, topo))(x, w)
    # the pure-XLA reference path itself equals the plain einsum bitwise,
    # and the unquantized unidirectional ring matches it bitwise — the
    # acceptance oracle
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))


@pytest.mark.parametrize("tp", [2, 4, 3])
def test_matmul_reducescatter_bitwise_vs_reference(tp, devices8):
    topo = topo_for(tp)
    dp = topo.dp_size
    B, S, K, N = 2 * dp, 4 * tp, 16 * tp, 24
    x, w = rand((B, S, K)), rand((K, N), seed=2)
    dense = jnp.einsum("bsk,kn->bsn", x, w)
    ref = jax.jit(
        lambda a, b: to.matmul_reducescatter(a, b, topo, reference=True)
    )(x, w)
    ring = jax.jit(lambda a, b: to.matmul_reducescatter(a, b, topo))(x, w)
    # the reference reduces in pinned ring order (qgZ all-to-all form), so
    # ring == reference is bitwise; both match the dense einsum+psum path
    # to f32 tolerance (different fp32 summation orders)
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("bidirectional", [False, True])
def test_uneven_chunks_change_nothing(bidirectional, devices8):
    """chunks that don't divide the rows (and odd per-shard rows for the
    bidirectional halves) are pure scheduling — bitwise-identical."""
    tp = 4
    topo = topo_for(tp)
    B, S, K, N = 4, 5 * tp, 24, 8 * tp  # 5 rows/shard: 3 chunks split 2/2/1
    x, w = rand((B, S, K)), rand((K, N), seed=3)
    base = jax.jit(lambda a, b: to.allgather_matmul(a, b, topo))(x, w)
    got = jax.jit(
        lambda a, b: to.allgather_matmul(
            a, b, topo, chunks=3, bidirectional=bidirectional
        )
    )(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    # scatter side: uneven chunks + bidirectional halves, f32 tolerance
    # (the backward half accumulates in reverse ring order)
    x2, w2 = rand((B, S, K * tp), seed=4), rand((K * tp, N), seed=5)
    dense = jnp.einsum("bsk,kn->bsn", x2, w2)
    got2 = jax.jit(
        lambda a, b: to.matmul_reducescatter(
            a, b, topo, chunks=3, bidirectional=bidirectional
        )
    )(x2, w2)
    np.testing.assert_allclose(
        np.asarray(got2), np.asarray(dense), rtol=1e-4, atol=1e-4
    )


def test_bidirectional_gather_still_bitwise(devices8):
    """The two-stream gather writes each row from exactly one dot — still
    bitwise against the reference, odd and even ring sizes. Two batch rows
    per dp member: with one, the 1-row half is an M=1 dot, for which
    XLA:CPU (jaxlib 0.9) emits a matrix-vector kernel that sums over K in
    another order than the reference's GEMM."""
    for tp in (4, 3):
        topo = topo_for(tp)
        # 3 rows/shard → halves 2 + 1
        x = rand((2 * topo.dp_size, 3 * tp, 16), seed=6)
        w = rand((16, 8 * tp), seed=7)
        ref = jax.jit(
            lambda a, b: to.allgather_matmul(a, b, topo, reference=True)
        )(x, w)
        got = jax.jit(
            lambda a, b: to.allgather_matmul(a, b, topo, bidirectional=True)
        )(x, w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_overlap_wrapper_requests_every_mesh_axis(monkeypatch, devices8):
    """The decomposed matmul is a FULL-manual program: the wrapper must
    hand jax.shard_map EVERY mesh axis and switch the vma check off."""
    seen = {}

    def fake_shard_map(f, mesh, in_specs, out_specs, **kw):
        seen.update(kw)
        raise RuntimeError("stop after capture")

    monkeypatch.setattr(jax, "shard_map", fake_shard_map)
    topo = topo_for(4)
    with pytest.raises(RuntimeError, match="stop after capture"):
        to.allgather_matmul(jnp.zeros((2, 8, 16)), jnp.zeros((16, 8)), topo)
    assert seen["axis_names"] == set(topo.mesh.axis_names)
    assert seen["check_vma"] is False


def test_quantized_hops(devices8):
    """Gather wires quantize once at the source: ring == reference
    BITWISE (same int8+scale payload either way) and within fake-quant
    error of the dense product. Scatter accumulators re-quantize per hop:
    tolerance grows with the ring (documented O(tp) error)."""
    tp = 4
    topo = topo_for(tp)
    x, w = rand((2, 4 * tp, 24), seed=8), rand((24, 8 * tp), seed=9)
    dense = jnp.einsum("bsk,kn->bsn", x, w)
    q_ring = jax.jit(
        lambda a, b: to.allgather_matmul(a, b, topo, quantized=True)
    )(x, w)
    q_ref = jax.jit(
        lambda a, b: to.allgather_matmul(
            a, b, topo, quantized=True, reference=True
        )
    )(x, w)
    np.testing.assert_array_equal(np.asarray(q_ring), np.asarray(q_ref))
    err = np.max(np.abs(np.asarray(q_ring) - np.asarray(dense)))
    assert err < 0.5, f"int8 gather-wire error too large: {err}"

    x2, w2 = rand((2, 4 * tp, 8 * tp), seed=10), rand((8 * tp, 24), seed=11)
    dense2 = jnp.einsum("bsk,kn->bsn", x2, w2)
    q_rs = jax.jit(
        lambda a, b: to.matmul_reducescatter(a, b, topo, quantized=True)
    )(x2, w2)
    rel = np.max(np.abs(np.asarray(q_rs) - np.asarray(dense2))) / (
        np.max(np.abs(np.asarray(dense2))) + 1e-9
    )
    assert rel < 0.2, f"int8 scatter-wire relative error too large: {rel}"
    # the quantized reference (per-block qgZ all-to-all) must trace, run
    # and stay within the same tolerance — it quantizes each partial once
    # where the ring re-quantizes the riding sum per hop, so the two are
    # compared to the dense product, not to each other
    q_rs_ref = jax.jit(
        lambda a, b: to.matmul_reducescatter(
            a, b, topo, quantized=True, reference=True
        )
    )(x2, w2)
    rel_ref = np.max(np.abs(np.asarray(q_rs_ref) - np.asarray(dense2))) / (
        np.max(np.abs(np.asarray(dense2))) + 1e-9
    )
    assert rel_ref < 0.2, f"quantized reference error too large: {rel_ref}"


def test_features_scatter_decode_form(devices8):
    """The S=1 decode form: feature-scatter + gather == plain matmul
    (decomposed all-reduce)."""
    tp = 4
    topo = topo_for(tp)
    x, w = rand((1, 1, 8 * tp), seed=12), rand((8 * tp, 16 * tp), seed=13)
    dense = jnp.einsum("bsk,kn->bsn", x, w)
    got = jax.jit(
        lambda a, b: to.matmul_reducescatter(
            a, b, topo, scatter="features", gather_result=True
        )
    )(x, w)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense), rtol=1e-4, atol=1e-4
    )


# ------------------------------------------------------------- ring bytes
def test_rings_are_logged_and_validated(devices8):
    """The rings go through comm.collectives.permute: hop bytes reach the
    comms-logger hook bus and a malformed hand-built perm raises at
    construction (satellite: the neighbor_chain contract, now enforced)."""
    seen = []
    comm.collectives.register_comm_hook(
        lambda op, axis, nbytes: seen.append((op, nbytes))
    )
    try:
        tp = 4
        topo = topo_for(tp)
        x, w = rand((2, 4 * tp, 16)), rand((16, 8 * tp))
        jax.jit(lambda a, b: to.allgather_matmul(a, b, topo))(x, w)
    finally:
        comm.collectives.clear_comm_hooks()
    hops = [n for op, n in seen if op == "ppermute"]
    assert len(hops) == tp - 1  # one wire per hop, traced unrolled
    assert all(n == hops[0] > 0 for n in hops)


# ------------------------------------------------------ engine integration
def tiny_llama(**kw):
    d = dict(vocab_size=128, max_seq_len=32, hidden_size=32, num_layers=2,
             num_heads=4, num_kv_heads=4, intermediate_size=64)
    d.update(kw)
    return llama("llama-tiny", **d)


def test_engine_loss_parity_and_ring_accounting(devices8):
    """tp=2 training with overlap on tracks the off run step-for-step, and
    the engine reports the analytic ring stream to the comms logger."""
    data = {"input_ids": np.random.RandomState(0).randint(0, 128, size=(8, 32))}

    def run(overlap):
        comm.destroy_process_group()
        cfg = {
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "tensor_parallel": {
                "tp_size": 2,
                "overlap_comm": {"enabled": overlap, "chunks": 2,
                                 "bidirectional": True},
            },
            "comms_logger": {"enabled": True},
            "steps_per_print": 1000,
        }
        eng, *_ = deepspeed_tpu.initialize(model=tiny_llama(), config=cfg)
        losses = [float(eng.train_batch(batch=data)) for _ in range(2)]
        stream = eng.tp_overlap_stream
        logged = eng.comm_logger.ring_bytes
        pperm = eng.comm_logger.counts.get("ppermute", 0)
        eng.destroy()
        return losses, stream, logged, pperm

    l_off, s_off, logged_off, pp_off = run(False)
    l_on, s_on, logged_on, pp_on = run(True)
    np.testing.assert_allclose(l_off, l_on, rtol=2e-3, atol=2e-3)
    assert s_off is None and logged_off == 0
    assert s_on is not None and s_on["bytes_per_step"] > 0
    assert logged_on == 2 * s_on["bytes_per_step"]  # two recorded steps
    assert pp_on > pp_off  # ring hops hit the trace-time hook bus too


def test_inference_generate_parity_under_overlap(devices8):
    """Dense tp=4 serving with overlap_comm produces token-identical
    output to the unsharded engine (prefill takes the Megatron-SP pair
    when shapes divide; S=1 decode takes the feature-scatter ring)."""
    m = tiny_llama(num_kv_heads=2)
    p = m.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = np.array([[5, 9, 11, 3]])
    e1 = deepspeed_tpu.init_inference(m, dtype=jnp.float32, params=p)
    out1 = e1.generate(prompt, max_new_tokens=6)
    topo = MeshTopology(dims=ParallelDims(tp=4, dp=2))
    e2 = deepspeed_tpu.init_inference(
        m, dtype=jnp.float32, params=p, topology=topo,
        tensor_parallel={
            "tp_size": 4,
            "overlap_comm": {"enabled": True, "bidirectional": True},
        },
    )
    out2 = e2.generate(prompt, max_new_tokens=6)
    np.testing.assert_array_equal(out1, out2)


def test_overlap_noop_outside_scope_and_inside_manual(devices8):
    """Without the scope the dispatchers are the plain projections; under
    an installed topology but inside a manual shard_map they fall back
    (the pipeline schedule case)."""
    topo = MeshTopology(dims=ParallelDims(tp=4, dp=2))
    x, w = rand((2, 8, 16)), rand((16, 8))
    with use_topology(topo):
        (y,) = to.tp_in_proj(x, (w,))  # no scope: plain einsum
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.einsum("bsk,kn->bsn", x, w)),
        rtol=1e-6, atol=1e-6,
    )
    assert to.current_overlap() is None
    cfg = DeepSpeedConfig(
        {"train_batch_size": 8,
         "tensor_parallel": {"tp_size": 4,
                             "overlap_comm": {"enabled": True}}}
    ).tensor_parallel.overlap_comm
    with to.overlap_scope(cfg):
        assert to.current_overlap() is cfg
        assert to._active(topo) is cfg
        # inside a manual mapped context the guard must refuse
        from jax.sharding import PartitionSpec as P

        flags = {}

        def body(a):
            with use_topology(topo):
                flags["active"] = to._active(topo)
            return a

        jax.jit(jax.shard_map(
            body, mesh=topo.mesh, in_specs=P(("dp",)), out_specs=P("dp"),
            axis_names=set(topo.mesh.axis_names), check_vma=False,
        ))(jnp.ones((8,)))
        assert flags["active"] is None


# ------------------------------------------------------------------ config
def test_overlap_comm_config_surface():
    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "tensor_parallel": {
            "tp_size": 2,
            "overlap_comm": {"enabled": True, "chunks": 4,
                             "bidirectional": True, "quantized_hops": True},
        },
    })
    oc = cfg.tensor_parallel.overlap_comm
    assert (oc.enabled, oc.chunks, oc.bidirectional, oc.quantized_hops) == (
        True, 4, True, True,
    )
    # defaults: knob off, unit chunks
    oc2 = DeepSpeedConfig({"train_batch_size": 8}).tensor_parallel.overlap_comm
    assert (oc2.enabled, oc2.chunks) == (False, 1)
    # the autotp_size alias must not drop the rest of the section
    tp3 = DeepSpeedConfig({
        "train_batch_size": 8,
        "tensor_parallel": {"autotp_size": 2,
                            "overlap_comm": {"enabled": True}},
    }).tensor_parallel
    assert tp3.tp_size == 2 and tp3.overlap_comm.enabled
    # bare boolean (the zero_optimization.overlap_comm spelling) coerces
    tp4 = DeepSpeedConfig({
        "train_batch_size": 8,
        "tensor_parallel": {"tp_size": 2, "overlap_comm": True},
    }).tensor_parallel
    assert tp4.overlap_comm.enabled and tp4.overlap_comm.chunks == 1
    with pytest.raises(DeepSpeedConfigError, match="chunks"):
        DeepSpeedConfig({
            "train_batch_size": 8,
            "tensor_parallel": {"overlap_comm": {"enabled": True,
                                                 "chunks": 0}},
        })
    with pytest.raises(DeepSpeedConfigError, match="pipeline"):
        DeepSpeedConfig({
            "train_batch_size": 8,
            "pipeline": {"stages": 2},
            "tensor_parallel": {"tp_size": 2,
                                "overlap_comm": {"enabled": True}},
        })


def test_quantized_hops_training_gradients_flow(devices8):
    """quantized_hops is forward-only (straight-through backward): the
    engine must still move the loss — int8 casts inside the ring would
    otherwise zero every activation cotangent below the projection."""
    data = {"input_ids": np.random.RandomState(1).randint(0, 128, size=(8, 32))}
    comm.destroy_process_group()
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
        "tensor_parallel": {
            "tp_size": 2,
            "overlap_comm": {"enabled": True, "quantized_hops": True},
        },
        "steps_per_print": 1000,
    }
    eng, *_ = deepspeed_tpu.initialize(model=tiny_llama(), config=cfg)
    first = float(eng.train_batch(batch=data))
    embed0 = np.asarray(eng.state.params["embed"]["tok"])
    for _ in range(3):
        last = float(eng.train_batch(batch=data))
    embed1 = np.asarray(eng.state.params["embed"]["tok"])
    eng.destroy()
    assert np.isfinite(first) and np.isfinite(last)
    # the embedding sits BELOW every ring: it only moves if cotangents
    # survive the quantized wires
    assert not np.allclose(embed0, embed1)
    assert last < first
