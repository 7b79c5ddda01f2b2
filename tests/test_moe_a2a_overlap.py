"""Decomposed MoE all-to-all (ISSUE 10): CPU-mesh oracles prove the
chunked ppermute rings match the module's pure-XLA reference path BITWISE
for both dispatch modes, plus moe_layer/engine integration and the
moe.overlap_a2a config surface.

Kept inside the tier-1 budget: one tiny expert layer shared by the oracle
grid; the engine legs use 2-layer models and 2 steps.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as comm
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.models.sharding import use_topology
from deepspeed_tpu.moe.sharded_moe import (
    moe_layer,
    top_k_gating,
    top_k_gating_indices,
)
from deepspeed_tpu.parallel import a2a_overlap as a2a

pytestmark = pytest.mark.a2a_overlap


def topo_for(ep: int) -> MeshTopology:
    """ep over the smallest device subset, keeping a dp axis when
    possible (odd ep sizes truncate the device list — 8 has no odd
    divisor > 1)."""
    if 8 % ep == 0:
        return MeshTopology(dims=ParallelDims(dp=8 // ep, ep=ep))
    return MeshTopology(
        dims=ParallelDims(dp=1, ep=ep), devices=jax.devices()[:ep]
    )


def _case(ep, *, B=None, S_mult=4, E_mult=1, top_k=2, cap_factor=2.0,
          seed=0):
    """One oracle case: tokens, gating tensors/tables and expert weights
    sized to the ep mesh (B divides the dp axis). Returns everything
    both paths need."""
    topo = topo_for(ep)
    B = B or 2 * topo.dp_size
    D, F = 16, 32
    E = ep * E_mult
    S = S_mult * ep
    N = B * S
    capacity = max(4, int(math.ceil(cap_factor * top_k * N / E)))
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(B, S, D), jnp.float32)
    wi = jnp.asarray(r.randn(E, D, F) * 0.1, jnp.float32)
    wg = jnp.asarray(r.randn(E, D, F) * 0.1, jnp.float32)
    wo = jnp.asarray(r.randn(E, F, D) * 0.1, jnp.float32)
    logits = jnp.asarray(r.randn(N, E), jnp.float32)
    return topo, x, (wi, wg, wo), logits, E, capacity, top_k, B, S


def _einsum_gating(logits, top_k, capacity, B, S, E, dtype):
    dispatch, combine, _ = top_k_gating(logits, top_k, capacity, None, True)
    return ("einsum", dispatch.astype(dtype).reshape(B, S, E, capacity),
            combine.astype(dtype).reshape(B, S, E, capacity))


def _gather_gating(logits, top_k, capacity, B, S):
    tos, sv, sot, wot, _ = top_k_gating_indices(
        logits, top_k, capacity, None, True
    )
    return ("gather", tos, sv, sot.reshape(B, S, -1), wot.reshape(B, S, -1))


def _run(topo, x, gating, weights, **kw):
    with use_topology(topo):
        return jax.jit(
            lambda x, wi, wg, wo: a2a.moe_a2a_ffn(
                x, gating, (wi, wg, wo), topo, **kw
            )
        )(x, *weights)


# ----------------------------------------------------------------- oracles
@pytest.mark.parametrize("ep", [2, 4, 3])  # odd AND even ring sizes
@pytest.mark.parametrize("mode", ["einsum", "gather"])
def test_ring_bitwise_vs_reference(ep, mode, devices8):
    topo, x, w, logits, E, C, K, B, S = _case(ep)
    gating = (
        _einsum_gating(logits, K, C, B, S, E, x.dtype)
        if mode == "einsum" else _gather_gating(logits, K, C, B, S)
    )
    ref = _run(topo, x, gating, w, reference=True)
    ring = _run(topo, x, gating, w)
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))


def test_uneven_chunks_and_bidirectional_bitwise(devices8):
    """Capacity chunks that don't divide C, and the two-stream halves,
    pin the ring to the reference AT THE SAME chunking (the reference
    mirrors the local loop structure, only the wire differs), and
    moderate chunkings also reproduce the unchunked reference bitwise
    (top_k=2: a token's two combine terms commute). Degenerate width-1
    chunks (chunks > C) stay ring==reference but may drift an ulp from
    the unchunked shape — XLA picks a different dot kernel, reassociating
    the d-contraction — which is why the oracle is same-chunking."""
    topo, x, w, logits, E, C, K, B, S = _case(4, cap_factor=1.0)
    gating = _einsum_gating(logits, K, C, B, S, E, x.dtype)
    ref = _run(topo, x, gating, w, reference=True)
    for kw in (dict(chunks=3), dict(chunks=2, bidirectional=True),
               dict(chunks=C + 5, bidirectional=True)):
        got = _run(topo, x, gating, w, **kw)
        same = _run(topo, x, gating, w, reference=True, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(same),
                                      err_msg=str(kw))
        if kw["chunks"] <= C // 2:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=str(kw))


def test_capacity_dropped_tokens_bitwise(devices8):
    """A tight capacity drops tokens: dropped slots are exact zeros in
    both paths and dropped tokens' outputs stay zero — ring == reference
    bitwise, both modes."""
    topo, x, w, logits, E, C, K, B, S = _case(2, cap_factor=0.25, seed=3)
    assert C < (B * S * K) // E  # capacity really binds
    for gating in (_einsum_gating(logits, K, C, B, S, E, x.dtype),
                   _gather_gating(logits, K, C, B, S)):
        ref = _run(topo, x, gating, w, reference=True)
        ring = _run(topo, x, gating, w, chunks=2)
        np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))


def test_dp_sharded_tokens_and_serial_parity(devices8):
    """With a live dp axis the per-chunk psum folds the dp token shards;
    the overlapped output matches the serial moe_layer expert path to
    fp32 tolerance (different GSPMD reduction orders), and the gather
    mode matches it exactly."""
    cfg = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=32,
                  num_experts=4).config
    m = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=32,
                num_experts=4)
    params = m.init(jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    topo = MeshTopology(dims=ParallelDims(dp=2, ep=4))
    B, S, D = 2, 16, cfg.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)

    def serial(cfg_, x_):
        out, _ = moe_layer(cfg_, p, x_, rng=None, train=True)
        return out

    def overlapped(cfg_, x_):
        ov = DeepSpeedConfig({
            "train_batch_size": 8,
            "moe": {"enabled": True, "ep_size": 4,
                    "overlap_a2a": {"enabled": True, "chunks": 2}},
        }).moe.overlap_a2a
        with use_topology(topo), a2a.a2a_scope(ov):
            out, _ = moe_layer(cfg_, p, x_, rng=None, train=True)
            return out

    base = serial(cfg, x)
    got = jax.jit(lambda x_: overlapped(cfg, x_))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=2e-5, atol=2e-5)
    cfg_g = dataclasses.replace(cfg, moe_dispatch="gather")
    base_g = serial(cfg_g, x)
    got_g = jax.jit(lambda x_: overlapped(cfg_g, x_))(x)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(base_g),
                               rtol=1e-6, atol=1e-6)


def test_gradients_flow_through_ring(devices8):
    """The rings are plain differentiable collectives (ppermute transpose
    = reversed ring): token and weight cotangents match the reference
    path's."""
    topo, x, w, logits, E, C, K, B, S = _case(2)
    gating = _einsum_gating(logits, K, C, B, S, E, x.dtype)

    def loss(ref):
        def f(x_, wi):
            with use_topology(topo):
                out = a2a.moe_a2a_ffn(x_, gating, (wi, w[1], w[2]), topo,
                                      chunks=2, reference=ref)
            return jnp.sum(out ** 2)
        return f

    gx_r, gw_r = jax.jit(jax.grad(loss(True), argnums=(0, 1)))(x, w[0])
    gx, gw = jax.jit(jax.grad(loss(False), argnums=(0, 1)))(x, w[0])
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.sum(jnp.abs(gx))) > 0


# ------------------------------------------------------ engine integration
def test_engine_loss_parity_and_stream_accounting(devices8):
    """ep=4 training with overlap on tracks the off run step-for-step;
    the moe_a2a stream is declared on BOTH runs (the serial path moves
    the same logical bytes — the ISSUE-10 fix), flips overlapped with
    the knob, and its bytes reach the comms logger's ring intake; the
    ring hops hit the trace-time hook bus only when the knob is on."""
    data = {"input_ids":
            np.random.RandomState(0).randint(0, 256, size=(8, 32))}

    def run(overlap):
        comm.destroy_process_group()
        cfg = {
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "moe": {"enabled": True, "ep_size": 4,
                    "overlap_a2a": {"enabled": overlap, "chunks": 2,
                                    "bidirectional": True}},
            "comms_logger": {"enabled": True},
            "steps_per_print": 1000,
        }
        model = mixtral("mixtral-tiny", vocab_size=256, max_seq_len=32,
                        num_experts=4)
        eng, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
        losses = [float(eng.train_batch(batch=data)) for _ in range(2)]
        stream = eng.analytic_streams()["moe_a2a"]
        pperm = eng.comm_logger.counts.get("ppermute", 0)
        ring_logged = eng.comm_logger.ring_bytes
        eng.destroy()
        return losses, stream, pperm, ring_logged

    l_off, s_off, pp_off, rb_off = run(False)
    l_on, s_on, pp_on, rb_on = run(True)
    np.testing.assert_allclose(l_off, l_on, rtol=2e-3, atol=2e-3)
    assert not s_off["overlapped"] and s_on["overlapped"]
    assert s_on["bytes_per_step"] == s_off["bytes_per_step"] > 0
    assert rb_on == 2 * s_on["bytes_per_step"]  # two recorded steps
    assert pp_on > pp_off == 0


def test_fallback_outside_scope_and_on_undividable_shapes(devices8):
    """Without the scope moe_layer is untouched; with the scope active
    but shapes not dividing the mesh the applicability predicate refuses
    and the serial path runs (no shard_map in the trace)."""
    topo = MeshTopology(dims=ParallelDims(dp=2, ep=4))
    assert a2a.current_a2a() is None
    # E=3 does not divide ep=4; S=6 does not divide sp*ep
    assert not a2a.moe_a2a_applicable(topo, B=4, S=8 * 4, E=3, F=32)
    assert not a2a.moe_a2a_applicable(topo, B=4, S=6, E=4, F=32)
    assert not a2a.moe_a2a_applicable(topo, B=3, S=8, E=4, F=32)
    assert a2a.moe_a2a_applicable(topo, B=4, S=8, E=4, F=32)
    ep1 = MeshTopology(dims=ParallelDims(dp=8))
    assert not a2a.moe_a2a_applicable(ep1, B=8, S=8, E=4, F=32)
    # an engine whose knob is ON but whose shapes keep the rings from
    # engaging must NOT declare the stream overlapped (R8 would hide
    # wire that actually runs serialized) — bytes still declared
    comm.destroy_process_group()
    model = mixtral("mixtral-tiny", vocab_size=256, max_seq_len=30,
                    num_experts=4)  # S=30 % ep=4 != 0 → serial fallback
    eng, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "moe": {"enabled": True, "ep_size": 4,
                    "overlap_a2a": {"enabled": True}},
        },
        abstract_init=True,
    )
    s = eng.analytic_streams()["moe_a2a"]
    assert eng.moe_a2a is not None and not s["overlapped"]
    assert s["bytes_per_step"] > 0
    eng.destroy()


def test_malformed_ring_raises_at_construction(devices8):
    """The rings go through comm.collectives.permute: a malformed
    hand-built perm raises at trace time (the R3 contract), so no
    a2a-overlap program can ever carry a hang-shaped exchange."""
    topo = MeshTopology(dims=ParallelDims(dp=2, ep=4))
    from jax.sharding import PartitionSpec as P

    bad = [(0, 1), (1, 2), (2, 3), (3, 1)]

    def body(v):
        return comm.collectives.permute(v, "ep", bad)

    fn = jax.shard_map(
        body, mesh=topo.mesh, in_specs=P("ep"), out_specs=P("ep"),
        axis_names=set(topo.mesh.axis_names), check_vma=False,
    )
    with pytest.raises(ValueError, match="malformed ppermute"):
        jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), jnp.float32))


def test_bytes_accounting_and_config_surface():
    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "moe": {"enabled": True, "ep_size": 2,
                "overlap_a2a": {"enabled": True, "chunks": 4,
                                "bidirectional": True}},
    })
    oa = cfg.moe.overlap_a2a
    assert (oa.enabled, oa.chunks, oa.bidirectional) == (True, 4, True)
    # bare boolean coerces; defaults off
    oa2 = DeepSpeedConfig({
        "train_batch_size": 8, "moe": {"enabled": True, "overlap_a2a": True},
    }).moe.overlap_a2a
    assert oa2.enabled and oa2.chunks == 1
    assert not DeepSpeedConfig(
        {"train_batch_size": 8}).moe.overlap_a2a.enabled
    with pytest.raises(DeepSpeedConfigError, match="chunks"):
        DeepSpeedConfig({
            "train_batch_size": 8,
            "moe": {"overlap_a2a": {"enabled": True, "chunks": 0}},
        })
    with pytest.raises(DeepSpeedConfigError, match="pipeline"):
        DeepSpeedConfig({
            "train_batch_size": 8,
            "pipeline": {"stages": 2},
            "moe": {"enabled": True, "overlap_a2a": {"enabled": True}},
        })
    # analytic bytes: 2 exchanges/layer fwd, doubled for backward
    mcfg = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=32,
                   num_experts=4).config
    topo = MeshTopology(dims=ParallelDims(dp=2, ep=4))
    s = a2a.moe_a2a_bytes_per_step(mcfg, topo, batch=4, seq=32, itemsize=4)
    C = s["capacity"]
    per_dir = (4 // 4) * C * mcfg.hidden_size * 4 * 3
    assert s["fwd_bytes_per_step"] == 2 * per_dir * mcfg.num_layers
    assert s["bytes_per_step"] == 2 * s["fwd_bytes_per_step"]
    assert a2a.moe_a2a_bytes_per_step(
        mcfg, MeshTopology(dims=ParallelDims(dp=8)), batch=4, seq=32
    ) is None
