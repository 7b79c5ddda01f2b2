"""Flash attention kernel vs XLA reference (fwd + grads), interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(rng, B=2, S=256, H=4, KV=None, D=64, dtype=jnp.float32):
    KV = KV or H
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gqa_forward():
    q, k, v = _qkv(jax.random.PRNGKey(1), H=8, KV=2)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(2), B=1, S=256, H=2, D=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=128, block_k=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


def test_gqa_grads():
    q, k, v = _qkv(jax.random.PRNGKey(3), B=1, S=128, H=4, KV=2, D=64)

    g_flash = jax.jit(jax.grad(
        lambda *a: jnp.sum(flash_attention(*a, causal=True, block_q=128, block_k=128) ** 2),
        argnums=(0, 1, 2),
    ))(q, k, v)
    g_ref = jax.jit(jax.grad(
        lambda *a: jnp.sum(xla_attention(*a, causal=True) ** 2), argnums=(0, 1, 2)
    ))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


def test_unsupported_falls_back():
    # unaligned seq length (not a multiple of 128) → fallback to XLA path
    rng = jax.random.PRNGKey(4)
    q = jax.random.normal(rng, (1, 100, 2, 64))
    out = flash_attention(q, q, q, causal=True)
    ref = xla_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_cross_length_falls_back():
    # Sq != Sk (decode-style) must NOT silently truncate keys
    rng = jax.random.PRNGKey(5)
    q = jax.random.normal(rng, (1, 128, 2, 64))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (1, 256, 2, 64))
    out = flash_attention(q, k, v, causal=False)
    ref = xla_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_sharded_flash_matches_reference(devices8):
    """Under a >1-device topology, flash runs in shard_map and must agree."""
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.comm import ParallelDims
    from deepspeed_tpu.models.sharding import use_topology

    topo = comm.init_distributed(dims=ParallelDims(dp=4, tp=2))
    q, k, v = _qkv(jax.random.PRNGKey(6), B=4, S=256, H=4, KV=2, D=64)
    ref = xla_attention(q, k, v, causal=True)
    with use_topology(topo):
        out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    # grads flow through the shard_mapped kernel too
    with use_topology(topo):
        g = jax.jit(
            jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v) ** 2), argnums=0)
        )(q, k, v)
    g_ref = jax.jit(jax.grad(lambda q, k, v: jnp.sum(xla_attention(q, k, v, causal=True) ** 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-4)


def test_registered_as_attention_impl():
    from deepspeed_tpu.ops.attention import _IMPLS

    assert "flash" in _IMPLS


# ---------------------------------------------------------------------------
# r3: in-kernel segment masking, ALiBi slopes, sp composition
# ---------------------------------------------------------------------------
def _segments(B, S, n=3, seed=7):
    """Sorted segment ids (packed-sequence style) [B, S]."""
    r = np.random.RandomState(seed)
    out = np.zeros((B, S), np.int32)
    for b in range(B):
        cuts = np.sort(r.choice(np.arange(1, S), size=n - 1, replace=False))
        out[b] = np.searchsorted(cuts, np.arange(S), side="right")
    return jnp.asarray(out)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_in_kernel(causal):
    """segment_ids must take the Pallas kernel (no fallback) and match XLA."""
    q, k, v = _qkv(jax.random.PRNGKey(8), B=2, S=256, H=2, D=64)
    seg = _segments(2, 256)
    called = {}
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    orig = fa._flash_fwd

    def spy(*a, **kw):
        called["yes"] = True
        return orig(*a, **kw)

    fa._flash_fwd, orig_saved = spy, orig
    try:
        out = flash_attention(q, k, v, causal=causal, segment_ids=seg,
                              block_q=128, block_k=128)
    finally:
        fa._flash_fwd = orig_saved
    assert called.get("yes"), "segment_ids fell back to XLA"
    ref = xla_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_segment_ids_grads():
    q, k, v = _qkv(jax.random.PRNGKey(9), B=1, S=256, H=2, D=64)
    seg = _segments(1, 256)
    g_flash = jax.jit(jax.grad(
        lambda *a: jnp.sum(
            flash_attention(*a, causal=True, segment_ids=seg,
                            block_q=128, block_k=128) ** 2
        ),
        argnums=(0, 1, 2),
    ))(q, k, v)
    g_ref = jax.jit(jax.grad(
        lambda *a: jnp.sum(xla_attention(*a, causal=True, segment_ids=seg) ** 2),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


def test_alibi_slopes_in_kernel():
    """ALiBi via per-head slopes matches the dense-bias XLA reference,
    forward and backward, without materializing [B,H,S,S]."""
    from deepspeed_tpu.models.transformer import alibi_slopes as make_slopes

    H = 4
    q, k, v = _qkv(jax.random.PRNGKey(10), B=2, S=256, H=H, D=64)
    slopes = jnp.asarray(make_slopes(H))
    out = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                          block_q=128, block_k=128)
    ref = xla_attention(q, k, v, causal=True, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    g = jax.jit(jax.grad(
        lambda *a: jnp.sum(
            flash_attention(*a, causal=True, alibi_slopes=slopes,
                            block_q=128, block_k=128) ** 2
        )
    ))(q, k, v)
    g_ref = jax.jit(jax.grad(
        lambda *a: jnp.sum(xla_attention(*a, causal=True, alibi_slopes=slopes) ** 2)
    ))(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-4)


def test_alibi_plus_segments_in_kernel():
    from deepspeed_tpu.models.transformer import alibi_slopes as make_slopes

    H = 2
    q, k, v = _qkv(jax.random.PRNGKey(11), B=2, S=256, H=H, D=64)
    slopes = jnp.asarray(make_slopes(H))
    seg = _segments(2, 256)
    out = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                          segment_ids=seg, block_q=128, block_k=128)
    ref = xla_attention(q, k, v, causal=True, alibi_slopes=slopes,
                        segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_under_sp_mesh(devices8):
    """sp>1 (Ulysses layout: heads over tp×sp) must take the kernel."""
    import deepspeed_tpu.comm as comm
    import deepspeed_tpu.ops.pallas.flash_attention as fa
    from deepspeed_tpu.comm import ParallelDims
    from deepspeed_tpu.models.sharding import use_topology

    comm.destroy_process_group()
    topo = comm.init_distributed(dims=ParallelDims(dp=2, sp=2, tp=2))
    q, k, v = _qkv(jax.random.PRNGKey(12), B=2, S=256, H=4, KV=4, D=64)
    ref = xla_attention(q, k, v, causal=True)
    called = {}
    orig = fa._flash_fwd

    def spy(*a, **kw):
        called["yes"] = True
        return orig(*a, **kw)

    fa._flash_fwd = spy
    try:
        with use_topology(topo):
            out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
                q, k, v
            )
    finally:
        fa._flash_fwd = orig
    assert called.get("yes"), "sp>1 fell back to XLA"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    comm.destroy_process_group()


def test_flash_inside_manual_context_all_axes_manual(devices8):
    """pp-only topology: inside the pipeline's manual region no Auto axes
    remain — flash must run the kernel directly (axis_names=set() crashes
    shard_map)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.comm import MeshTopology, ParallelDims
    from deepspeed_tpu.models import llama

    comm.destroy_process_group()
    topo = MeshTopology(ParallelDims(pp=2), devices=jax.devices()[:2])
    comm.set_topology(topo)
    model = llama("llama-tiny", vocab_size=256, max_seq_len=128,
                  hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=4,
                  intermediate_size=128)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, topology=topo,
        config={
            "train_batch_size": 4,
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "pipeline": {"stages": 2},
            "tpu_kernels": {"flash_attention": True},
        },
        rng=jax.random.PRNGKey(0),
    )
    loss = engine.train_batch(
        batch={"input_ids": np.random.RandomState(0).randint(0, 256, size=(4, 128))}
    )
    assert np.isfinite(float(loss))
    comm.destroy_process_group()


def test_flash_under_onebit_stacked_grads(devices8):
    """1-bit wire path manualizes the dp axis; flash's nested shard_map must
    only map the still-Auto axes (r3 review repro)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.comm import MeshTopology, ParallelDims
    from deepspeed_tpu.models import llama

    comm.destroy_process_group()
    topo = MeshTopology(ParallelDims(dp=4, tp=2), devices=jax.devices())
    comm.set_topology(topo)
    model = llama("llama-tiny", vocab_size=256, max_seq_len=128,
                  hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=4,
                  intermediate_size=128)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, topology=topo,
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-3, "freeze_step": 2}},
            "zero_optimization": {"stage": 1},
            "tpu_kernels": {"flash_attention": True},
        },
        rng=jax.random.PRNGKey(0),
    )
    assert engine._stacked_grads_axes  # the wire path is actually active
    losses = [
        float(engine.train_batch(
            batch={"input_ids": np.random.RandomState(i).randint(0, 256, size=(8, 128))}
        ))
        for i in range(3)
    ]
    assert np.isfinite(losses).all()
    comm.destroy_process_group()


def test_dots_flash_policy_skips_fwd_recompute():
    """The dots_flash remat policy saves the kernel outputs (checkpoint_name
    tags in _fa_fwd), so backward must NOT re-run the forward kernel:
    3 pallas calls (fwd, dq, dkv) vs dots_saveable's 4 (+fwd recompute)."""
    from deepspeed_tpu.analysis.shardlint import pallas_grids
    from deepspeed_tpu.runtime.activation_checkpointing import policy_by_name

    q, k, v = _qkv(jax.random.PRNGKey(3), B=1, S=256, H=2, D=64)

    def counts(policy_name):
        f = jax.checkpoint(
            lambda q, k, v: flash_attention(q, k, v, interpret=True).sum(),
            policy=policy_by_name(policy_name),
            prevent_cse=False,
        )
        return len(pallas_grids(jax.make_jaxpr(jax.grad(f))(q, k, v).jaxpr))

    assert counts("dots_saveable") == 4
    assert counts("dots_flash") == 3


def test_dots_flash_policy_grads_match():
    from deepspeed_tpu.runtime.activation_checkpointing import policy_by_name

    q, k, v = _qkv(jax.random.PRNGKey(4), B=1, S=256, H=2, D=64)

    def loss(q, k, v):
        return (flash_attention(q, k, v, interpret=True) ** 2).sum()

    ref = jax.jit(jax.grad(loss))(q, k, v)
    got = jax.jit(jax.grad(
        jax.checkpoint(loss, policy=policy_by_name("dots_flash"),
                       prevent_cse=False)
    ))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# dense additive bias in-kernel (VERDICT r3 missing #5)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bias_bh", [(2, 4), (1, 4), (2, 1), (1, 1)])
def test_dense_bias_in_kernel_forward(causal, bias_bh):
    B, S, H, D = 2, 256, 4, 64
    q, k, v = _qkv(jax.random.PRNGKey(10), B=B, S=S, H=H, D=D)
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(11), (*bias_bh, S, S))
    out = flash_attention(q, k, v, causal=causal, bias=bias,
                          block_q=128, block_k=128)
    ref = xla_attention(q, k, v, causal=causal, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("bias_bh,kv_heads", [
    # B=2 so the broadcast accumulation over batch is a real reduction
    # (full-shape (2,H) takes the inline dq-kernel dbias path; the three
    # broadcast shapes take the dedicated accumulation kernel); the last
    # case composes the accumulation kernel with GQA head grouping
    ((2, 2), 2), ((1, 2), 2), ((2, 1), 2), ((1, 1), 2), ((1, 4), 2),
])
def test_dense_bias_grads_including_dbias(bias_bh, kv_heads):
    B, S, D = 2, 256, 64
    H = bias_bh[1] if bias_bh[1] > 1 else 2
    q, k, v = _qkv(jax.random.PRNGKey(12), B=B, S=S, H=H, KV=kv_heads, D=D)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(13), (*bias_bh, S, S))

    def loss(fn):
        return lambda q, k, v, b: jnp.sum(
            fn(q, k, v, causal=True, bias=b) ** 2
        )

    g_flash = jax.jit(jax.grad(
        loss(lambda q, k, v, causal, bias: flash_attention(
            q, k, v, causal=causal, bias=bias, block_q=128, block_k=128)),
        argnums=(0, 1, 2, 3),
    ))(q, k, v, bias)
    g_ref = jax.jit(jax.grad(loss(xla_attention), argnums=(0, 1, 2, 3)))(q, k, v, bias)
    for gf, gr, name in zip(g_flash, g_ref, ["q", "k", "v", "bias"]):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=1e-3, err_msg=f"d{name}"
        )


def test_dense_bias_with_gqa_and_segments():
    B, S, H, KV, D = 2, 256, 4, 2, 64
    q, k, v = _qkv(jax.random.PRNGKey(14), B=B, S=S, H=H, KV=KV, D=D)
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(15), (1, H, S, S))
    seg = jnp.concatenate(
        [jnp.zeros((B, S // 2), jnp.int32), jnp.ones((B, S - S // 2), jnp.int32)],
        axis=1,
    )
    out = flash_attention(q, k, v, causal=True, bias=bias, segment_ids=seg,
                          block_q=128, block_k=128)
    ref = xla_attention(q, k, v, causal=True, bias=bias, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


class _LogCapture:
    """The deepspeed_tpu logger sets propagate=False, so caplog can't see
    it; attach a handler directly."""

    def __enter__(self):
        import logging

        from deepspeed_tpu.utils.logging import logger

        self.records = []
        outer = self

        class H(logging.Handler):
            def emit(self, record):
                outer.records.append(record)

        self._handler = H()
        self._logger = logger
        logger.addHandler(self._handler)
        return self

    def __exit__(self, *exc):
        self._logger.removeHandler(self._handler)

    def messages(self):
        return [r.getMessage() for r in self.records]


def test_ineligible_bias_falls_back_with_log():
    from deepspeed_tpu.ops.pallas import flash_attention as fa_mod
    from deepspeed_tpu.utils import logging as logging_mod

    logging_mod.fallback_log_seen.clear()
    q, k, v = _qkv(jax.random.PRNGKey(16), B=2, S=256, H=4, D=64)
    # per-head bias missing the batch dim → not in-kernel-eligible → XLA
    # fallback, with exactly ONE log line naming the reason
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(17), (4, 256, 256))
    with _LogCapture() as cap:
        out = flash_attention(q, k, v, causal=True, bias=bias)
        _ = flash_attention(q, k, v, causal=True, bias=bias)
    ref = xla_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    hits = [m for m in cap.messages() if "falling back" in m]
    assert len(hits) == 1, cap.messages()
    assert "dense bias shape" in hits[0]


def test_unaligned_seq_fallback_names_reason():
    from deepspeed_tpu.ops.pallas import flash_attention as fa_mod
    from deepspeed_tpu.utils import logging as logging_mod

    logging_mod.fallback_log_seen.clear()
    rng = jax.random.PRNGKey(18)
    q = jax.random.normal(rng, (1, 100, 2, 64))
    with _LogCapture() as cap:
        flash_attention(q, q, q, causal=True)
    hits = [m for m in cap.messages() if "falling back" in m]
    assert len(hits) == 1 and "128-aligned" in hits[0]


_EMPTY_ROW = np.array([  # q-block 1 sees nothing; k-block 3 is seen by none
    [1, 0, 0, 0],
    [0, 0, 0, 0],
    [1, 1, 1, 0],
    [0, 1, 0, 0],
])


@pytest.mark.parametrize("case", [
    dict(),
    dict(alibi=True, seg=True),
    dict(H=4, KV=2),
    dict(S=512, bwd=(256, 128)),
    dict(S=512, bwd=(128, 256), alibi=True),
    dict(H=1, D=256),
    dict(S=512, layout=_EMPTY_ROW),
    dict(S=512, layout=_EMPTY_ROW, causal=False, alibi=True),
], ids=["plain", "alibi-segments", "gqa", "bwd-tiles-256x128",
        "bwd-tiles-128x256-alibi", "hd256", "empty-row-causal",
        "empty-row-noncausal-alibi"])
def test_flat_walk_bitmatches_dense_grid(case):
    """A static layout's kernels walk its live tiles, a row's in the dense
    grid's ascending order, so out, lse, dq, dk, dv are the dense grid's bit
    for bit: the causal triangle against ``tables=None`` (the in-kernel
    predicate), a block-sparse layout with an empty row against the dense
    grid under a 0 / NEG_INF block bias (a masked tile adds exact zeros)."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    S, H, D = case.get("S", 256), case.get("H", 2), case.get("D", 64)
    causal, layout = case.get("causal", True), case.get("layout")
    bq = bk = 128
    bqb, bkb = case.get("bwd", (bq, bk))
    q, k, v = _qkv(jax.random.PRNGKey(21), B=1, S=S, H=H,
                   KV=case.get("KV"), D=D)
    do = jax.random.normal(jax.random.PRNGKey(22), q.shape, q.dtype)
    slopes = (jnp.asarray([2.0 ** -(i + 1) for i in range(H)], jnp.float32)
              if case.get("alibi") else None)
    seg = _segments(1, S) if case.get("seg") else None
    qt, kt, vt, dot = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, do))

    bias = None
    if layout is None:
        fwd_layout = fa.causal_layout(S, bq, bk)
        bwd_layout = fa.causal_layout(S, bqb, bkb)
    else:
        tok = np.kron(layout, np.ones((bq, bk)))
        bias = jnp.where(jnp.asarray(tok) > 0, 0.0, fa.NEG_INF)[None, None]
        fwd_layout = bwd_layout = (
            layout & fa.causal_layout(S, bq, bk) if causal else layout)

    def run(fwd_walk, bwd_walks, bias):
        kw = dict(causal=causal, scale=D ** -0.5, interpret=True)
        out, lse = fa._flash_fwd(qt, kt, vt, bias, seg, slopes, fwd_walk,
                                 block_q=bq, block_k=bk, **kw)
        dq, dk, dv, _ = fa._flash_bwd(qt, kt, vt, out, lse, dot, bias, seg,
                                      slopes, bwd_walks, block_q=bqb,
                                      block_k=bkb, **kw)
        return out, lse, dq, dk, dv

    got = run(fa.flat_walk(fwd_layout),
              (fa.flat_walk(bwd_layout), fa.flat_walk(bwd_layout, by_col=True)),
              None)
    want = run(None, None, bias)
    for g, w, name in zip(got, want, ("out", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    if layout is not None:  # the empty row's block is written, as zeros
        assert not np.asarray(got[0][:, :, bq:2 * bq]).any()
        assert (np.asarray(got[1][:, :, bq:2 * bq]) == fa.NEG_INF).all()
        assert not np.asarray(got[3][:, :, 3 * bk:]).any()

    # and the public entry takes that walk: the same out and grads
    out, vjp = jax.vjp(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, alibi_slopes=slopes, segment_ids=seg,
            block_mask=layout, block_q=bq, block_k=bk, block_q_bwd=bqb,
            block_k_bwd=bkb), q, k, v)
    for g, w, name in zip((out, *vjp(do)), (got[0], *got[2:]),
                          ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(jnp.swapaxes(w, 1, 2)), err_msg=name)


def test_flash_calls_walk_the_live_tiles():
    """What engages is read off the traced program: a causal call's three
    kernels run over (B, H, live tiles), a dense bias or a non-causal call
    keeps the dense (B, H, nq, nk)."""
    from deepspeed_tpu.analysis.shardlint import pallas_grids
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    q = jax.ShapeDtypeStruct((2, 2048, 4, 64), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((2, 4, 2048, 2048), jnp.bfloat16)

    def grids(**kw):
        has_bias = kw.pop("has_bias", False)

        def loss(q, b):
            return flash_attention(q, q, q, bias=b if has_bias else None,
                                   **kw).astype(jnp.float32).sum()

        return pallas_grids(jax.make_jaxpr(jax.grad(loss))(q, bias).jaxpr)

    assert fa.walk_steps(fa.causal_layout(2048, 512, 512)) == (10, 10)
    assert grids(causal=True) == [
        ("_fwd_kernel", (2, 4, 10)), ("_bwd_dq_kernel", (2, 4, 10)),
        ("_bwd_dkv_kernel", (2, 4, 10))]
    # GLM's 4,096 rows: 36 of 64 tiles; distinct backward tiles: their own
    assert fa.walk_steps(fa.causal_layout(4096, 512, 512)) == (36, 36)
    assert [g for _, g in grids(causal=True, block_q_bwd=1024)] == [
        (2, 4, 10), (2, 4, 6), (2, 4, 6)]
    assert {g for _, g in grids(causal=False)} == {(2, 4, 4, 4)}
    assert {g for _, g in grids(causal=True, has_bias=True)} == {(2, 4, 4, 4)}


def test_walk_past_the_scalar_memory_takes_the_dense_grid(monkeypatch):
    """The list rides scalar prefetch: a causal triangle whose list would
    pass ``WALK_MAX_STEPS`` keeps the dense grid and its in-kernel predicate
    (same numbers), a block-sparse layout that long falls back to XLA and
    says why."""
    from deepspeed_tpu.analysis.shardlint import pallas_grids
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.utils import logging as logging_mod

    q, k, v = _qkv(jax.random.PRNGKey(23), B=1, S=512, H=2, D=64)
    call = lambda **kw: flash_attention(q, k, v, causal=True, block_q=128,
                                        block_k=128, **kw)
    want = call()
    monkeypatch.setattr(fa, "WALK_MAX_STEPS", 13)  # the triangle: 10 + 4
    grids = pallas_grids(jax.make_jaxpr(call)().jaxpr)
    assert grids == [("_fwd_kernel", (1, 2, 4, 4))]
    np.testing.assert_array_equal(np.asarray(call()), np.asarray(want))
    logging_mod.fallback_log_seen.clear()
    layout = np.tril(np.ones((4, 4), np.int32))
    np.testing.assert_allclose(np.asarray(call(block_mask=layout)),
                               np.asarray(want), atol=2e-5)
    reasons = [r for key in logging_mod.fallback_log_seen for r in key[1]]
    assert any("scalar memory" in r for r in reasons), reasons


@pytest.mark.parametrize("causal", [True, False])
def test_bwd_tiles_independent_of_fwd_tiles(causal):
    """dq/dkv kernels accept their own tile sizes (the causal triangle's
    tile lists are built at bwd granularity too): grads must be identical to the
    symmetric-tile run."""
    q, k, v = _qkv(jax.random.PRNGKey(22), B=1, S=256, H=2, D=64)

    def loss(bqb, bkb):
        def f(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=128, block_k=256,
                block_q_bwd=bqb, block_k_bwd=bkb) ** 2)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)

    base = loss(0, 0)           # inherit fwd tiles (128, 256)
    asym = loss(256, 128)       # bwd q-tile 2x fwd, bwd k-tile HALF fwd —
    # both directions of the causal-table rebuild covered
    for g0, g1 in zip(base, asym):
        np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                                   atol=2e-5)


def test_bwd_tiles_scope_and_config():
    """The scoped override carries the bwd pair, and a user block_mask pins
    bwd tiles to the layout granularity (grads still match the masked
    reference)."""
    from deepspeed_tpu.ops.pallas.flash_attention import block_sizes_scope

    q, k, v = _qkv(jax.random.PRNGKey(23), B=1, S=256, H=2, D=64)

    def g(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    base = jax.jit(jax.grad(g))(q, k, v)
    with block_sizes_scope(128, 128, 256, 128):
        scoped = jax.jit(jax.grad(g))(q, k, v)
    np.testing.assert_allclose(np.asarray(base), np.asarray(scoped),
                               atol=2e-5)

    # block_mask path: bwd tiles silently pinned to the mask granularity
    mask = np.tril(np.ones((2, 2), np.int32))
    def gm(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_mask=mask,
            block_q=128, block_k=128, block_q_bwd=64, block_k_bwd=64) ** 2)
    out = jax.jit(jax.grad(gm))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=2e-5)


# ---------------------------------------------------------------------------
# PR 33: positions are a [bq, 1] column and a [1, bk] row; causal ALiBi is a
# column plus a row. Tiles wholly below the diagonal, tiles it crosses and
# skipped tiles all ride one body
# ---------------------------------------------------------------------------
F32_TOL = dict(atol=1e-5, rtol=1e-5)
# bf16 operands, fp32 scores/softmax, bf16 probabilities into PV: a bf16 ulp
# of an O(1) output or gradient is 8e-3
BF16_TOL = dict(atol=4e-2, rtol=4e-2)


def _out_and_grads(fn, q, k, v, seed=99):
    """(out, dq, dk, dv) as float32 numpy, under one fixed cotangent."""
    out, vjp = jax.vjp(fn, q, k, v)
    cot = jax.random.normal(jax.random.PRNGKey(seed), out.shape, out.dtype)
    return [np.asarray(x, np.float32) for x in (out, *vjp(cot))]


def _assert_all_close(got, want, tol):
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, err_msg=name, **tol)


@pytest.mark.parametrize("seg", [False, True], ids=["noseg", "seg"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hd", [64, 128])
def test_causal_alibi_over_every_kind_of_tile(hd, dtype, seg):
    """4 x 4 tiles (S 512, tiles 128): 6 wholly below the diagonal, 4 the
    diagonal crosses, 6 skipped a head; forward and all three gradients
    against the dense reference. The slopes are powers of two
    (``alibi_slopes(4)``), so column + row is exact."""
    from deepspeed_tpu.models.transformer import alibi_slopes as make_slopes

    H = 4
    q, k, v = _qkv(jax.random.PRNGKey(33), B=1, S=512, H=H, D=hd, dtype=dtype)
    kw = dict(causal=True, alibi_slopes=jnp.asarray(make_slopes(H)),
              segment_ids=_segments(1, 512) if seg else None)
    got = _out_and_grads(
        lambda *a: flash_attention(*a, block_q=128, block_k=128, **kw),
        q, k, v)
    want = _out_and_grads(lambda *a: xla_attention(*a, **kw), q, k, v)
    _assert_all_close(got, want, F32_TOL if dtype == jnp.float32 else BF16_TOL)


def test_causal_alibi_column_plus_row_rounding():
    """Slopes that are no power of two (BLOOM's 16 heads have eight): the
    column and the row are rounded products of up to slope x 511, so a score
    carries their float32 rounding (1.5e-5 at 0.707 x 511) where the
    |qpos - kpos| form rounded a small product near the diagonal."""
    slopes = jnp.asarray([0.70710678, 0.3, 0.0442, 0.011], jnp.float32)
    q, k, v = _qkv(jax.random.PRNGKey(34), B=1, S=512, H=4, D=64)
    kw = dict(causal=True, alibi_slopes=slopes)
    got = _out_and_grads(
        lambda *a: flash_attention(*a, block_q=128, block_k=128, **kw),
        q, k, v)
    want = _out_and_grads(lambda *a: xla_attention(*a, **kw), q, k, v)
    _assert_all_close(got, want, dict(atol=1e-4, rtol=1e-4))


@pytest.mark.parametrize("hd", [64, 128])
def test_noncausal_alibi_abs_branch(hd):
    """Without ``causal`` keys lie on both sides of a query: the static
    ``abs`` form."""
    from deepspeed_tpu.models.transformer import alibi_slopes as make_slopes

    q, k, v = _qkv(jax.random.PRNGKey(35), B=1, S=256, H=4, D=hd)
    kw = dict(causal=False, alibi_slopes=jnp.asarray(make_slopes(4)))
    got = _out_and_grads(
        lambda *a: flash_attention(*a, block_q=128, block_k=128, **kw),
        q, k, v)
    want = _out_and_grads(lambda *a: xla_attention(*a, **kw), q, k, v)
    _assert_all_close(got, want, F32_TOL)


@pytest.mark.parametrize("causal", [True, False])
def test_alibi_over_block_sparse_layout(causal):
    """A block-sparse layout decides which tiles run, the block indices where
    each lies: ALiBi and the causal compare ride the compacted grid."""
    from deepspeed_tpu.models.transformer import alibi_slopes as make_slopes

    S, blk = 512, 128
    layout = np.array([[1, 0, 0, 1],
                       [1, 1, 0, 0],
                       [0, 1, 1, 0],
                       [1, 0, 1, 1]], np.int32)
    q, k, v = _qkv(jax.random.PRNGKey(36), B=1, S=S, H=4, D=64)
    slopes = jnp.asarray(make_slopes(4))
    tok = np.repeat(np.repeat(layout, blk, axis=0), blk, axis=1)
    mask_bias = jnp.where(jnp.asarray(tok) > 0, 0.0, -1e30)[None, None]
    got = _out_and_grads(
        lambda *a: flash_attention(*a, causal=causal, alibi_slopes=slopes,
                                   block_mask=layout, block_q=blk,
                                   block_k=blk),
        q, k, v)
    want = _out_and_grads(
        lambda *a: xla_attention(*a, causal=causal, alibi_slopes=slopes,
                                 bias=mask_bias),
        q, k, v)
    _assert_all_close(got, want, F32_TOL)


@pytest.mark.parametrize("S,bq,bk,want", [
    (2048, 512, 512, [1, 2, 3, 4]),   # both BLOOM cells: 10 of 16 tiles
    (2048, 512, 1024, [1, 1, 2, 2]),
    (2048, 1024, 512, [2, 4]),
    (512, 128, 128, [1, 2, 3, 4]),
    (512, 512, 512, [1]),
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, list) else str(x))
def test_causal_walk_by_hand(S, bq, bk, want):
    """The causal layout the wrapper walks: ``_block_visible`` over the
    block grid is the lower block triangle; the forward and dq list row r's
    ``want[r]`` visible k-blocks in order, flagged first and last, and no
    other step; dk/dv lists a k-block's q-blocks from the first that reaches
    it."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    layout = fa.causal_layout(S, bq, bk)
    assert layout.sum(axis=1).tolist() == want
    word = lambda qi, ki, first, last: (
        qi << fa._WALK_QI_SHIFT | ki << fa._WALK_KI_SHIFT | fa._WALK_LIVE
        | last * fa._WALK_LAST | first * fa._WALK_FIRST)
    assert fa.flat_walk(layout).tolist() == [
        word(r, c, c == 0, c == n - 1)
        for r, n in enumerate(want) for c in range(n)]
    assert fa.walk_steps(layout) == (sum(want), sum(want))
    # the dk/dv kernel walks by column: k-block c is seen by every q-block
    # from the first that reaches it to the last
    nq = S // bq
    firsts = [int(np.argmax(layout[:, c])) for c in range(S // bk)]
    assert fa.flat_walk(layout, by_col=True).tolist() == [
        word(r, c, r == f, r == nq - 1)
        for c, f in enumerate(firsts) for r in range(f, nq)]
    assert fa.walk_steps(layout, by_col=True) == (sum(want), sum(want))


@pytest.mark.parametrize("bq,bk", [(4, 4), (4, 8), (8, 4)])
def test_block_visible_against_brute_force(bq, bk):
    """``_block_visible`` over a grid of block indices and ring-hop offsets,
    against the position mask written out."""
    from deepspeed_tpu.ops.pallas.flash_attention import _block_visible

    for qi in range(4):
        for ki in range(4):
            for qoff in (0, 3, 8, 17):
                for koff in (0, 5, 8, 32):
                    qpos = qi * bq + qoff + np.arange(bq)[:, None]
                    kpos = ki * bk + koff + np.arange(bk)[None, :]
                    sees = qpos >= kpos
                    at = (qi, ki, qoff, koff)
                    assert _block_visible(qi, ki, bq, bk, qoff, koff) == \
                        sees.any(), at
