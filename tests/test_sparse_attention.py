"""Block-sparse attention vs masked-dense oracle (SURVEY §2.4; reference
csrc/sparse_attention + deepspeed/ops/sparse_attention). CPU interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.sparse_attention import (
    BigBirdSparsityConfig,
    BSLongformerSparsityConfig,
    DenseSparsityConfig,
    FixedSparsityConfig,
    VariableSparsityConfig,
    causal_trim,
    dense_blocksparse_reference,
    sparse_attention,
)


def _qkv(seed, B=2, S=512, H=2, D=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (B, S, H, D)),
        jax.random.normal(ks[1], (B, S, H, D)),
        jax.random.normal(ks[2], (B, S, H, D)),
    )


CONFIGS = [
    DenseSparsityConfig(block=128),
    FixedSparsityConfig(block=128, num_local_blocks=2, num_global_blocks=1),
    BigBirdSparsityConfig(block=128, num_sliding_window_blocks=3,
                          num_global_blocks=1, num_random_blocks=1),
    BSLongformerSparsityConfig(block=128, num_sliding_window_blocks=3,
                               global_block_indices=[0]),
    VariableSparsityConfig(block=128, local_window_blocks=[1, 2],
                           global_block_indices=[0], num_random_blocks=1),
]


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: type(c).__name__)
@pytest.mark.parametrize("causal", [True, False])
def test_sparse_matches_masked_dense(cfg, causal):
    q, k, v = _qkv(0)
    out = sparse_attention(q, k, v, cfg, causal=causal)
    layout = cfg.make_layout(512)
    if causal:
        layout = causal_trim(layout)
    ref = dense_blocksparse_reference(q, k, v, layout, cfg.block, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sparse_grads_match_masked_dense():
    cfg = FixedSparsityConfig(block=128, num_local_blocks=2, num_global_blocks=1)
    q, k, v = _qkv(1, B=1, S=256)
    layout = causal_trim(cfg.make_layout(256))

    g_sp = jax.grad(
        lambda *a: jnp.sum(sparse_attention(*a, cfg, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda *a: jnp.sum(
            dense_blocksparse_reference(*a, layout, cfg.block, causal=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gs, gr, name in zip(g_sp, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gs), np.asarray(gr), atol=5e-4, err_msg=f"d{name}"
        )


def test_layout_shapes_and_validation():
    cfg = FixedSparsityConfig(block=128, num_local_blocks=2)
    assert cfg.make_layout(512).shape == (4, 4)
    with pytest.raises(ValueError):
        cfg.make_layout(500)  # not block-divisible

    # kernel rejects a mismatched mask table
    q, k, v = _qkv(2, S=256)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_mask=np.ones((3, 3)), block_q=128,
                        block_k=128)


def test_fixed_layout_is_causal_friendly():
    """Every query block sees its own diagonal block (softmax never empty)."""
    for cfg in CONFIGS:
        layout = causal_trim(cfg.make_layout(512))
        assert (np.diag(layout) == 1).all(), type(cfg).__name__


def test_engine_sparse_attention_config(devices8, monkeypatch):
    """ds_config "sparse_attention" drives the train step: the flash kernel
    receives a block mask and training converges."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    import deepspeed_tpu.ops.pallas.flash_attention as fa
    from deepspeed_tpu.models import llama

    masks_seen = []
    orig = fa.flash_attention

    def spy(q, k, v, **kw):
        masks_seen.append(kw.get("block_mask") is not None)
        return orig(q, k, v, **kw)

    # the real sparse_attention imports flash_attention from the module at
    # call time, so this spy observes the genuine engine → sparse → kernel
    # path (no reimplementation in the test)
    monkeypatch.setattr(fa, "flash_attention", spy)

    comm.destroy_process_group()
    model = llama("llama-tiny", vocab_size=128, max_seq_len=256,
                  hidden_size=64, num_layers=2, num_heads=2, num_kv_heads=2,
                  intermediate_size=128)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 1000,
            "sparse_attention": {"mode": "fixed", "block": 128,
                                 "num_local_blocks": 1,
                                 "num_global_blocks": 1},
        },
        rng=jax.random.PRNGKey(0),
    )
    data = {"input_ids": np.random.RandomState(0).randint(0, 128, size=(8, 256))}
    losses = [float(engine.train_batch(batch=data)) for _ in range(10)]
    assert masks_seen and all(masks_seen), "block mask never reached the kernel"
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_sparse_attention_config_validation():
    import pytest as _pytest

    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError

    with _pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "sparse_attention": {"mode": "wat"}})
    with _pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "sparse_attention": {"mode": "fixed"},
                         "sequence_parallel": {"sp_size": 2}})
    with _pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "sparse_attention": {"mode": "fixed"},
                         "data_efficiency": {"data_routing": {"random_ltd": {
                             "enabled": True}}}})


def _unpack(walk):
    """A flat walk's words as (qi, ki, first, last, live) tuples."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    return [(w >> fa._WALK_QI_SHIFT,
             (w >> fa._WALK_KI_SHIFT) & (fa._WALK_MAX_BLOCKS - 1),
             bool(w & fa._WALK_FIRST), bool(w & fa._WALK_LAST),
             bool(w & fa._WALK_LIVE)) for w in walk.tolist()]


def test_flat_walk_lists_live_tiles_and_one_dead_entry_an_empty_row():
    """The kernels' walk of a static layout: the live tiles in row-major
    order (by column for dk/dv), each saying whether it opens and closes its
    output block's run; a row with no live tile keeps one dead entry so its
    output block is still written."""
    import numpy as np

    from deepspeed_tpu.ops.pallas.flash_attention import flat_walk, walk_steps

    layout = np.array([
        [1, 0, 1, 0],
        [0, 0, 0, 0],
        [1, 1, 1, 1],
        [0, 1, 0, 0],
    ])
    F, T = False, True
    assert _unpack(flat_walk(layout)) == [
        (0, 0, T, F, T), (0, 2, F, T, T),
        (1, 0, T, T, F),  # empty row: first, last, computes nothing
        (2, 0, T, F, T), (2, 1, F, F, T), (2, 2, F, F, T), (2, 3, F, T, T),
        (3, 1, T, T, T),
    ]
    assert walk_steps(layout) == (8, 7)  # 16 steps on the padded table
    # dk/dv: a k-block's q-blocks ascending, (qi, ki) still in that order
    assert _unpack(flat_walk(layout, by_col=True)) == [
        (0, 0, T, F, T), (2, 0, F, T, T),
        (2, 1, T, F, T), (3, 1, F, T, T),
        (0, 2, T, F, T), (2, 2, F, T, T),
        (2, 3, T, T, T),
    ]
    assert walk_steps(layout, by_col=True) == (7, 7)


def test_sparse_grid_is_the_live_tiles():
    """The kernel grid's last dim IS the layout's live tiles: fewer than the
    padded table's rows x densest row, far fewer than the dense grid — the
    structural evidence that masked tiles are skipped, not just predicated."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.analysis.shardlint import pallas_grids
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_attention,
        walk_steps,
    )

    cfg = BSLongformerSparsityConfig(block=128, num_sliding_window_blocks=3)
    S = 128 * 16
    layout = causal_trim(cfg.make_layout(S))
    nq = nk = S // 128
    steps, live = walk_steps(layout)
    assert steps == live == int(layout.sum())
    jmax = int(layout.sum(axis=1).max())
    assert jmax <= 2 + 1 + 1  # window(2 causal) + global col + row
    assert live < nq * jmax < nq * nk, (live, jmax)
    # and the lowered call walks exactly that list, forward and backward
    q = jax.ShapeDtypeStruct((1, S, 2, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: flash_attention(
        q, q, q, causal=True, block_mask=layout, block_q=128,
        block_k=128).sum()))(q)
    grids = [g for _, g in pallas_grids(jaxpr.jaxpr)]
    assert grids == [(1, 2, live)] * 3, grids


def test_traced_block_mask_falls_back_with_reason():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import flash_attention as fa_mod
    from deepspeed_tpu.utils import logging as logging_mod
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    logging_mod.fallback_log_seen.clear()
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (1, 256, 2, 64))
    # non-trivial layout: dropping it would NOT reproduce dense attention
    layout = np.array([[1, 0], [0, 1]], np.int32)

    @jax.jit
    def run(q, mask):
        return flash_attention(q, q, q, causal=True, block_mask=mask,
                               block_q=128, block_k=128)

    out = run(q, jnp.asarray(layout))  # mask is a tracer inside jit
    ref = dense_blocksparse_reference(q, q, q, layout, 128, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    reasons = [r for key in logging_mod.fallback_log_seen
               for r in key[1]]
    assert any("trace-time static" in r for r in reasons), reasons


# ------------------- the serving walks that share decode_attention's helper
# sparse_paged_attention, sparse_attention / latent_attention and
# block_sparse_attention fold every key tile through
# decode_attention._tile_update, whose running max and sum are [rows, 128]
# lane-replicated and meet the scores and the accumulator through
# flash_attention._lanes_to. Each walk against its own float32 dense lines
# at the widths that helper branches on: a key block of 128, 256 or 512
# (whole vregs) or 192 (no multiple of the lanes), set by the pages a slot
# may hold; accumulators of 64, 128 and 512 lanes. The selection is given,
# not searched: a key is chosen where its score is positive (``thr`` the
# sort key of 0.0, no tie taken), so about half of every tile is masked and
# some rows have no chosen key in their first tile.
WALK_PS = 16
WALK_PAGES = {128: 8, 192: 12, 256: 16, 512: 72}  # block_k -> pages a slot


def _walk_operands(seed, mp, row, B=4, S=16, L=2):
    """Pools ``[L, P+1, 16, *row]`` under a shuffled table, frontiers that
    end inside a block, and the four kinds of slot: a chunk deep in its
    context, a prompt from empty, an idle slot, a decoding one."""
    from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla

    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    P, cap = B * mp, mp * WALK_PS
    bk = WALK_PS * min(sla.BLOCK_K // WALK_PS, mp)
    cl = np.asarray([cap - 3 * S, 0, cap // 2, cap - S - 5], np.int32)
    nn = jnp.asarray([S, S - 7, 0, 1], jnp.int32)
    scores = r.normal(size=(B, -(-cap // bk), S, bk))
    # a row's own key is always chosen (a row that chose nothing is zeros
    # in the walks and the mean of every value in the dense lines)
    own = cl[:, None] + np.arange(S)[None]
    scores[np.arange(B)[:, None], own // bk, np.arange(S)[None],
           own % bk] = 1.0
    scores, cl = jnp.asarray(scores, jnp.float32), jnp.asarray(cl)
    zero_key = sla._sort_key(jnp.zeros((B, S), jnp.float32))
    seen = jnp.arange(cap)[None, None] <= (
        cl[:, None] + jnp.arange(S)[None])[..., None]
    return dict(
        k=f(L, P + 1, WALK_PS, *row), v=f(L, P + 1, WALK_PS, *row),
        pt=jnp.asarray(r.permutation(P).reshape(B, mp), jnp.int32),
        cl=cl, nn=nn, scores=scores, thr=zero_key,
        tie=jnp.full((B, S), -1, jnp.int32), seen=seen,
        chosen=(sla.unblocked(scores)[:, :, :cap] > 0) & seen)


def _view(pool, layer, pt):
    from deepspeed_tpu.models.decoding import _paged_gather

    return _paged_gather(pool[layer], pt)


def _real_rows_match(got, want, nn, tol):
    """Real rows to ``tol``; padded rows finite, an idle slot's zeros."""
    got, want = np.asarray(got, np.float32), np.asarray(want)
    for b, n in enumerate(np.asarray(nn)):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=tol,
                                   rtol=tol)
    assert np.isfinite(got).all() and not got[nn == 0].any()


@pytest.mark.parametrize("block_k", [128, 192, 256, 512])
def test_sparse_paged_walk_at_every_key_block_width(block_k):
    from deepspeed_tpu.ops.pallas import sparse_paged_attention as spa

    H, KV, hd, layer = 4, 2, 128, 1
    o = _walk_operands(block_k, WALK_PAGES[block_k], (KV, hd))
    assert o["scores"].shape[-1] == block_k
    q = jnp.asarray(np.random.default_rng(1).normal(size=(4, 16, H, hd)),
                    jnp.float32)
    got = spa.sparse_paged_attention_kernel(
        q, o["k"], o["v"], o["scores"], o["thr"], o["tie"], o["cl"],
        o["pt"], layer=layer, num_new=o["nn"], interpret=True)
    want = spa.dense_sparse_paged_attention(
        q, _view(o["k"], layer, o["pt"]), _view(o["v"], layer, o["pt"]),
        o["chosen"])
    _real_rows_match(got, want, o["nn"], 2e-5)


@pytest.mark.parametrize("selected", [True, False],
                         ids=["selected", "every-key"])
@pytest.mark.parametrize("block_k,width,v_width", [
    (128, 128, 64), (192, 128, 128), (256, 640, 512), (512, 640, 512)])
def test_latent_walk_at_every_key_block_and_value_width(block_k, width,
                                                        v_width, selected):
    """The latent walks (a row is key and, in its first ``v_width`` lanes,
    value): accumulators of 64 lanes (a prefix of the statistics' vreg),
    128 and 512 (tiled), with a selection (``sparse_latent_attention``) and
    over every key (``latent_attention``)."""
    from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla

    H, layer, scale = 2, 1, 0.07
    o = _walk_operands(block_k + v_width, WALK_PAGES[block_k], (width,))
    q = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16, H, width)),
                    jnp.float32)
    sel = (o["scores"], o["thr"], o["tie"]) if selected else (None,) * 3
    got = sla.sparse_attention(
        q, o["k"], *sel, o["cl"], o["pt"], layer=layer, scale=scale,
        v_width=v_width, num_new=o["nn"], interpret=True)
    want = sla.dense_sparse_attention(
        q, _view(o["k"], layer, o["pt"]),
        o["chosen"] if selected else o["seen"], scale, v_width)
    _real_rows_match(got, want, o["nn"], 1e-5)


@pytest.mark.parametrize("block_k", [128, 256, 512])
def test_block_sparse_walk_at_every_key_block_width(block_k):
    """The block walk under GIVEN flags (one in three blocks kept beside a
    row's own, the first not forced: a row may keep nothing in its first
    trip, and its running max is NEG_INF until a later one), a trip of 2, 4
    or 8 selected blocks of 64 tokens."""
    from deepspeed_tpu.ops.pallas import block_sparse_attention as bsa

    H, KV, hd, layer, S = 4, 2, 128, 1, 16
    mp = WALK_PAGES[block_k]
    geom = bsa.BlockSparse(kernel_stride=WALK_PS)
    o = _walk_operands(block_k, mp, (KV, hd))
    assert WALK_PS * bsa._attention_trip(WALK_PS, mp, geom) == block_k
    r = np.random.default_rng(3)
    q = jnp.asarray(r.normal(size=(4, S, H, hd)), jnp.float32)
    chunks = bsa._padded_blocks(geom, mp * WALK_PS) // 128
    kept = r.random((4, KV, chunks, S, 128)) < 1 / 3
    # a row's own block is kept (a row that kept nothing is zeros in the
    # walk and the mean of every value in the dense lines)
    own = (np.asarray(o["cl"])[:, None] + np.arange(S)[None]) // 64
    kept[np.arange(4)[:, None], :, own // 128, np.arange(S)[None],
         own % 128] = True
    kept = jnp.asarray(kept, jnp.float32)
    got = bsa.block_sparse_attention(
        q, o["k"], o["v"], kept, o["cl"], o["pt"], layer=layer, geom=geom,
        num_new=o["nn"], interpret=True)
    positions = o["cl"][:, None] + jnp.arange(S)[None]
    want = bsa.dense_block_attention(
        q, _view(o["k"], layer, o["pt"]), _view(o["v"], layer, o["pt"]),
        bsa.unchunked(kept)[..., :geom.blocks(mp * WALK_PS)], positions,
        geom)
    _real_rows_match(got, want, o["nn"], 2e-5)
