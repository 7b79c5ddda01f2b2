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
