"""MoE gating/dispatch tests. Parity model: reference tests/unit/moe/test_moe.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.sharded_moe import top_k_gating
from deepspeed_tpu.models import make_lm_batch, mixtral


def test_capacity_never_exceeded():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(rng, (64, 4))
    dispatch, combine, metrics = top_k_gating(logits, top_k=2, capacity=8, rng=None, train=True)
    per_expert = np.asarray(dispatch.sum(axis=(0, 2)))
    assert (per_expert <= 8).all()
    # each (expert, slot) holds at most one token
    slot_fill = np.asarray(dispatch.sum(axis=0))
    assert (slot_fill <= 1.0 + 1e-6).all()


def test_combine_weights_normalized():
    rng = jax.random.PRNGKey(1)
    logits = jax.random.normal(rng, (32, 4))
    dispatch, combine, _ = top_k_gating(logits, top_k=2, capacity=32, rng=None, train=True)
    sums = np.asarray(combine.sum(axis=(1, 2)))
    # ample capacity => every token fully routed, weights sum to 1
    np.testing.assert_allclose(sums, np.ones(32), atol=1e-5)


def test_top1_routes_to_argmax():
    logits = jnp.eye(4, dtype=jnp.float32) * 10.0  # token i loves expert i
    dispatch, combine, _ = top_k_gating(logits, top_k=1, capacity=4, rng=None, train=True)
    routed = np.asarray(dispatch.sum(axis=2))  # [N, E]
    np.testing.assert_allclose(routed, np.eye(4))


def test_aux_loss_uniform_vs_skewed():
    n = 128
    rng = jax.random.PRNGKey(2)
    uniform = jax.random.normal(rng, (n, 4)) * 0.01
    skewed = jnp.concatenate([jnp.full((n, 1), 5.0), jnp.full((n, 3), -5.0)], axis=1)
    _, _, m_u = top_k_gating(uniform, 1, n, None, True)
    _, _, m_s = top_k_gating(skewed, 1, n, None, True)
    # balanced routing => aux ~1; collapsed routing => aux ~E
    assert float(m_u["aux_loss"]) < float(m_s["aux_loss"])
    assert abs(float(m_u["aux_loss"]) - 1.0) < 0.2
    assert abs(float(m_s["aux_loss"]) - 4.0) < 0.2


def test_drop_fraction_with_tight_capacity():
    logits = jnp.zeros((64, 2))  # all tokens tie; capacity forces drops
    dispatch, _, metrics = top_k_gating(logits, top_k=1, capacity=4, rng=None, train=True)
    assert float(metrics["drop_fraction"]) > 0.8


def test_mixtral_trains_one_step():
    m = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=32)
    rng = jax.random.PRNGKey(0)
    params = m.init(rng)
    batch = make_lm_batch(jax.random.randint(rng, (2, 16), 0, 64))
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: m.loss(p, batch, rng=rng), has_aux=True
    )(params)
    assert np.isfinite(float(loss))
    assert float(metrics["moe_aux_loss"]) > 0
    router_g = grads["layers"]["mlp"]["router"]
    assert float(jnp.sum(jnp.abs(router_g))) > 0  # router learns


def test_residual_moe_trains_and_differs():
    """Residual/PR-MoE (reference: deepspeed/moe/layer.py use_residual):
    dense branch + learned coefficient must be present, trained, and change
    the output vs plain MoE."""
    m = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=32,
                moe_use_residual=True)
    rng = jax.random.PRNGKey(0)
    params = m.init(rng)
    mlp = params["layers"]["mlp"]
    assert {"res_wi", "res_wo", "res_wg", "coef"} <= set(mlp)
    assert m.num_params() == sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)
    )
    batch = make_lm_batch(jax.random.randint(rng, (2, 16), 0, 64))
    (loss, _), grads = jax.value_and_grad(
        lambda p: m.loss(p, batch, rng=rng), has_aux=True
    )(params)
    assert np.isfinite(float(loss))
    g = grads["layers"]["mlp"]
    assert float(jnp.sum(jnp.abs(g["res_wi"]))) > 0
    assert float(jnp.sum(jnp.abs(g["coef"]))) > 0

    # the dense branch must actually be mixed into the output: zeroing its
    # weights has to change the logits
    logits, _ = m.apply(params, batch["input_ids"])
    ablated = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy tree
    ablated["layers"] = dict(ablated["layers"])
    ablated["layers"]["mlp"] = dict(ablated["layers"]["mlp"])
    ablated["layers"]["mlp"]["res_wi"] = jnp.zeros_like(mlp["res_wi"])
    logits2, _ = m.apply(ablated, batch["input_ids"])
    assert float(jnp.max(jnp.abs(logits - logits2))) > 1e-4

    # specs tree matches the params tree (engine sharding requirement)
    specs = m.partition_specs()
    assert jax.tree_util.tree_structure(specs) == jax.tree_util.tree_structure(
        jax.tree.map(lambda _: 0, params)
    )


def test_residual_moe_convergence_smoke():
    m = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=32,
                moe_use_residual=True)
    rng = jax.random.PRNGKey(1)
    params = m.init(rng)
    import optax

    tx = optax.adam(3e-3)
    opt = tx.init(params)
    batch = make_lm_batch(jax.random.randint(rng, (4, 16), 0, 64))

    @jax.jit
    def step(params, opt):
        (loss, _), grads = jax.value_and_grad(
            lambda p: m.loss(p, batch, rng=rng), has_aux=True
        )(params)
        upd, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, upd), opt, loss

    losses = []
    for _ in range(30):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_moe_aux_loss_in_step_metrics(devices8):
    """The train step surfaces model metrics (reference: MoE aux loss is
    visible in DeepSpeed's step logging/monitor)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import mixtral

    model = mixtral(
        "mixtral-tiny", vocab_size=256, max_seq_len=32, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, intermediate_size=128,
        num_experts=4, moe_top_k=2,
    )
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": 16,
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        },
    )
    engine.train_batch(
        batch={"input_ids": np.random.RandomState(0).randint(0, 256, size=(16, 32))}
    )
    m = engine._metrics
    assert {"lm_loss", "moe_aux_loss", "tokens"} <= set(m)
    assert float(m["moe_aux_loss"]) > 0
    assert float(m["tokens"]) > 0


def test_gather_dispatch_matches_einsum_dispatch():
    """moe_dispatch="gather" replaces the one-hot dispatch/combine dots
    with index gathers; outputs and gradients (tokens AND router) must
    match the einsum formulation bit-for-bit-close."""
    from deepspeed_tpu.moe.sharded_moe import moe_layer

    m_e = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=32)
    cfg_e = m_e.config
    import dataclasses

    cfg_g = dataclasses.replace(cfg_e, moe_dispatch="gather")

    rng = jax.random.PRNGKey(0)
    params = m_e.init(rng)
    # layer params are scan-stacked [L, ...]: take layer 0
    p = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (2, 16, cfg_e.hidden_size), jnp.float32)

    def run(cfg, x):
        out, aux = moe_layer(cfg, p, x, rng=None, train=True)
        return out, aux

    out_e, aux_e = run(cfg_e, x)
    out_g, aux_g = run(cfg_g, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_e),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_g), float(aux_e), rtol=1e-6)

    ge = jax.grad(lambda x: jnp.sum(run(cfg_e, x)[0] ** 2))(x)
    gg = jax.grad(lambda x: jnp.sum(run(cfg_g, x)[0] ** 2))(x)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(ge),
                               rtol=1e-4, atol=1e-4)

    def router_loss(cfg, router):
        pp = dict(p, router=router)
        out, _ = moe_layer(cfg, pp, x, rng=None, train=True)
        return jnp.sum(out ** 2)

    gre = jax.grad(lambda r: router_loss(cfg_e, r))(p["router"])
    grg = jax.grad(lambda r: router_loss(cfg_g, r))(p["router"])
    np.testing.assert_allclose(np.asarray(grg), np.asarray(gre),
                               rtol=1e-4, atol=1e-4)


def test_gather_dispatch_trains_under_ep_mesh(devices8):
    """The gather formulation must GSPMD-compile and train on an ep mesh."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.comm import ParallelDims

    comm.destroy_process_group()
    topo = comm.init_distributed(dims=ParallelDims(dp=2, ep=4))
    model = mixtral(
        "mixtral-tiny", vocab_size=256, max_seq_len=32, num_experts=4,
        moe_dispatch="gather",
    )
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, topology=topo, config={
        "train_batch_size": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 2},
    })
    r = np.random.RandomState(0)
    batch = {"input_ids": r.randint(0, 256, size=(4, 16))}
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# the one-pass placement against the round loop it replaced (PR 40)
# ---------------------------------------------------------------------------
def _mask(n, kind, seed):
    """A ``valid`` mask: None, or [n] bool with the named share real."""
    if kind is None:
        return None
    if kind == "all":
        return jnp.ones((n,), bool)
    if kind == "none":
        return jnp.zeros((n,), bool)
    if kind == "one":
        return jnp.zeros((n,), bool).at[n // 3].set(True)
    return jax.random.uniform(jax.random.PRNGKey(seed), (n,)) < kind


def _logits(n, e, kind, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, e))
    if kind == "ties":  # a handful of distinct values: ties in every row
        return jnp.round(x * 1.5) / 1.5
    if kind == "flat":  # every expert ties in every row
        return jnp.zeros((n, e))
    return x


def _same(got, want, what):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (what, i)
        np.testing.assert_array_equal(g, w, err_msg=f"{what}[{i}]")


# (N, E, K, capacity, mask, logits): the serving cells' shapes first
# (Mixtral's two cells [16 x 128] rows top-2 of 8, Mellum's [8 x 128] top-8
# of 64; capacity 128 is their no-drop ``eval_capacity``), then capacities
# that drop, every kind of mask, ties, and row counts that take several
# row blocks (3,000 = 3 x 1,000; 2,050 pads to 3 x 684; 16,384 = 16 x 1,024)
SOFTMAX_CASES = {
    "mixtral-cells": (2048, 8, 2, 128, 0.06, "normal"),
    "mellum-cell": (1024, 64, 8, 128, 0.12, "normal"),
    "mellum-drops": (1024, 64, 8, 6, 0.12, "normal"),
    "mixtral-drops-all-real": (2048, 8, 2, 6, None, "normal"),
    "mask-all-true": (256, 8, 2, 40, "all", "normal"),
    "mask-one-row": (256, 8, 2, 4, "one", "normal"),
    "mask-no-row": (256, 8, 2, 4, "none", "normal"),
    "ties": (512, 16, 4, 24, 0.5, "ties"),
    "every-row-ties-drops": (300, 8, 3, 7, 0.7, "flat"),
    "round-fills-the-rest": (64, 2, 2, 40, None, "flat"),
    "blocks-3": (3000, 16, 4, 20, 0.7, "normal"),
    "blocks-padded": (2050, 8, 2, 9, 0.9, "ties"),
    "n-16384": (16384, 8, 2, 16, 0.8, "normal"),
}
# (N, held, K, capacity, routed experts, first, mask): DeepSeek's cell
# ([4 x 128] rows, top-8 of 256, 16 held, room for every real token), a
# member that is not the first, capacities that drop, masks, row blocks
HELD_CASES = {
    "deepseek-cell": (512, 16, 8, 128, 256, 0, 0.25),
    "deepseek-member-3": (512, 16, 8, 128, 256, 48, 0.25),
    "deepseek-drops": (512, 16, 8, 3, 256, 0, None),
    "held-all-true": (128, 4, 2, 5, 8, 4, "all"),
    "held-one-row": (128, 4, 2, 5, 8, 0, "one"),
    "held-no-row": (128, 4, 2, 5, 8, 0, "none"),
    "held-blocks-padded": (2050, 8, 4, 11, 64, 8, 0.6),
    "held-n-16384": (16384, 8, 4, 16384, 64, 0, 0.9),
}
EINSUM_ROOM = 4_000_000  # [N, E, C] elements the einsum form is run up to


@pytest.mark.parametrize("gate,case", [
    *(("indices", c) for c in SOFTMAX_CASES),
    *(("einsum", c) for c, v in SOFTMAX_CASES.items()
      if v[0] * v[1] * v[3] <= EINSUM_ROOM),
    *(("held", c) for c in HELD_CASES),
])
def test_one_pass_placement_is_the_round_loop(gate, case):
    """``top_k_gating_indices``, ``top_k_gating`` and ``held_expert_tables``
    return, bit for bit, what the round-by-round loop they replaced returns
    (tests/moe_round_oracle.py): tables, weights, ``tokens_per_expert``,
    ``drop_fraction``, ``unrouted_tokens``; the two losses to the file's
    tolerance."""
    import moe_round_oracle as oracle
    from deepspeed_tpu.moe import sharded_moe

    seed = sum(map(ord, case))
    if gate == "held":
        N, held, K, cap, R, first, mask = HELD_CASES[case]
        idx = jnp.argsort(jax.random.uniform(jax.random.PRNGKey(seed), (N, R)),
                          axis=1)[:, :K].astype(jnp.int32)
        w = jax.random.uniform(jax.random.PRNGKey(seed + 1), (N, K))
        valid = _mask(N, mask, seed + 2)
        got = jax.jit(lambda i, w, v: sharded_moe.held_expert_tables(
            i, w, v, first, held, cap))(idx, w, valid)
        want = jax.jit(lambda i, w, v: oracle.held_expert_tables(
            i, w, v, first, held, cap))(idx, w, valid)
        _same(got, want, "held_expert_tables")
        if mask == "none":
            assert int(got[4].sum()) == 0 and int(got[5]) == 0
        return
    N, E, K, cap, mask, kind = SOFTMAX_CASES[case]
    logits, valid = _logits(N, E, kind, seed), _mask(N, mask, seed + 1)
    name = "top_k_gating_indices" if gate == "indices" else "top_k_gating"
    got = jax.jit(lambda l, v: getattr(sharded_moe, name)(
        l, K, cap, None, False, valid=v))(logits, valid)
    want = jax.jit(lambda l, v: getattr(oracle, name)(l, K, cap, v))(
        logits, valid)
    _same(got[:-1], want[:-1], name)
    gm, wm = got[-1], want[-1]
    assert set(gm) == set(wm)
    for key in ("tokens_per_expert", "routed_tokens", "drop_fraction"):
        _same(gm[key], wm[key], key)
    for key in ("aux_loss", "z_loss"):
        np.testing.assert_allclose(float(gm[key]), float(wm[key]), rtol=1e-6)
    if "drops" in case:
        assert float(gm["drop_fraction"]) > 0.0


def test_placement_of_many_rows_builds_no_square():
    """At a training caller's 16,384 tokens the ranks come block by block:
    the compiled placement holds no [N, N] array in any type, its
    temporaries are far under one's bytes, and the row block follows N."""
    from deepspeed_tpu.moe import sharded_moe

    N, E, K, cap = 16384, 8, 2, 5120
    compiled = jax.jit(lambda l: sharded_moe.top_k_gating_indices(
        l, K, cap, None, False)).lower(
            jax.ShapeDtypeStruct((N, E), jnp.float32)).compile()
    assert f"[{N},{N}]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < N * N // 8
    for n, want in ((512, 512), (1024, 1024), (1025, 513), (2048, 1024),
                    (2050, 684), (16384, 1024)):
        blocks = -(-n // sharded_moe._RANK_BLOCK)
        assert -(-n // blocks) == want
