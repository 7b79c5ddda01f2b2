"""GLM-4.7-Flash's mechanisms on the training path at a small size on the CPU,
float32, seeded weights: latent attention in its training form, a leading
dense layer, the sigmoid router that drops no token over one member's share
of the experts, a shared expert, the selection bias as engine state, and the
multi-token-prediction loss, each against the plain reference
``benchmarks/families/glm4_moe_lite.py``."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import deepspeed_tpu
from benchmarks import reference
from benchmarks.run import merged
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import glm
from deepspeed_tpu.models.transformer import (_mlp, make_lm_batch, mtp_labels)
from deepspeed_tpu.moe import sharded_moe as sm
from slot_program import jit_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
fam = reference.family("glm4_moe_lite")
S = 48


def config_file():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return glm("glm-tiny")


@pytest.fixture(scope="module")
def params(model):
    p = jit_init(model, jax.random.PRNGKey(7))
    # a selection bias large enough to decide choices (init draws 0.02)
    for stack, key in ((p["layers"], 8), (p["mtp"]["layers"], 9)):
        stack["mlp"]["sel_bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(key), stack["mlp"]["sel_bias"].shape)
    return p


@pytest.fixture(scope="module")
def shape():
    cfg = config_file()
    return fam.shape_of(merged(cfg, cfg["rehearse"]))


@pytest.fixture(scope="module")
def ids():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(3), (S,), 0, 512))


@functools.partial(jax.jit, static_argnums=0)
def program_loss(model, params, ids):
    return model.loss(params, make_lm_batch(jnp.asarray(ids)[None]),
                      dtype=F32)


# the sound reference's three losses and its gradients, a compile each and
# not one an operation
ref_losses = jax.jit(fam.losses, static_argnames=("shape",))
ref_grads = jax.jit(fam.grads, static_argnames=("shape",))


def test_the_tiny_preset_is_the_rehearsals_shape(model, shape):
    c = model.config
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd, c.ffn,
            c.vocab_size, c.num_experts, c.moe_top_k, c.tie_embeddings) == (
        shape.d, shape.layers, shape.heads, shape.kv_heads, shape.hd,
        shape.ffn, shape.vocab, shape.experts, shape.top_k, shape.tied)
    assert (c.routed_experts, c.lead_dense_layers, c.lead_dense_ffn,
            c.moe_shared_width, c.q_latent_dim, c.kv_latent_dim,
            c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim, c.mtp_layers,
            c.mtp_loss_weight, c.moe_routed_scale, c.rope_theta) == (
        shape.routed, shape.dense_layers, shape.dense_ffn, shape.shared_ffn,
        shape.q_rank, shape.kv_rank, shape.nope, shape.rope_dim, shape.v_dim,
        shape.mtp, shape.mtp_weight, shape.routed_scale, shape.rope_theta)


def test_the_published_preset_is_the_catalogs_model():
    c = glm("glm-4.7-flash").config
    assert (c.hidden_size, c.total_layers, c.lead_dense_layers, c.num_heads,
            c.hd, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim, c.q_latent_dim,
            c.kv_latent_dim, c.ffn, c.lead_dense_ffn, c.moe_shared_width,
            c.num_experts, c.moe_top_k, c.moe_groups, c.moe_routed_scale,
            c.vocab_size, c.mtp_layers, c.rope_theta, c.norm_eps,
            c.tie_embeddings, c.attn_scale_mult) == (
        2048, 47, 1, 20, 256, 192, 64, 256, 768, 512, 1536, 10240, 1536, 64,
        4, 1, 1.8, 154880, 1, 1e6, 1e-5, False, 1.0)


def test_num_params_is_the_count_of_leaves_and_the_configs_706_million(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert model.num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    eng = config_file()["engine"]["model"]
    big = glm(eng["size"], **eng["overrides"])
    shapes = jax.eval_shape(big.init, jax.random.PRNGKey(0))
    assert big.num_params() == 706_518_848 == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))


def test_the_loss_is_the_references(model, params, shape, ids):
    total, m = program_loss(model, params, ids)
    want, main, mtp = ref_losses(params, ids, shape)
    np.testing.assert_allclose(total, want, rtol=2e-6)
    np.testing.assert_allclose(m["lm_loss"], main, rtol=2e-6)
    np.testing.assert_allclose(m["mtp_loss"], mtp, rtol=2e-6)
    np.testing.assert_allclose(
        total, m["lm_loss"] + model.config.mtp_loss_weight * m["mtp_loss"],
        rtol=1e-6)
    assert fam.loss(params, ids, shape) == pytest.approx(float(want))


def test_the_gradients_are_the_references(model, params, shape, ids):
    got = jax.jit(jax.grad(lambda p: program_loss(model, p, ids)[0]))(params)
    want = ref_grads(params, ids, shape)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            g, w, atol=2e-5 * scale + 1e-9, rtol=2e-4,
            err_msg=jax.tree_util.keystr(path))
    # the selection bias chooses and never weighs: no gradient reaches it
    for stack in (got["layers"], got["mtp"]["layers"]):
        assert not np.any(np.asarray(stack["mlp"]["sel_bias"]))
    # ... and backward through the weights reaches the router
    assert float(jnp.abs(got["layers"]["mlp"]["router"]).max()) > 0


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference(params, shape, ids, fault):
    """Each fault moves the reference's loss by far more than float32
    rounding, or (a change of the weight of the MTP loss aside) its
    gradients."""
    sound = float(ref_losses(params, ids, shape)[0])
    # (a broken reference runs as the benchmark runs it, its pieces jitted
    # and shared: whole, every fault would be a compile of its own)
    broken = float(fam.losses(params, ids, shape, fault=fault)[0])
    moved = abs(broken - sound) / sound
    if moved > 1e-4:
        return
    g0 = ref_grads(params, ids, shape)
    g1 = fam.grads(params, ids, shape, fault=fault)
    worst = max(float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-12))
                for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))
    assert worst > 1e-3, (fault, moved, worst)


def test_the_mtp_module_predicts_the_token_after_next_and_shares_the_head(
        model, params):
    labels = make_lm_batch(jnp.arange(10, 16)[None])["labels"]
    assert labels.tolist() == [[11, 12, 13, 14, 15, -1]]
    assert mtp_labels(labels).tolist() == [[12, 13, 14, 15, -1, -1]]
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    assert [n for n in names if "tok" in n] == ["['embed']['tok']"]
    assert [n for n in names if "lm_head" in n] == ["['lm_head']"]
    assert sorted(params["mtp"]) == ["eh_proj", "enorm", "final_norm",
                                     "hnorm", "layers"]
    # ... so the embedding and the head take gradient from both losses
    only_mtp = dataclasses.replace(model.config, mtp_loss_weight=1e3)
    ids = jnp.arange(40)[None] % 7
    g = [jax.jit(jax.grad(lambda p: glm_loss(cfg, p, ids)))(params)
         for cfg in (model.config, only_mtp)]
    for leaf in (lambda t: t["embed"]["tok"], lambda t: t["lm_head"]):
        assert float(jnp.abs(leaf(g[1])).max()) > 10 * float(
            jnp.abs(leaf(g[0])).max())


def glm_loss(cfg, params, ids):
    from deepspeed_tpu.models.transformer import loss_fn

    return loss_fn(cfg, params, make_lm_batch(ids), dtype=F32)[0]


def test_the_members_shares_add_up_to_the_uncut_layer(model):
    """Guide section 4: the partial sums of all the members, with what every
    member computes alike (the shared expert) counted once, are the uncut
    16-expert layer, forward and dX."""
    c = model.config
    members = c.routed_experts // c.num_experts
    keys = jax.random.split(jax.random.PRNGKey(21), 8)
    d, f, R = c.hidden_size, c.ffn, c.routed_experts
    whole = {
        "router": jax.random.normal(keys[0], (d, R)) * 0.5,
        "sel_bias": jax.random.normal(keys[1], (R,)) * 0.3,
        "wi": jax.random.normal(keys[2], (R, d, f)) * 0.2,
        "wg": jax.random.normal(keys[3], (R, d, f)) * 0.2,
        "wo": jax.random.normal(keys[4], (R, f, d)) * 0.2,
        "shared": {k: jax.random.normal(keys[5 + i], s) * 0.2 for i, (k, s) in
                   enumerate((("wi", (d, c.moe_shared_width)),
                              ("wg", (d, c.moe_shared_width)),
                              ("wo", (c.moe_shared_width, d))))},
    }
    x = jax.random.normal(jax.random.PRNGKey(22), (2, 24, d))
    ct = jax.random.normal(jax.random.PRNGKey(23), (2, 24, d))
    uncut = dataclasses.replace(c, num_experts=R, moe_routed_experts=R)

    def shared(x):
        return _mlp(c, whole["shared"], x, None, True, dense=True)[0]

    def member(x, m):
        cfg = dataclasses.replace(c, moe_first_expert=m * c.num_experts)
        lo, hi = m * c.num_experts, (m + 1) * c.num_experts
        p = dict(whole, **{k: whole[k][lo:hi] for k in ("wi", "wg", "wo")})
        return sm.moe_held_layer(cfg, p, x)

    def summed(x):
        return shared(x) + sum(member(x, m)[0] for m in range(members))

    def layer(x):
        return shared(x) + sm.moe_held_layer(uncut, whole, x)[0]

    np.testing.assert_allclose(jax.jit(summed)(x), jax.jit(layer)(x),
                               rtol=1e-5, atol=1e-6)
    dx = [jax.jit(jax.grad(lambda x: jnp.sum(fn(x) * ct)))(x)
          for fn in (summed, layer)]
    np.testing.assert_allclose(dx[0], dx[1], rtol=1e-5, atol=1e-6)
    # the counts every member sees are the layer's, its held rows its own
    stats = [member(x, m)[1] for m in range(members)]
    for st in stats:
        np.testing.assert_array_equal(st["counts"], stats[0]["counts"])
    np.testing.assert_array_equal(
        np.concatenate([st["held"] for st in stats]), stats[0]["counts"])
    assert float(stats[0]["counts"].sum()) == 2 * 24 * c.moe_top_k
    # one member's share is not the layer: the reference's shares differ too
    assert float(jnp.abs(member(x, 0)[0] - member(x, 1)[0]).max()) > 1e-3


def test_no_token_is_dropped_when_every_token_chooses_one_held_expert(model):
    c = model.config
    d, f, R, E = c.hidden_size, c.ffn, c.routed_experts, c.num_experts
    keys = jax.random.split(jax.random.PRNGKey(31), 5)
    p = {"router": jnp.zeros((d, R)),
         # every token's first choice is held expert 2; the other three of
         # its four are experts held elsewhere
         "sel_bias": jnp.zeros((R,)).at[jnp.asarray([2, 9, 10, 11])].set(1.0),
         "wi": jax.random.normal(keys[0], (E, d, f)) * 0.2,
         "wg": jax.random.normal(keys[1], (E, d, f)) * 0.2,
         "wo": jax.random.normal(keys[2], (E, f, d)) * 0.2}
    x = jax.random.normal(keys[3], (2, 40, d))
    out, stats = sm.moe_held_layer(c, p, x)
    assert stats["held"].tolist() == [0.0, 0.0, 80.0, 0.0]
    one = {k: p[k][2] for k in ("wi", "wg", "wo")}
    # all scores are sigmoid(0): each of the four chosen weighs 1/4 x scale
    want = _mlp(c, one, x, None, True, dense=True)[0] * (
        c.moe_routed_scale / c.moe_top_k)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # ... and the last token's row is as good as the first's
    assert float(jnp.abs(out[-1, -1]).max()) > 0


def ds_config(**over):
    cfg = {"train_batch_size": 2, "gradient_accumulation_steps": 1,
           "optimizer": {"type": "adamw",
                         "params": {"lr": 1e-3, "weight_decay": 0.1}},
           "zero_optimization": {"stage": 0},
           "activation_checkpointing": {"policy": "full"}}
    cfg.update(over)
    return cfg


def one_device():
    from deepspeed_tpu.comm import MeshTopology, ParallelDims

    return MeshTopology(dims=ParallelDims(), devices=jax.devices()[:1])


def bias_leaves(params):
    return [params["layers"]["mlp"]["sel_bias"],
            params["mtp"]["layers"]["mlp"]["sel_bias"]]


def test_the_bias_is_engine_state_that_moves_towards_balance(model, tmp_path):
    """No gradient (above), no moments, no decay; after a step every entry
    has moved by the update rate against the sign of its expert's excess
    load; a checkpoint keeps it."""
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=ds_config(), rng=jax.random.PRNGKey(5),
        topology=one_device())
    u = model.config.moe_bias_update_rate
    assert u == 0.001
    start = jax.tree.map(jnp.array, engine.state.params)  # the step donates
    before = [np.asarray(b) for b in bias_leaves(start)]
    # no moments: where a parameter has its mu and nu, the bias has none
    adam = engine.state.opt_state.inner_state[0][0]
    for tree in (adam.mu, adam.nu):
        assert isinstance(tree["layers"]["mlp"]["sel_bias"], optax.MaskedNode)
        assert tree["layers"]["mlp"]["router"].shape == (
            model.config.num_layers, model.config.hidden_size,
            model.config.routed_experts)
    moments = sum(a.size for a in jax.tree.leaves(adam.mu))
    assert moments == model.num_params() - sum(b.size for b in before)

    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, 512))
    loss = engine.train_batch(batch={"input_ids": ids})
    m = engine._metrics
    np.testing.assert_allclose(
        loss, m["lm_loss"] + model.config.mtp_loss_weight * m["mtp_loss"],
        rtol=1e-6)
    assert "moe_counts" not in m and float(m["moe_rows_held"]) > 0
    assert float(m["moe_rows_max_over_mean"]) >= 1.0
    # the counts the step saw, recomputed from the weights it started from
    _, mm = model.loss(start, make_lm_batch(jnp.asarray(ids)), dtype=F32)
    counts = np.asarray(mm["moe_counts"])
    want = u * np.sign(counts.mean(-1, keepdims=True) - counts)
    after = [np.asarray(b) for b in bias_leaves(engine.state.params)]
    L = model.config.num_layers
    np.testing.assert_allclose(after[0] - before[0], want[:L], atol=1e-7)
    np.testing.assert_allclose(after[1] - before[1], want[L:], atol=1e-7)
    assert np.any(want != 0)
    # a parameter beside it did decay and move
    assert float(jnp.abs(engine.state.params["layers"]["mlp"]["router"]
                         ).max()) > 0

    engine.save_checkpoint(str(tmp_path))
    other, *_ = deepspeed_tpu.initialize(
        model=model, config=ds_config(), rng=jax.random.PRNGKey(99),
        topology=one_device())
    assert not np.allclose(bias_leaves(other.state.params)[0], after[0])
    other.load_checkpoint(str(tmp_path))
    for got, kept in zip(bias_leaves(other.state.params), after):
        np.testing.assert_array_equal(got, kept)
    loss2 = other.train_batch(batch={"input_ids": ids})
    assert np.isfinite(float(loss2)) and float(loss2) < float(loss)


def test_without_an_update_rate_the_bias_stands_still_and_does_not_decay():
    still = glm("glm-tiny", moe_bias_update_rate=0.0)
    engine, *_ = deepspeed_tpu.initialize(
        model=still, config=ds_config(), rng=jax.random.PRNGKey(5),
        topology=one_device())
    before = [np.asarray(b) for b in bias_leaves(engine.state.params)]
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0, 512))
    engine.train_batch(batch={"input_ids": ids})
    for got, kept in zip(bias_leaves(engine.state.params), before):
        np.testing.assert_array_equal(got, kept)


def test_the_traced_step_carries_the_new_scopes_and_counters(model, params):
    text = jax.jit(lambda p, b: model.loss(p, b, dtype=F32)[0]).lower(
        params, make_lm_batch(jnp.zeros((1, 16), jnp.int32))).as_text(
        debug_info=True)
    for scope in ("latent_attention", "moe_route", "moe_experts", "mtp"):
        assert scope in text, scope
    from deepspeed_tpu.profiling import steptrace

    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=ds_config(steptrace={"enabled": True}),
            rng=jax.random.PRNGKey(5), topology=one_device())
        engine.train_batch(batch={"input_ids": np.zeros((2, 32), np.int32)})
        span = engine.tracer.spans_named("train/device")[-1]
    finally:
        steptrace.reset()
    assert set(span["args"]) >= {"mtp_loss", "moe_rows_held",
                                 "moe_rows_max_over_mean"}
    assert span["args"]["moe_rows_held"] == float(
        engine._metrics["moe_rows_held"])


def test_forward_of_a_latent_model_runs_and_the_indexer_is_still_refused(
        model, params):
    logits, _ = jax.jit(lambda p, ids: model.apply(p, ids, dtype=F32))(
        params, jnp.zeros((1, 8), jnp.int32))
    assert logits.shape == (1, 8, 512) and bool(jnp.isfinite(logits).all())
    from deepspeed_tpu.models import deepseek

    with pytest.raises(DeepSpeedConfigError, match="index_topk"):
        deepseek("deepseek-tiny").loss(
            jax.jit(deepseek("deepseek-tiny").init)(jax.random.PRNGKey(0)),
            make_lm_batch(jnp.zeros((1, 8), jnp.int32)))


def test_serving_the_preset_ignores_the_mtp_leaves(model, params):
    """Serving this preset is not asked for; what ``init_serving`` does with
    it is pinned: the next-token logits do not depend on the MTP module, so
    the paged engine serves the model and never reads ``params['mtp']``."""
    from deepspeed_tpu.serving import Request

    srv = deepspeed_tpu.init_serving(
        model, params=params, dtype=F32, serving=dict(
            max_slots=2, token_budget=8, max_tokens=64, paged=True,
            page_size=8, prefix_cache=False))
    prompt = np.arange(5, 17, dtype=np.int32)
    st = srv.submit(Request(request_id="r", prompt=prompt, max_new_tokens=4,
                            temperature=0.0, eos_token_id=-1))
    srv.run_until_idle()
    ids = np.concatenate([prompt, np.asarray(st.tokens, np.int32)])
    logits, _ = model.apply(params, jnp.asarray(ids[:-1])[None], dtype=F32)
    assert st.tokens == np.argmax(np.asarray(logits[0, -4:]), -1).tolist()
