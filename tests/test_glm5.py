"""GLM-5.3-Flash on the serving path, float32 on the CPU at a tiny size: four
hyper-connected residual streams round every half-layer, KDA layers (Kimi
Linear's low-rank decay and channel gate) three to one beside latent
attention without a rotary part under an indexer whose keys are pooled by
four, under one member's share of a sigmoid-routed layer of clamped SwiGLUs,
against the benchmark's plain reference (benchmarks/families/glm5_next.py)."""

import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import brumby, glm5, ling, mixers
from deepspeed_tpu.models.decoding import (forward_with_cache,
                                           init_paged_cache)
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla
from deepspeed_tpu.serving import Request

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks.families import glm5_next as fam  # noqa: E402
from slot_program import (drive, ids_of, init_params,  # noqa: E402
                          reference_logits, schedule)

F32 = jnp.float32
logits_of = reference_logits(fam)
# float32 against float32 on logits whose spread is about 1: what is left is
# the order of the sums (the KDA chunk form's cumulative log-decays reach 80
# a sub-block, so a decay carries 1e-5 of relative rounding; twenty Sinkhorn
# rounds and ten boundaries add theirs). bf16 in place of float32 anywhere
# moves a logit by 1e-2 and more.
TOL = 3e-4
PS, W, SLOTS = 16, 16, 3
SERVING = dict(max_slots=SLOTS, token_budget=W, max_tokens=240, paged=True,
               page_size=PS, prefix_cache=False)
IDS = [0, 1, 2, 3, 4, 7]  # dense K K, routed K D K D of the tiny preset
HELD = dict(num_experts=4, moe_routed_experts=16)
LIMIT = 0.3  # a clamp that bites at these widths (the release's 10 does not)
CONFIG = dict(
    family="glm5_next", hidden_size=64, num_hidden_layers=len(IDS),
    vocab_size=512, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=4, num_experts_per_tok=4, n_shared_experts=1,
    tie_word_embeddings=False, rms_norm_eps=1e-5, routed_scaling_factor=2.5,
    swiglu_limit=LIMIT, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=0, v_head_dim=16, mla_use_nope=True, mhc=True, n_group=1,
    index_n_heads=2, index_head_dim=16, index_topk=6, index_kpool=4,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, first_k_dense_replace=2,
    layer_ids=IDS,
    layer_types=["deepseek_sparse_attention" if i % 4 == 3
                 else "linear_attention" for i in range(8)],
    linear_attn_config=dict(num_heads=4, head_dim=16,
                            short_conv_kernel_size=4, gate_lower_bound=-5),
    published=dict(first_k_dense_replace=2, n_routed_experts=16,
                   num_hidden_layers=8),
    assumed=dict(index_rope_theta=1e6, kda_gate_rank=8, index_rope_dim=8))


def tiny(**over):
    # weights five times the preset's spread, so that the mixers weigh as
    # much as the residual streams and a fault in one shows in the logits
    return glm5("glm5-tiny", **{
        **dict(layer_ids=IDS, initializer_range=0.1, swiglu_limit=LIMIT),
        **HELD, **over})


@pytest.fixture(scope="module")
def model():
    return tiny()


@pytest.fixture(scope="module")
def params(model):
    return init_params(model)


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(CONFIG)


def test_the_plan_names_both_families_kinds(model):
    cfg = model.config
    assert cfg.mixer_types == ("kda", "kda", "kda", "mla", "kda", "mla")
    assert cfg.mixer_family == "ling" and cfg.has_state
    assert (cfg.lead_dense_layers, cfg.num_layers) == (2, 4)
    plan = mixers.layer_plan(cfg)
    assert [(l.stack, l.mixer_at, l.mlp_stack, l.mlp_at) for l in plan] == [
        ("kda_layers", 0, "lead_layers", 0),
        ("kda_layers", 1, "lead_layers", 1),
        ("kda_layers", 2, "layers", 0), ("mla_layers", 0, "layers", 1),
        ("kda_layers", 3, "layers", 2), ("mla_layers", 1, "layers", 3)]
    assert set(mixers.slot_leaves(cfg, SLOTS, F32)) == {
        "state", "conv", "ki_tail"}
    pools = jax.eval_shape(
        lambda: init_paged_cache(cfg, 48, PS, F32, max_slots=SLOTS))
    # the pooled index keys: a quarter as long, on the latent pool's table
    assert pools["kv"].shape == (2, 49, PS, 128)
    assert pools["ki"].shape == (2, 49, PS // 4, 16)
    assert pools["ki_tail"].shape == (2, SLOTS, 3, 16)


def test_num_params_is_the_count_of_leaves_and_the_issues_arithmetic(
        model, params):
    assert model.num_params() == sum(
        a.size for a in jax.tree.leaves(params))
    real = glm5("glm-5.3-flash", layer_ids=[0, 4, 5, 6, 7], num_experts=36,
                moe_routed_experts=288, vocab_size=19360).config
    fam_of = mixers.family(real)  # models/ling.py, the kinds' owner
    d = 4096
    kda = (4 * d * 8192 + 2 * 128 * (d + 8192) + d * 64 + 3 * 8192 * 4
           + 64 + 8192 + 128)
    mla = (d * 1536 + 1536 + 1536 * 64 * 256 + d * 512 + 512
           + 512 * 64 * 512 + 64 * 256 * d
           + 1536 * 32 * 128 + d * 128 + 2 * 128 + d * 32)
    hc = (4 * d + 1) * 24 + 3
    assert (fam_of.mixer_params(real, "kda"), fam_of.mixer_params(real, "mla"),
            fam_of.hyper_params(real)) == (kda, mla, hc)
    assert (round(kda / 1e6, 1), round(mla / 1e6, 1)) == (137.7, 124.4)
    routed = d * 288 + 288 + 3 * d * 2048 * 37 + d
    total = (4 * (kda + d) + mla + d + 3 * d * 12288 + d + 4 * routed
             + 10 * hc + 2 * 19360 * d + d)
    assert real.num_params() == total == 4_718_150_030


def test_chunks_that_cut_pooled_blocks_then_decode_match_the_reference(
        model, params, shape):
    """Three slots at different frontiers, prefilled in chunks whose
    boundaries fall inside blocks of four (and inside the convolution's
    reach), then decoded a row a step, against the reference's one pass over
    each sequence. The contexts pass the selection's reach (6 blocks = 24
    tokens + tail) many times over."""
    seqs = {0: ids_of(150, 1), 1: ids_of(61, 2), 2: ids_of(94, 3)}
    sizes = {0: [5, 7, 3, 1, 6, 2], 1: [3, 1, 1, 4, 2], 2: [6, 1, 5, 3]}
    got, _ = drive(model, params, schedule(seqs, sizes), slots=SLOTS,
                   width=W, pages_per_slot=16, page_size=PS)
    for slot, ids in seqs.items():
        want = np.asarray(logits_of(params, ids, shape))
        np.testing.assert_allclose(np.concatenate(got[slot]), want,
                                   atol=TOL, rtol=0)


def test_engine_serves_what_the_reference_predicts(model, params, shape):
    srv = deepspeed_tpu.init_serving(model, serving=SERVING, params=params,
                                     dtype=F32)
    d = srv.describe()
    assert set(d["attention"]) == {"kda", "mla"}
    assert d["residual_streams"] == 4 and d["paged_layers"] == 2
    assert set(d["state_leaves"]) == {"state", "conv", "ki_tail"}
    assert d["expert_path"] == "einsum"
    prompts = [ids_of(n, 10 + n) for n in (50, 21, 90)]
    states = [srv.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=8, temperature=0.0,
        eos_token_id=-1)) for i, p in enumerate(prompts)]
    srv.run_until_idle()
    for p, st in zip(prompts, states):
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        want = np.asarray(logits_of(params, ids[:-1], shape, last=8))
        gaps = want.max(-1) - want[np.arange(8), st.tokens]
        assert gaps.max() <= TOL, gaps
    snap = srv.metrics.snapshot()
    assert snap["hyper_streams"] == 4 and snap["state_resets"] == 3
    assert snap["state_bytes"] == sum(d["state_leaves"].values())
    assert {"attention_paged_kernel_kda", "attention_paged_kernel_mla",
            "expert_touched_kernel"} <= set(snap)
    # the step's counts: one query at position 25 sees 6 whole blocks, 2
    # tail tokens; its selection (6 blocks) spares it nothing yet, one at
    # position 41 attends 6 of its 10 blocks and its 2 tail tokens: its row
    # tile is the one program of the selection's grid that searches
    from deepspeed_tpu.serving.engine import _mla_counts

    got = _mla_counts(srv, np.asarray([25, 41, 0]), np.asarray([1, 1, 0]))
    assert got == dict(context_keys=26 + 42, index_keys=6 + 10,
                       index_rows=6 + 10, attended_sparse=26 + 26,
                       tail_keys=2 + 2, chosen_min=26 + 26,
                       selection_tiles=1, selection_tiles_grid=3 * W // 8,
                       # 6 and 10 pooled keys: one block of one tile a slot
                       score_tiles=2, score_tiles_grid=3)


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference(params, shape, fault):
    ids = ids_of(150, 5)  # past a chunk of 128 rows, past the reach
    sound = np.asarray(fam.logits(params, ids, shape))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, **fam.faulted(params, fault, shape)))
    assert np.abs(broken - sound).max() > 100 * TOL, fault


def test_the_members_shares_add_up_to_the_uncut_layer(shape):
    """Four members' partial sums of one routed layer add up to the layer
    with all 16 experts held once the shared expert is counted once (mixers
    and hyper-connections are whole on every member), and each member's
    share is the reference's."""
    from deepspeed_tpu.models.transformer import _mlp
    from deepspeed_tpu.moe.sharded_moe import moe_serving_mlp

    whole = tiny(num_experts=16, moe_routed_experts=16)
    bank = init_params(whole)["layers"]["mlp"]
    layer = jax.tree.map(lambda a: a[1], bank)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64), F32)
    want, _ = moe_serving_mlp(whole.config, layer, x, budget_tokens=24)
    shared = _mlp(whole.config, layer["shared"], x, None, False,
                  dense=True)[0]
    cut = lambda tree, lo, axis: {**tree, **{
        k: jax.lax.slice_in_dim(tree[k], lo, lo + 4, axis=axis)
        for k in ("wi", "wg", "wo")}}
    load = lambda t: jax.tree.map(lambda w: jnp.asarray(w, F32), t)
    total = shared
    for first in range(0, 16, 4):
        part, _ = moe_serving_mlp(tiny(moe_first_expert=first).config,
                                  cut(layer, first, 0), x, budget_tokens=24)
        ref_part, _ = fam.routed_mlp(x[0], cut(bank, first, 1), 1, shape,
                                     load, first=first)
        np.testing.assert_allclose(np.asarray(part[0]), np.asarray(ref_part),
                                   atol=1e-5, rtol=0)
        total = total + part - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=0)


def test_sinkhorn_is_doubly_stochastic_and_one_stream_is_the_residual():
    raw = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 2, 9), F32)
    m = mixers.sinkhorn(raw, 20)
    assert float(jnp.abs(m.sum(0) - 1).max()) < 1e-5
    assert float(jnp.abs(m.sum(1) - 1).max()) < 1e-5
    assert float(jnp.abs(mixers.sinkhorn(raw, 1).sum(1) - 1).max()) > 1e-3
    # the reference's rounds, the other way laid out, agree
    want = fam.sinkhorn(jnp.exp(jnp.moveaxis(raw, (0, 1), (-2, -1))), 20)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(m, (0, 1), (-2, -1))),
                               np.asarray(want), atol=1e-6)
    # pre = post = res = 1 over one stream: h + y, the plain residual
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 9, 8), F32)
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 8), F32)
    one = jnp.ones((1, 2, 9), F32)
    np.testing.assert_allclose(
        np.asarray(mixers.hyper_post(h, y, one, one[None])[0]),
        np.asarray(h[0] + y), atol=1e-6)


def test_hyper_pre_is_the_references_reading(model, params, shape):
    cfg = model.config
    hc = jax.tree.map(lambda a: a[1], params["kda_layers"]["hc"])
    X = jax.random.normal(jax.random.PRNGKey(4), (4, 1, 11, 64), F32)
    u, post, res = mixers.hyper_pre(cfg, hc, X)
    wu, wpost, wres = fam._hyper_read(X[:, 0], hc, iters=20, eps=1e-6)
    after = fam._hyper_write(tuple(X[:, 0]), wu, wpost, wres)
    np.testing.assert_allclose(
        np.asarray(mixers.hyper_post(X, u, post, res)[:, 0]),
        np.asarray(jnp.stack(after)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(u[0]), np.asarray(wu), atol=1e-5)
    np.testing.assert_allclose(np.asarray(post[:, 0].T), np.asarray(wpost),
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.moveaxis(res[:, :, 0], (0, 1), (1, 2))),
        np.asarray(wres), atol=1e-5)


# (model, sha256 of its step's lowered text): Ling's as it lowers since the
# pools took the computed rows (PR 53: against the text of commit 63b0d39,
# ``fb7c5cda...``, the places of the packed rows formed before the scans, the
# latent row scattered from them, and its unpack and the by-slot places
# gone; nothing else: CHANGES.md); Brumby's, which keeps no page, the text
# of commit 63b0d39 still
PARENT_LING_WALK = (
    "3ea5e2b534d3d0962eb5e81661557fbcccb217bbf78fb5d734839e6cad0491b2")
PARENT_BRUMBY_WALK = (
    "3136cf397a8e286617f76477f9e9c1c7ea290ee1c333c3118e8177c9ba74b3fd")
PARENT_WALKS = {
    "ling": (lambda: ling("ling-tiny", layer_ids=list(range(12)),
                          num_experts=4, moe_routed_experts=16),
             PARENT_LING_WALK),
    "brumby": (lambda: brumby("brumby-tiny", layer_ids=[0, 1, 2]),
               PARENT_BRUMBY_WALK),
}


@pytest.mark.parametrize("name", list(PARENT_WALKS))
def test_a_model_without_streams_traces_the_parents_walk(name):
    """``hc_mult`` 0 is the two residual lines: a step of a model without
    streams lowers to the text it is pinned to (the sha256 of it), which a
    change to the walk, the rows or a pool's write has to own up to."""
    make, pinned = PARENT_WALKS[name]
    model = make()
    cfg = model.config
    assert cfg.hc_mult == 0 and cfg.swiglu_limit == 0
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, 48, PS, F32, max_slots=SLOTS))
    p = jax.eval_shape(lambda k: model.init(k, dtype=F32),
                       jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = jax.jit(lambda p, t, c, s, tb, n: forward_with_cache(
        cfg, p, t, c, s, dtype=F32, page_table=tb, num_new=n,
        token_budget=W)).lower(
        p, i32(SLOTS, W), caches, i32(SLOTS), i32(SLOTS, 16),
        i32(SLOTS)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == pinned


def test_the_clamp_bites_and_zero_is_none(model, params):
    from deepspeed_tpu.models.transformer import _mlp

    cfg = model.config
    m = jax.tree.map(lambda a: a[0], params["lead_layers"]["mlp"])
    x = 4.0 * jax.random.normal(jax.random.PRNGKey(5), (1, 7, 64), F32)
    clamped = _mlp(cfg, m, x, None, False, dense=True)[0]
    plain = _mlp(tiny(swiglu_limit=0.0).config, m, x, None, False,
                 dense=True)[0]
    want = fam._gated(x[0], m, LIMIT)
    np.testing.assert_allclose(np.asarray(clamped[0]), np.asarray(want),
                               atol=1e-5)
    assert float(jnp.abs(clamped - plain).max()) > 0.1
    g, up = x[0] @ m["wg"], x[0] @ m["wi"]
    assert float(g.max()) > LIMIT and float(jnp.abs(up).max()) > LIMIT


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_pooled_selection_kernels_are_their_dense_twins(kernels):
    """The three calls over pooled keys (interpret mode) against the plain
    lines, at a context past the selection's reach, rows at different
    frontiers, a chunk boundary inside a block."""
    B, S, H, Hi, Di, Wd, kp, topk = 2, 8, 2, 2, 16, 128, 4, 40
    ps, mp = 16, 64  # 1,024 tokens a slot: two key blocks of 512
    P = B * mp
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    kv = jax.random.normal(key[0], (1, P + 1, ps, Wd), F32)
    ki = jax.random.normal(key[1], (1, P + 1, ps // kp, Di), F32)
    q_abs = jax.random.normal(key[2], (B, S, H, Wd), F32)
    q_idx = jax.random.normal(key[3], (B, S, Hi, Di), F32)
    w_idx = jax.random.normal(key[4], (B, S, Hi), F32)
    table = jnp.arange(P, dtype=jnp.int32).reshape(B, mp)
    cl = jnp.asarray([1001, 610], jnp.int32)
    nn = jnp.asarray([8, 5], jnp.int32)
    qpos = cl[:, None] + jnp.arange(S)[None, :]
    view = lambda pool: pool[0][table].reshape(B, -1, pool.shape[-1])
    blocks = sla.dense_selection(
        sla.dense_index_scores(q_idx, w_idx, view(ki)),
        sla.last_block(qpos, kp), topk)
    chosen = sla.tokens_of_blocks(blocks, qpos, kp)
    want = sla.dense_sparse_attention(q_abs, view(kv), chosen, 0.1, Wd)
    # a row past the reach attends topk blocks and its tail, no more
    n = np.asarray(chosen.sum(-1))
    assert n[0, 0] == topk * kp + (1001 + 1) % kp
    if not kernels:
        return
    out, why = sla.latent_sparse_attention(
        q_abs, q_idx, w_idx, kv, ki, cl, table, layer=0, topk=topk,
        scale=0.1, v_width=Wd, num_new=nn, interpret=True, kpool=kp)
    assert why == []
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out[b, :int(nn[b])]), np.asarray(want[b, :int(nn[b])]),
            atol=2e-5, rtol=0)


def test_what_cannot_be_built_is_refused_in_words(model, params):
    with pytest.raises(ValueError, match="share the module"):
        TransformerConfig(num_layers=2, mixer_types=("lightning", "mla"),
                          mixer_layer_ids=(0, 1), mixer_depth=2,
                          kv_latent_dim=16)
    with pytest.raises(ValueError, match="index_kpool"):
        TransformerConfig(num_layers=1, kv_latent_dim=16, q_latent_dim=16,
                          qk_nope_dim=8, qk_rope_dim=8, head_dim=16,
                          v_head_dim=16, num_kv_heads=1, index_topk=4,
                          index_heads=2, index_dim=16, index_kpool=4)
    with pytest.raises(ValueError, match="hc_mult"):
        TransformerConfig(num_layers=1, hc_mult=4)
    with pytest.raises(ValueError, match="published order"):
        glm5("glm5-tiny", layer_ids=[3, 0])
    with pytest.raises(DeepSpeedConfigError, match="serving.paged"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))
    serve = lambda **over: deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, **over), params=params, dtype=F32)
    with pytest.raises(DeepSpeedConfigError, match="state layers"):
        serve(spec=dict(enabled=True, max_draft=2))
    with pytest.raises(DeepSpeedConfigError, match="state layers"):
        serve(host_pages=8)
    with pytest.raises(DeepSpeedConfigError, match="paged"):
        serve(paged=False)
    # a clamped bank is the einsum's, with the reason
    from deepspeed_tpu.moe.sharded_moe import expert_bank_path

    cfg = glm5("glm5-tiny", num_experts=64, moe_routed_experts=512).config
    assert "clamped" in expert_bank_path(
        cfg, {"wi": jnp.zeros((64, 128, 128))}, 16, True)[1]
