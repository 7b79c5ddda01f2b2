"""Command A+ (a parallel attention + expert block under one bias-free
LayerNorm, averaged shared experts beside one member's share of a plain
sigmoid-routed layer, rotary window layers three to one beside NoPE full
layers, many query heads a KV head) at a small size on the CPU in float32,
seeded random weights, against the benchmark's plain reference
(``benchmarks/families/cohere2_moe.py``): the cached forward through the two
paged pools, the uncached ``apply``, the serving engine, the share against
the uncut layer, the rotary pairing rule, and the row-tiled paged kernel."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.families import cohere2_moe as fam
from deepspeed_tpu.models import cohere, mellum
from deepspeed_tpu.models.cohere import (averaged_shared_bank,
                                         half_split_columns)
from deepspeed_tpu.models.decoding import (_dense_cached_attention,
                                           _paged_gather)
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.serving import Request
from slot_program import (ids_of, init_params, paged_forward,
                          reference_logits)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
# float32 on both sides, the same equations in another order of summation:
# the largest difference seen is 4e-6 of the logits' range; the mildest
# fault moves them by thirty times the tolerance
RTOL = 1e-4
PS = 16
logits_of = reference_logits(fam)
HELD = dict(num_experts=4, moe_routed_experts=8)  # member 0 of two


def tiny_config(**over):
    """The benchmark's configuration at its rehearsal size."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "command-a-plus-05-2026.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse")
    tiny.pop("engine")
    return {**cfg, **tiny, **over}


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(tiny_config())


def tiny(**over):
    # wide initial weights, so that attention and the experts move the
    # logits by as much as the embedding does
    return cohere("cohere-tiny", **{**HELD, "initializer_range": 0.2, **over})


@pytest.fixture(scope="module")
def model():
    return tiny()


@pytest.fixture(scope="module")
def params(model):
    return init_params(model, spread=0.2)


@functools.cache
def apply_of(model):
    """The uncached forward in float32, under ``jit``."""
    return jax.jit(functools.partial(model.apply, dtype=F32))


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= rtol * np.abs(want).max()


def test_the_preset_is_the_published_model_and_the_tiny_one_its_shape(shape):
    c = cohere("command-a-plus-05-2026").config
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd,
            c.ffn, c.num_experts, c.moe_top_k, c.moe_shared_width,
            c.vocab_size, c.attn_window, c.rope_theta) == (
        4096, 32, 128, 8, 128, 4096, 128, 8, 4 * 4096, 262144, 4096, 50000.0)
    assert c.layer_pattern == ("window", "window", "window", "full")
    assert c.nope_kinds == ("full",) and c.rope_of("full") is None
    assert c.rope_of("window").theta == 50000.0
    assert (c.norm, c.norm_bias, c.parallel_block, c.moe_gate,
            c.tie_embeddings) == ("layernorm", False, True, "sigmoid", True)
    # the issue's arithmetic: a layer outside its routed experts, a routed
    # expert, and the benchmark's cut
    layer = c.num_params() - 262144 * 4096 - 4096
    assert layer // 32 - 128 * 3 * 4096 * 4096 == 344_461_312
    cut = cohere("command-a-plus-05-2026", num_layers=4, num_experts=16,
                 moe_routed_experts=128, vocab_size=32768)
    assert cut.num_params() == 4_733_292_544  # the issue's 4.733 B
    t = tiny().config
    assert (t.hidden_size, t.num_layers, t.num_heads, t.kv_heads, t.hd,
            t.ffn, t.num_experts, t.routed_experts, t.moe_top_k,
            t.moe_shared_width, t.vocab_size, t.attn_window) == (
        shape.d, shape.layers, shape.heads, shape.kv_heads, shape.hd,
        shape.ffn, shape.experts, shape.routed, shape.top_k,
        shape.shared * shape.ffn, shape.vocab, shape.window)
    assert shape.pattern == (fam.SLIDING,) * 3 + (fam.FULL,)


def test_the_tree_has_one_norm_a_layer_and_no_bias(model, params):
    L = params["layers"]
    assert set(L) == {"ln1", "attn", "mlp"} and set(L["ln1"]) == {"scale"}
    assert set(params["final_norm"]) == {"scale"} and "lm_head" not in params
    assert set(L["mlp"]) == {"router", "wi", "wg", "wo", "shared"}
    assert L["mlp"]["router"].shape == (4, 64, 8)        # all routed experts
    assert L["mlp"]["wi"].shape == (4, 4, 64, 32)        # the four held
    # the two averaged shared experts side by side, as one bank
    assert L["mlp"]["shared"]["wi"].shape == (4, 64, 2 * 32)
    assert L["mlp"]["shared"]["wo"].shape == (4, 2 * 32, 64)
    assert sum(a.size for a in jax.tree.leaves(params)) == model.num_params()
    specs = model.partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: 0, params))


def test_what_the_fields_exclude_is_refused_by_name():
    with pytest.raises(ValueError, match="parallel_block"):
        TransformerConfig(parallel_block=True, hc_mult=4,
                          mixer_types=("kda",) * 4,
                          mixer_layer_ids=(0, 1, 2, 3), mixer_depth=4)
    with pytest.raises(ValueError, match="no groups"):
        cohere("cohere-tiny", moe_groups=2)
    with pytest.raises(ValueError, match="nope_kinds"):
        cohere("cohere-tiny", nope_kinds=("mla",))
    # the program scales no logits: a configuration that asks for it
    with pytest.raises(ValueError, match="logit_scale"):
        fam.shape_of({**tiny_config(), "logit_scale": 0.25})
    # the share of a layer is a sigmoid router's: the softmax gate drops
    with pytest.raises(ValueError, match="sigmoid routers"):
        mellum("mellum-tiny", moe_routed_experts=16)


def test_apply_computes_the_reference(model, params, shape):
    ids = ids_of(100)  # past the window (24)
    got, _ = apply_of(model)(params, jnp.asarray(ids[None]))
    want = logits_of(params, ids, shape)
    assert close(got[0], want)


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference_beyond_the_tolerance(
        params, shape, fault):
    ids = ids_of(100, seed=3)
    want = fam.logits(params, ids, shape)
    broken = fam.logits(ids=ids, shape=shape,
                        **fam.faulted(params, fault, shape))
    assert not close(broken, want, rtol=30 * RTOL), fault


@pytest.mark.parametrize("chunk,budget", [(16, None), (10, 16)],
                         ids=["slots", "packed"])
def test_the_cached_forward_through_both_pools_is_the_reference(
        model, params, shape, chunk, budget):
    # a slot shorter than the window beside one far longer; chunks of 16
    # straddle the window's edge (24), chunks of 10 cut pages too
    prompts = [ids_of(13, seed=1), ids_of(150, seed=2)]
    rows, seqs = paged_forward(model, params, prompts, chunk, PS,
                               budget=budget)
    for got, seq in zip(rows, seqs):
        want = logits_of(params, np.asarray(seq, np.int32), shape)
        assert got.shape == want.shape
        assert close(got, want)


def test_the_engine_serves_the_references_argmax(model, params, shape):
    srv = deepspeed_tpu.init_serving(
        model, params=params, dtype=F32, serving=dict(
            max_slots=3, token_budget=16, max_tokens=240, paged=True,
            page_size=PS))
    d = srv.describe()
    assert d["parallel_block"] is True
    assert d["shared_width"] == 2 * 32
    assert d["pool_pages"] == {"full": srv.num_pages,
                               "window": 3 * ((24 + 16) // PS + 2)}
    assert srv.scheduler.prefix_cache is None
    prompts = [ids_of(n, seed=n) for n in (9, 61, 133)]
    states = [srv.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=6, temperature=0.0,
        eos_token_id=-1)) for i, p in enumerate(prompts)]
    srv.run_until_idle()
    for p, st in zip(prompts, states):
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        want = logits_of(params, ids[:-1], shape, last=6)
        assert list(np.argmax(np.asarray(want), -1)) == list(st.tokens)
    snap = srv.metrics.snapshot()
    assert snap["moe_experts_touched"] > 0
    # the held share: the step's annotation counts what landed here
    assert srv._held_assignments > 0 and srv._experts_touched is not None


def test_the_members_parts_add_up_to_the_uncut_layer(shape):
    """Two members hold four experts each of the tiny layer's eight: their
    routed parts, with the attention, the shared branch and the residual
    counted once, are the uncut layer, in the reference and in the
    program."""
    whole = tiny(num_experts=8, moe_routed_experts=0)
    full = init_params(whole, seed=4, spread=0.2)
    uncut = fam.shape_of(tiny_config(num_experts=8))
    assert uncut.experts == uncut.routed == 8
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)), F32)

    def member(tree, j):  # the banks of experts 4j .. 4j + 3
        mlp = tree["layers"]["mlp"]
        cut = {k: mlp[k][:, 4 * j:4 * j + 4] for k in ("wi", "wg", "wo")}
        return {**tree, "layers": {**tree["layers"], "mlp": {**mlp, **cut}}}

    for i in (0, 3):  # a window layer and the full one
        with fam.ref.HIGHEST():
            A, R, Sh, _ = fam.layer_parts(full["layers"], i, x, uncut)
            parts = [fam.layer_parts(member(full, j)["layers"], i, x, shape,
                                     first=4 * j) for j in range(2)]
        for a, _, sh, _ in parts:  # what every member computes alike
            np.testing.assert_array_equal(a, A)
            np.testing.assert_array_equal(sh, Sh)
        assert close(parts[0][1] + parts[1][1], R)
    # the program: each member's serving layer against the whole layer's
    from deepspeed_tpu.models.transformer import _mlp
    from deepspeed_tpu.moe.sharded_moe import moe_serving_mlp

    h = x[None]
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    want, _ = moe_serving_mlp(whole.config, at(full["layers"]["mlp"], 1), h)
    got = []
    for j in range(2):
        cfg = tiny(moe_first_expert=4 * j).config
        out, stats = moe_serving_mlp(
            cfg, at(member(full, j)["layers"]["mlp"], 1), h)
        got.append(out)
        assert int(stats["unrouted_tokens"]) >= 0
    shared = _mlp(  # every member adds it: counted once
        whole.config, at(full["layers"]["mlp"], 1)["shared"], h, None, False,
        dense=True)[0]
    assert close(got[0] + got[1] - shared, want, rtol=3e-4)


def test_the_folded_bank_is_the_mean_of_the_published_shared_experts(shape):
    """Shared experts in the PUBLISHED layout (``n`` experts, averaged): the
    program's one bank (``averaged_shared_bank``) and the reference, which
    unfolds it, both compute their mean."""
    from deepspeed_tpu.models.transformer import _mlp

    rng = np.random.default_rng(3)
    n, d, f = shape.shared, shape.d, shape.ffn
    wg, wi = rng.normal(size=(2, n, d, f)) / 8
    wo = rng.normal(size=(n, f, d)) / 8
    x = rng.normal(size=(40, d))
    silu = lambda a: a / (1 + np.exp(-a))
    want = sum((silu(x @ wg[j]) * (x @ wi[j])) @ wo[j] for j in range(n)) / n
    bank = jax.tree.map(lambda a: jnp.asarray(a, F32),
                        averaged_shared_bank(wg, wi, wo))
    assert bank["wg"].shape == (d, n * f) and bank["wo"].shape == (n * f, d)
    with fam.ref.HIGHEST():
        got = _mlp(tiny().config, bank, jnp.asarray(x, F32)[None], None,
                   False, dense=True)[0][0]
        stacked = jax.tree.map(lambda a: a[None], bank)
        ref = fam._experts(jnp.asarray(x, F32), jnp.ones((40, n), F32),
                           stacked["wg"], stacked["wi"], stacked["wo"], 0,
                           folded=n) / n
    assert close(got, want, rtol=1e-5) and close(ref, want, rtol=1e-5)


def test_permuted_columns_make_rotate_half_the_published_rotation(
        model, shape):
    """Weights in the PUBLISHED layout (interleaved rotary pairs): the
    program on ``half_split_columns`` of W_q and W_k is the reference's
    interleaved rotation on the published columns."""
    published = init_params(model, seed=9, spread=0.2)
    attn = published["layers"]["attn"]
    cfg = model.config
    loaded = {**published, "layers": {**published["layers"], "attn": {
        **attn, "wq": half_split_columns(attn["wq"], cfg.num_heads, cfg.hd),
        "wk": half_split_columns(attn["wk"], cfg.kv_heads, cfg.hd)}}}
    # the reference's un-permutation is the inverse of the program's load
    np.testing.assert_array_equal(
        fam.published_columns(loaded["layers"]["attn"]["wq"], cfg.hd),
        attn["wq"])
    ids = ids_of(90, seed=5)
    got, _ = apply_of(model)(loaded, jnp.asarray(ids[None]))
    # by hand: interleaved pairs on the published columns
    x = jnp.asarray(np.random.default_rng(2).normal(size=(7, 8, 16)), F32)
    r = fam._rotate(x, 3, 50000.0)
    ang = (3 + np.arange(7))[:, None] * 50000.0 ** -(np.arange(8) / 8)
    np.testing.assert_allclose(
        r[..., 0::2], x[..., 0::2] * np.cos(ang)[:, None]
        - x[..., 1::2] * np.sin(ang)[:, None], rtol=1e-5, atol=1e-5)
    assert close(got[0], logits_of(loaded, ids, shape))
    # and the published weights unpermuted are NOT the program's model
    wrong, _ = apply_of(model)(published, jnp.asarray(ids[None]))
    assert not close(wrong[0], got[0], rtol=30 * RTOL)


@pytest.mark.parametrize("window", [None, 24])
def test_the_row_tiled_kernel_at_16_heads_a_kv_head(monkeypatch, window):
    """G = 16: with a budget small enough that the whole [KV, S x G, hd]
    block does not fit, the grid takes a program a slot and row tile; in
    interpret mode against the dense lines, decoding and idle slots beside
    chunks that end inside a tile."""
    B, S, H, KV, hd, ps, mp = 4, 32, 32, 2, 32, 4, 48
    cfg = cohere("cohere-tiny", num_heads=H, num_kv_heads=KV,
                 head_dim=hd).config
    rng = np.random.default_rng(0)
    P = B * mp
    k = jnp.asarray(rng.normal(size=(P + 1, ps, KV, hd)), F32)
    v = jnp.asarray(rng.normal(size=(P + 1, ps, KV, hd)), F32)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), F32)
    pt = jnp.asarray(rng.permutation(P).reshape(B, mp), jnp.int32)
    whole = pa._vmem_bytes(S, H // KV, KV, hd, ps, 4, 4, 4)
    assert pa.row_tile(S, H // KV, KV, hd, ps, 4, 4, 4) == S
    monkeypatch.setattr(pa, "VMEM_BUDGET_BYTES", whole // 3)
    assert pa.row_tile(S, H // KV, KV, hd, ps, 4, 4, 4) == 8  # 4 tiles
    for cl, nn in (([0, 5, 77, 140], [32, 32, 3, 9]),
                   ([20, 33, 150, 0], [1, 17, 32, 0])):
        cl, nn = jnp.asarray(cl, jnp.int32), jnp.asarray(nn, jnp.int32)
        out = pa.paged_attention_kernel(
            q, k[None], v[None], cl, pt, layer=0, num_new=nn, block_k=16,
            interpret=True, window=window)
        want = _dense_cached_attention(
            cfg, q, _paged_gather(k, pt), _paged_gather(v, pt), cl,
            window=window)
        for b in range(B):
            n = int(nn[b])
            np.testing.assert_allclose(out[b, :n], want[b, :n], atol=2e-5,
                                       rtol=2e-5)
            if not n:  # an idle slot's rows are zeros in every tile
                assert not np.asarray(out[b]).any()


def test_the_tiled_grid_says_where_it_stops():
    # Command A+'s calls: whole at 128 rows, tiles of 16 from 256 on
    for S, rows in ((128, 128), (256, 16), (512, 16)):
        assert pa.row_tile(S, 16, 8, 128, 16, 32, 2, 2) == rows
    # Mixtral's and Mellum's stay one program a slot
    assert pa.row_tile(128, 4, 8, 128, 16, 32, 2, 2) == 128
    assert pa.row_tile(128, 8, 4, 128, 16, 32, 2, 2) == 128
    # and a chunk no tile of which fits is refused with the reason
    pool = jnp.zeros((1, 9, 16, 1, 128), jnp.bfloat16)
    out, why = pa.paged_attention(
        jnp.zeros((1, 4100, 2048, 128), jnp.bfloat16), pool, pool,
        jnp.zeros((1,), jnp.int32), jnp.zeros((1, 8), jnp.int32), layer=0,
        interpret=True)
    assert out is None and "no tile" in why[0]
