"""The families' plain references (float32, written from the published
descriptions) against deepspeed_tpu.models at tiny sizes in float32, and the
comparison that decides ``correct`` for served tokens."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference
from benchmarks.families import bloom as bloom_family
from benchmarks.families import mixtral as mixtral_family
from deepspeed_tpu.models import bloom, mixtral


def tiny_bloom():
    model = bloom("bloom-tiny", vocab_size=512, max_seq_len=64)
    shape = bloom_family.shape_of({
        "family": "bloom", "hidden_size": 128, "n_layer": 2, "n_head": 4,
        "vocab_size": 512, "layer_norm_epsilon": 1e-5})
    params = model.init(jax.random.PRNGKey(1), dtype=jnp.float32)
    # give the biases and norms something to do
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        params)
    return model, shape, params


def test_bloom_logits_and_loss_agree_with_the_program():
    model, shape, params = tiny_bloom()
    ids = np.random.RandomState(0).randint(0, 512, size=(2, 64))
    want, _ = model.apply(params, jnp.asarray(ids), dtype=jnp.float32)
    for b in range(2):
        got = bloom_family.logits(params, ids[b], shape)
        # float32 both sides; the program's erf GELU against BLOOM's tanh
        # form is the largest term (about 1e-4 on a logit here)
        np.testing.assert_allclose(got, want[b], atol=2e-3, rtol=0)
    from deepspeed_tpu.models.transformer import make_lm_batch

    loss, _ = model.loss(params, make_lm_batch(jnp.asarray(ids[:1])),
                         dtype=jnp.float32, train=False)
    assert bloom_family.loss(params, ids[0], shape) == pytest.approx(
        float(loss), rel=1e-4)


def tiny_mixtral():
    # capacity factor 4 x top-2 >= 4 experts: nothing dropped, as published
    model = mixtral("mixtral-tiny", vocab_size=512, max_seq_len=64,
                    moe_capacity_factor=4.0)
    shape = mixtral_family.shape_of({
        "family": "mixtral", "hidden_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 256, "vocab_size": 512, "num_local_experts": 4,
        "num_experts_per_tok": 2, "rms_norm_eps": 1e-5, "rope_theta": 1e6})
    params = model.init(jax.random.PRNGKey(2), dtype=jnp.float32)
    params = jax.tree.map(lambda a: a * 3.0, params)  # spread the router
    ids = np.random.RandomState(1).randint(0, 512, size=(48,))
    return model, shape, params, ids


def test_mixtral_logits_agree_with_the_program():
    model, shape, params, ids = tiny_mixtral()
    want, _ = model.apply(params, jnp.asarray(ids[None]), dtype=jnp.float32)
    got = mixtral_family.logits(params, ids, shape)
    np.testing.assert_allclose(got, want[0], atol=2e-4, rtol=0)
    last, margin = mixtral_family.logits(params, ids, shape, last=5,
                                            with_margin=True)
    np.testing.assert_allclose(last, got[-5:], atol=1e-6)
    # the margin is a difference of router logits, the larger first
    assert margin.shape == (5,) and (margin > 0).all()
    h = reference.rmsnorm(jnp.ones((3, 8)), {"scale": jnp.ones(8)}, 1e-5)
    router = jnp.eye(8)[:, :4] * jnp.array([4.0, 3.0, 1.0, 0.5])
    _, w, m = mixtral_family._route(jnp.ones((3, 8)), {"scale": jnp.ones(8)},
                                    router, top_k=2, eps=1e-5)
    np.testing.assert_allclose(m, 2.0 * h[0, 0], rtol=1e-6)  # 3 - 1
    np.testing.assert_allclose(w[0], [np.e / (1 + np.e), 1 / (1 + np.e), 0, 0],
                               rtol=1e-5)


@pytest.mark.parametrize("fault", mixtral_family.FAULTS)
def test_a_fault_of_the_mixtral_reference_moves_its_logits(fault):
    _model, shape, params, _ids = tiny_mixtral()
    # long enough to hold a whole 128-position chunk before the last tokens
    ids = np.random.RandomState(3).randint(0, 512, size=(300,))
    clean = mixtral_family.logits(params, ids, shape, last=8)
    handed = mixtral_family.faulted(params, fault, shape)
    # only the faults that need the reference's internals reach it by name
    assert (handed.get("fault") is not None) == (fault in mixtral_family.INNER)
    got = mixtral_family.logits(ids=ids, shape=shape, last=8, **handed)
    assert np.isfinite(np.asarray(got)).all()
    # int8 is the precision just below the served one: it moves little
    assert float(jnp.abs(got - clean).max()) > (
        1e-4 if fault == "weights_int8" else 1e-3)


@pytest.mark.parametrize("how", ["faulted", "logits"])
def test_an_unknown_fault_is_refused(how):
    _model, shape, params, ids = tiny_mixtral()
    with pytest.raises(ValueError, match="no fault"):
        if how == "faulted":
            mixtral_family.faulted(params, "nope", shape)
        else:  # a fault of the weights is not the reference's to make
            mixtral_family.logits(params, ids, shape, fault="weights_int8")


def test_no_fault_hands_the_reference_what_was_served():
    _model, shape, params, _ids = tiny_mixtral()
    assert mixtral_family.faulted(params, None, shape) == dict(
        params=params, fault=None)


def test_a_mispaired_head_is_the_rolled_projection():
    """``gqa_mispaired`` as a transform of the weights equals rolling the
    K/V heads after the projection."""
    _model, shape, params, _ids = tiny_mixtral()
    a = mixtral_family.faulted(params, "gqa_mispaired", shape)[
        "params"]["layers"]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(2), (5, shape.d))
    for name in ("wk", "wv"):
        plain = (h @ params["layers"]["attn"][name][0]).reshape(
            5, shape.kv_heads, shape.hd)
        np.testing.assert_allclose(
            (h @ a[name][0]).reshape(5, shape.kv_heads, shape.hd),
            jnp.roll(plain, 1, axis=1), atol=1e-6)


def test_rope_from_another_first_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 2, 8))
    np.testing.assert_allclose(
        reference.rope(x, 1e4, first=1),
        reference.rope(jnp.concatenate([x[:1], x]), 1e4)[1:], atol=1e-6)


def test_served_token_gaps():
    logits = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]])
    np.testing.assert_allclose(reference.served_token_gaps(logits, [1, 0]), 0)
    np.testing.assert_allclose(reference.served_token_gaps(logits, [1, 2]),
                               [0.0, 0.5])


CC = {"logit_tol": 0.2, "min_near_share": 0.8, "min_margin": 0.15,
      "outlier_tol": 0.75, "min_judged": 3}
# a run of 48 as the chip gives them: argmaxes, clear of a near-tie
RUN = ([0.0] * 48, [0.3] * 48)


def run_with(*tokens):
    """RUN with its first tokens replaced by (gap, margin) pairs."""
    gaps, margins = list(RUN[0]), list(RUN[1])
    for i, (g, m) in enumerate(tokens):
        gaps[i], margins[i] = g, m
    return gaps, margins


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "tests", "benchmark",
                       "recorded_served_pairs.json")) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize("gaps,margins,fault", [
    ([0.0, 0.1, 0.0, 0.15], [0.5, 0.2, 0.3, 0.1], None),
    # a near-tie in the routing is excused by the outlier rule, whatever its
    # gap, and counted by the share rule: 1 of 4 over is too many
    ([0.0, 3.0, 0.0, 0.1], [0.5, 0.09, 0.3, 0.2], "too few served tokens"),
    # a few tenths at a clear position is no outlier; the share rule counts it
    ([0.0, 0.25, 0.0, 0.1], [0.5, 0.2, 0.3, 0.2], "too few served tokens"),
    ([0.0] * 9 + [0.7], [0.5] * 10, None),
    # too few clear of a near-tie, by count; their share no longer matters
    ([0.0, 0.0, 0.0, 0.0, 0.0, 0.0], [0.5, 0.5, 0.0, 0.0, 0.0, 0.0], "only 2"),
    ([0.0] * 7, [0.5, 0.5, 0.5, 0.0, 0.0, 0.0, 0.0], None),
    ([0.0, 0.0], [0.0, 0.0], "only 0"),
    # the share rule: 9 of 48 over the tolerance at near-ties pass, 10 do not
    (*run_with(*[(3.0, 0.001)] * 9), None),
    (*run_with(*[(3.0, 0.001)] * 10), "too few served tokens"),
    (*run_with(*[(0.21, 0.5)] * 10), "too few served tokens"),
    # the outlier rule: one token far off where the routing is clear
    (*run_with((0.8, 0.25)), "far from the reference's argmax"),
    (*run_with((0.8, 0.1)), None),
    (*run_with((0.75, 0.25)), None),
    # enough to judge
    ([0.0] * 48, [0.5, 0.5] + [0.14] * 46, "only 2"),
])
def test_the_serving_comparison(gaps, margins, fault):
    faults, n = reference.judge_served(gaps, margins, CC)
    assert n["tokens"] == len(gaps)
    assert n["clear"] == sum(m >= 0.15 for m in margins)
    assert n["near"] == sum(g <= 0.2 for g in gaps)
    if fault is None:
        assert faults == [] and n["outliers"] == 0
    else:
        assert any(fault in f for f in faults), faults


@pytest.mark.parametrize("misses,fault", [
    (0, None), (153, None), (154, "the reference's argmax"),
    (700, "the reference's argmax")])
def test_the_exact_rule_of_the_precision_sample(misses, fault):
    """Over the precision sample's 768 tokens only the share of exact
    argmaxes is judged: near-ties and margins play no part."""
    gaps = [3.0] * misses + [0.0] * (768 - misses)
    faults, n = reference.judge_served(gaps, [0.0] * 768,
                                       {"min_argmax_share": 0.8})
    assert n == {"tokens": 768, "argmax": 768 - misses}
    assert faults == [] if fault is None else fault in faults[0]


@pytest.mark.parametrize("mix,sound,control", [
    ("chat", 666, 547), ("longdoc", 688, 583)])
def test_the_precision_limit_lies_between_the_chips_readings(mix, sound,
                                                             control):
    """The fewest exact argmaxes of 768 that a sound run of the unchanged
    tree gave on the chip (16 and 12 seeds) and the most that the control,
    the reference with int8 weights, gave (8 and 6 seeds; PERF.md section
    6, PR 27): the mix's limit refuses the one and passes the other, with
    room on both sides, and the sample is what the engine's slots hold."""
    with open(os.path.join(ROOT, "benchmarks", "traffic", mix + ".json")) as f:
        pc = json.load(f)["correctness"]["precision"]
    assert len(pc["prompts"]) * pc["new_tokens"] == 768
    assert len(pc["prompts"]) <= 16
    limit = pc["min_argmax_share"] * 768
    assert control + 60 < limit < sound - 30
    for argmax, correct in ((sound, True), (control, False)):
        gaps = [0.0] * argmax + [1.0] * (768 - argmax)
        faults, _ = reference.judge_served(gaps, [0.0] * 768, pc)
        assert (faults == []) == correct


@pytest.mark.parametrize("run", [
    "mixtral8x7b-chat|3000000011", "mixtral8x7b-chat|3333333403",
    "mixtral8x7b-longdoc|3000000041"])
def test_the_runs_the_old_rule_refused_read_correct(run):
    """What the chip printed for the unchanged tree at the three seeds of
    ISSUE 27 (margins as router-logit differences), judged by the mix's own
    ``correctness`` block; the old rule's reading for comparison."""
    gaps, margins = map(np.array, zip(*RECORDED[run]))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cells[run.split("|")[0]]["traffic"] + ".json")) as f:
        cc = json.load(f)["correctness"]
    faults, n = reference.judge_served(gaps, margins, cc)
    assert faults == [] and n["tokens"] == 48 and n["near"] >= 44
    # a near-tie the old rule judged: a gap over its tolerance of 0.2 at a
    # margin the new rule knows to be inside bf16's reach
    assert ((gaps > 0.2) & (margins < cc["min_margin"])).any()
    assert gaps[margins >= cc["min_margin"]].max() <= 0.2


@pytest.mark.parametrize("clear,at", [(True, 1), (False, 0)])
def test_one_swapped_token(clear, at):
    tokens, margins = [5, 6, 511], [0.01, 0.3, 0.4]
    got = reference.swap_one_token(tokens, margins, 0.15, clear, vocab=512)
    want = list(tokens)
    want[at] += 1
    assert list(got) == want and tokens == [5, 6, 511]
    # no such position: nothing to swap; the vocabulary wraps
    assert list(reference.swap_one_token([511], [0.5], 0.15, True, 512)) == [0]
    assert list(reference.swap_one_token([7], [0.5], 0.15, False, 512)) == [7]


def test_a_dense_family_routes_nothing_so_every_token_is_judged():
    _model, shape, params = tiny_bloom()
    _logits, margin = bloom_family.logits(params, np.arange(8), shape, last=4,
                                          with_margin=True)
    faults, n = reference.judge_served([0.0] * 4, margin, CC)
    assert n["clear"] == 4 and faults == []
