"""The families' plain references (float32, written from the published
descriptions) against deepspeed_tpu.models at tiny sizes in float32, and the
comparison that decides ``correct`` for served tokens."""

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


def test_mixtral_logits_agree_with_the_program():
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
    want, _ = model.apply(params, jnp.asarray(ids[None]), dtype=jnp.float32)
    got = mixtral_family.logits(params, ids, shape)
    np.testing.assert_allclose(got, want[0], atol=2e-4, rtol=0)
    last, margin = mixtral_family.logits(params, ids, shape, last=5,
                                            with_margin=True)
    np.testing.assert_allclose(last, got[-5:], atol=1e-6)
    # the margin is a difference of router probabilities: in (0, 1)
    assert margin.shape == (5,) and (margin > 0).all() and (margin < 1).all()


def test_served_token_gaps():
    logits = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]])
    np.testing.assert_allclose(reference.served_token_gaps(logits, [1, 0]), 0)
    np.testing.assert_allclose(reference.served_token_gaps(logits, [1, 2]),
                               [0.0, 0.5])


CC = {"logit_tol": 0.2, "min_margin": 0.01, "max_unjudged_share": 0.5,
      "min_judged": 3}


@pytest.mark.parametrize("gaps,margins,fault", [
    ([0.0, 0.1, 0.0, 0.15], [0.5, 0.02, 0.3, 0.01], None),
    # a near-tie in the routing is set aside, whatever its gap
    ([0.0, 3.0, 0.0, 0.1], [0.5, 0.009, 0.3, 0.2], None),
    # a fault of a few tenths at a judged position is a fault
    ([0.0, 0.25, 0.0, 0.1], [0.5, 0.02, 0.3, 0.2], "near-argmax"),
    # too few judged, by count and by share
    ([0.0, 0.0, 0.0, 0.0, 0.0, 0.0], [0.5, 0.5, 0.0, 0.0, 0.0, 0.0], "only 2"),
    ([0.0] * 7, [0.5, 0.5, 0.5, 0.0, 0.0, 0.0, 0.0], "set aside"),
    ([0.0, 0.0], [0.0, 0.0], "only 0"),
])
def test_the_serving_comparison(gaps, margins, fault):
    faults, worst, judged = reference.judge_served(gaps, margins, CC)
    assert judged == sum(m >= 0.01 for m in margins)
    if fault is None:
        assert faults == [] and worst <= 0.2
    else:
        assert any(fault in f for f in faults), faults


def test_a_dense_family_routes_nothing_so_every_token_is_judged():
    _model, shape, params = tiny_bloom()
    _logits, margin = bloom_family.logits(params, np.arange(8), shape, last=4,
                                          with_margin=True)
    faults, _, judged = reference.judge_served([0.0] * 4, margin, CC)
    assert judged == 4 and faults == []
