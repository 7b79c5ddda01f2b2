"""Brumby-14B-Base on the serving path, float32 on the CPU at a tiny size:
power-retention layers (a state a kv head and its normaliser a slot, NO page
in any layer) against the benchmark's plain reference
(benchmarks/families/brumby.py), which has no state and no feature map."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import brumby
from deepspeed_tpu.models.mixers import layer_plan, walk_runs
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.serving import Request

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks import reference as ref  # noqa: E402
from benchmarks.families import brumby as fam  # noqa: E402
from slot_program import (drive, ids_of, init_params,  # noqa: E402
                          reference_logits, schedule)

F32 = jnp.float32
logits_of = reference_logits(fam)
# float32 against float32 on logits whose spread is about 1: what is left is
# the order of the sums, and the state's way to (q . k) ** 2, a sum of 136
# products of pairs where the reference squares one dot product: a weight
# that is small against |q|^2 |k|^2 / hd carries about 1e-5 of relative
# rounding into a row whose normaliser is small
TOL = 3e-4
W, SLOTS = 16, 3
SERVING = dict(max_slots=SLOTS, token_budget=W, max_tokens=240, paged=True,
               prefix_cache=False)
IDS = [0, 1, 2]
CONFIG = dict(
    family="brumby", hidden_size=64, num_hidden_layers=3,
    num_attention_heads=10, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, vocab_size=512, rms_norm_eps=1e-6,
    rope_theta=1000000, hidden_act="silu", attention_bias=False,
    tie_word_embeddings=False, layer_ids=IDS,
    assumed=dict(degree=2, normaliser_eps=1e-6))


@pytest.fixture(scope="module")
def model():
    # weights five times the preset's spread, so that the mixers weigh as
    # much as the residual stream and a fault in one shows in the logits
    return brumby("brumby-tiny", layer_ids=IDS, initializer_range=0.1)


@pytest.fixture(scope="module")
def params(model):
    # gates that remember: log sigmoid(x W_g + 3) is about -0.05 a row with a
    # spread, so a state carries hundreds of rows and a fault in what it
    # holds shows (the benchmark's draw forgets in a few tokens: PERF.md
    # section 7)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 4 if (getattr(path[-1], "key", "") == "wg"
                                  and a.shape[-1] == 2) else a,
        init_params(model))


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(CONFIG)


def test_one_run_of_one_kind_and_no_page_anywhere(model):
    """The walk needed no change: one run of one kind, period 1, the dense
    MLP in the mixer's stack; no layer keeps a page; ``num_params()`` is the
    count of leaves, at the tiny size and at the published one."""
    cfg = model.config
    assert [(l.mixer, l.mlp, l.stack, l.mlp_stack) for l in layer_plan(cfg)
            ] == [("retention", "dense", "retention_layers",
                   "retention_layers")] * 3
    (run,) = walk_runs(cfg)
    assert (run.trips, run.period) == (3, (("retention", "dense"),))
    assert cfg.has_state and cfg.paged_layers == 0 and not cfg.is_latent
    for m in (model, brumby("brumby-14b", layer_ids=range(8))):
        got = sum(a.size for a in jax.tree.leaves(
            jax.eval_shape(lambda m=m: m.init(jax.random.PRNGKey(0)))))
        assert m.num_params() == got
    assert brumby("brumby-14b", layer_ids=range(8)).num_params() == (
        4_198_652_928)
    with pytest.raises(ValueError, match="published order"):
        brumby("brumby-tiny", layer_ids=[2, 1])


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_slots_at_different_frontiers_match_the_reference(model, params,
                                                          shape, kernels):
    """Prefill in chunks through the one slot step with packed rows: three
    slots at different frontiers and chunk sizes, then one-row steps
    (decode), then slot 1 taken by a SECOND request from position 0 (its
    state and normaliser start from zero, whatever the first left). Logits
    of every row against the reference's full forward in the attention
    form, with the kernel (interpret mode) and without."""
    seqs = {0: ids_of(29, 1), 1: ids_of(13, 2), 2: ids_of(9, 3)}
    feeds = schedule(seqs, {0: 7, 1: 5, 2: 2})
    more = {s: ids_of(3, 10 + s) for s in seqs}
    for j in range(3):  # decode rows, all three slots in a step
        feeds.append({s: (more[s][j:j + 1], len(seqs[s]) + j) for s in seqs})
    again = ids_of(11, 7)
    feeds += schedule({1: again}, {1: 6})
    # the pageless arena: a page table of one column that no layer reads
    got, caches = drive(model, params, feeds, slots=SLOTS, width=W,
                        pages_per_slot=1, page_size=256, kernels=kernels)
    assert set(caches) == {"state", "norm"}
    for s in seqs:
        ids = np.concatenate([seqs[s], more[s]])
        want = np.asarray(logits_of(params, ids, shape))
        have = np.concatenate(got[s])[:len(ids)]
        assert np.abs(have - want).max() < TOL, (s, np.abs(have - want).max())
    want = np.asarray(logits_of(params, again, shape))
    have = np.concatenate(got[1])[len(seqs[1]) + 3:]
    assert np.abs(have - want).max() < TOL


def test_a_pageless_engine_serves_what_the_reference_predicts(model, params,
                                                              shape):
    """Through init_serving (scheduler, overlapped step order, packed rows):
    four requests over three slots, so one slot is reused; every served
    token is the reference's argmax for its context, or within TOL of it.
    The arena is the two slot leaves: a page is a slot's whole length, one a
    slot, whatever ``page_size`` and ``num_pages`` ask."""
    srv = deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, page_size=16, num_pages=7),
        params=params, dtype=F32)
    assert srv.row_layout == "packed" and srv.step_order == "overlapped"
    assert (srv.page_size, srv.pages_per_slot, srv.num_pages) == (
        240 + W, 1, SLOTS)
    assert set(srv._caches) == {"state", "norm"}
    prompts = [ids_of(n, 20 + i) for i, n in enumerate((37, 5, 50, 21))]
    states = [srv.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=6, temperature=0.0,
        eos_token_id=-1)) for i, p in enumerate(prompts)]
    srv.run_until_idle()
    for p, st in zip(prompts, states):
        assert len(st.tokens) == 6
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        logits = logits_of(params, ids[:-1], shape, last=6)
        assert ref.served_token_gaps(logits, st.tokens).max() < TOL
    d = srv.describe()
    assert d["paged_layers"] == 0
    assert d["attention"] == {"retention": {"path": "dense", "reasons": [
        "the registered attention is not the kernel one"]}}
    R = 16 // 2 + 1
    assert d["state_leaves"] == {"state": 3 * SLOTS * 2 * R * 16 * 16 * 4,
                                 "norm": 3 * SLOTS * 2 * R * 16 * 4}
    snap = srv.metrics.snapshot()
    assert snap["state_resets"] == 4
    assert snap["state_bytes"] == sum(d["state_leaves"].values())
    assert snap["attention_paged_kernel_retention"] == 0.0
    # the counts a step's annotation carries, by kind
    from deepspeed_tpu.serving.engine import _KIND_COUNTS

    cl, nn = np.array([0, 40, 7]), np.array([5, 1, 0])
    assert _KIND_COUNTS["retention"](srv, cl, nn) == {
        "retention_rows": 6, "retention_state_slots": 2, "state_resets": 1}
    # every kind ``mixer_types`` may name has its counter, and no other
    from deepspeed_tpu.models.transformer import MIXER_KINDS

    assert set(_KIND_COUNTS) == {
        k for k, kind in MIXER_KINDS.items()
        if kind.family != "decoding" or kind.stacked_by} >= {
        "sparse", "lightning", "kda", "latent", "mla", "retention"}


def test_the_kernel_path_is_taken_and_named(model, params):
    with attention_impl("flash"):
        srv = deepspeed_tpu.init_serving(model, serving=SERVING,
                                         params=params, dtype=F32)
    assert srv.attention_paths == {"retention": "retention_kernel"}
    assert srv.describe()["attention"]["retention"]["reasons"] == []
    assert srv.metrics.snapshot()["attention_paged_kernel_retention"] == 1.0


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference(params, shape, fault):
    """Each name in FAULTS changes the reference's logits by far more than
    the tolerance the engine is held to (150 tokens: the chunk faults bite
    at row 128)."""
    ids = ids_of(150, 31)
    sound = np.asarray(fam.logits(params, ids, shape))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, **fam.faulted(params, fault, shape)))
    assert np.abs(broken - sound).max() > 20 * TOL, fault


def test_what_cannot_be_built_is_refused_in_words(model, params):
    with pytest.raises(DeepSpeedConfigError, match="paged arena"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))
    serve = lambda **over: deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, **over), params=params, dtype=F32)
    nothing = r"holds nothing \(no layer of this model keeps a page\)"
    with pytest.raises(DeepSpeedConfigError,
                       match=r"state layers \(retention\).*rolled back"):
        serve(spec=dict(enabled=True, max_draft=2))
    with pytest.raises(DeepSpeedConfigError,
                       match="host_pages is refused.*" + nothing):
        serve(host_pages=8)
    with pytest.raises(DeepSpeedConfigError,
                       match="prefill_replicas is refused.*" + nothing):
        serve(fleet=dict(prefill_replicas=1))
    with pytest.raises(DeepSpeedConfigError, match="paged false is refused"):
        serve(paged=False)
    srv = serve(prefix_cache=True)  # off, with the reason logged: a prefix
    assert srv.scheduler.prefix_cache is None  # hit has no state to resume
    with pytest.raises(RuntimeError, match="export_kv_pages: a page "
                       + nothing):
        srv.export_kv_pages([0])
