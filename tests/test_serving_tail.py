"""The serving step's tail computes what the sampler reads (ISSUE 35).

Rows: ``forward_with_cache`` norms and projects only the rows named by
``logit_rows`` (each slot's verify window), and those logits are the same
rows of the whole-chunk logits. Filters: the sampler's top-k / top-p
sorts run under one ``lax.cond`` a step, taken when a live slot asks for
them. The oracle of both is the tail as it was before: whole-chunk
logits, a gather of the window, and ``sample_one`` vmapped over the
slots with its filters always on — kept here, word for word.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.serving.engine as serving_engine
from deepspeed_tpu.models import llama
from deepspeed_tpu.models.decoding import (forward_with_cache, init_cache,
                                           verify_window_rows)
from deepspeed_tpu.serving import Request, ServingEngine
from deepspeed_tpu.serving.engine import _book_seen, _make_sample_window
from deepspeed_tpu.serving.spec import verify_window
from layer_loop_oracle import paged_setup, random_cache

VOCAB = 128


def tiny_llama(**kw):
    d = dict(vocab_size=VOCAB, max_seq_len=64, hidden_size=32, num_layers=2,
             num_heads=4, num_kv_heads=2, intermediate_size=64)
    d.update(kw)
    return llama("llama-tiny", **d)


# ---------------------------------------------------------------------------
# the oracle: the tail before ISSUE 35
# ---------------------------------------------------------------------------
def parent_sample_one(vocab):
    """``_make_sample_one`` as it was: both sorts, the softmax and the
    cumulative sum for every slot, discarded through ``where`` gates."""

    def sample_one(row, key, temp, tk, tp_):
        l = row[None, :] / jnp.maximum(temp, 1e-6)
        sorted_desc = jnp.sort(l, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(tk, 1, vocab).reshape(1, 1) - 1, axis=-1
        )
        l = jnp.where((tk > 0) & (l < kth), -1e30, l)
        nuc = jnp.sort(l, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(nuc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < tp_
        keep = keep.at[:, 0].set(True)
        pth = jnp.min(jnp.where(keep, nuc, jnp.inf), axis=-1, keepdims=True)
        l = jnp.where((tp_ < 1.0) & (l < pth), -1e30, l)
        greedy = jnp.argmax(l, axis=-1)
        sampled = jax.random.categorical(key, l, axis=-1)
        return jnp.where(temp == 0.0, greedy, sampled)[0]

    return sample_one


def parent_sample_window(vocab):
    """The old sampler in the shape ``verify_window`` calls: position j of
    the window is ``vmap(sample_one)`` over the slots, as its loop ran."""
    one = jax.vmap(parent_sample_one(vocab))

    def sample_window(win, keys, live, temp, tk, tp_):
        return jnp.stack([one(win[:, j], keys[:, j], temp, tk, tp_)
                          for j in range(win.shape[1])], axis=1)

    return sample_window


def parent_step_fn(cfg, dtype, vocab, cache_shardings=None, max_draft=0):
    """``make_step_fn`` with the tail both step functions had: logits of
    every row of the chunk, the window gathered from them."""
    sample_window = parent_sample_window(vocab)

    def step(params, caches, seen, tokens, num_new, start_pos, fresh,
             sample_flag, spec_len, eos_id, rng, temperature, top_k, top_p,
             rep_penalty, from_prev, prev_tok, prev_rng, page_table=None,
             page_table_win=None):
        # (ISSUE 37's operands, as the engine now hands them to any step:
        # a row fed by the step in flight takes its token and key there)
        tokens = tokens.at[:, 0].set(
            jnp.where(from_prev, prev_tok[:, 0], tokens[:, 0]))
        rng = jnp.where(from_prev[:, None], prev_rng, rng)
        live = sample_flag & (num_new > 0)
        seen = _book_seen(seen, tokens, num_new, spec_len, fresh, vocab)
        logits, caches = forward_with_cache(
            cfg, params, tokens, caches, start_pos, dtype=dtype,
            page_table=page_table, page_table_win=page_table_win,
            num_new=num_new,
        )
        rows = verify_window_rows(num_new, spec_len, max_draft,
                                  tokens.shape[1])
        win = jnp.take_along_axis(logits, rows[:, :, None], axis=1)
        return (caches, seen) + verify_window(
            sample_window, win, tokens, rows, seen, spec_len, live, rng,
            temperature, top_k, top_p, rep_penalty, eos_id,
        )

    return step


# ---------------------------------------------------------------------------
# rows
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("max_draft", [0, 2])
def test_window_logits_are_the_whole_chunks_rows(max_draft, layout):
    """A ragged batch — an idle slot, a one-token decode row, a full chunk
    and (with drafts) a verify window: the logits of the rows asked for
    are those rows of the whole-chunk logits, and the cache is written
    alike."""
    model = tiny_llama(num_layers=3)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(1), dtype=jnp.float32)
    B, W = 4, 8
    ids = jnp.asarray(
        np.random.RandomState(3).randint(0, VOCAB, size=(B, W)))
    num_new = jnp.asarray([0, 1, W, 1 + max_draft], jnp.int32)
    spec_len = jnp.asarray([0, 0, 0, max_draft], jnp.int32)
    frontier = jnp.asarray([0, 9, 5, 11], jnp.int32)
    rows = verify_window_rows(num_new, spec_len, max_draft, W)
    assert rows.shape == (B, max_draft + 1)
    # idle reads row 0, decode its one token, the chunk its last, the
    # window its committed token and then its drafts
    np.testing.assert_array_equal(np.asarray(rows[:, 0]), [0, 0, W - 1, 0])
    np.testing.assert_array_equal(
        np.asarray(rows[3]), np.arange(max_draft + 1))
    kw = {}
    if layout == "paged":
        cache, table = paged_setup(cfg, B, 4, 8, False, seed=5)
        kw = dict(page_table=table, num_new=num_new)
    else:
        cache = random_cache(init_cache(cfg, B, 32, jnp.float32), 5)
    fwd = jax.jit(lambda r: forward_with_cache(
        cfg, params, ids, cache, frontier, dtype=jnp.float32,
        logit_rows=r, **kw))
    whole, whole_cache = jax.jit(lambda: forward_with_cache(
        cfg, params, ids, cache, frontier, dtype=jnp.float32, **kw))()
    got, got_cache = fwd(rows)
    assert whole.shape == (B, W, VOCAB)
    assert got.shape == (B, max_draft + 1, VOCAB) and got.dtype == whole.dtype
    want = np.take_along_axis(
        np.asarray(whole), np.asarray(rows)[:, :, None], axis=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    for n in whole_cache:
        np.testing.assert_array_equal(
            np.asarray(got_cache[n]), np.asarray(whole_cache[n]), err_msg=n)


# ---------------------------------------------------------------------------
# replays: token for token what the parent's tail serves
# ---------------------------------------------------------------------------
SAMPLED = [
    dict(temperature=0.8, top_k=10, top_p=1.0),
    dict(temperature=0.7, top_k=0, top_p=0.85),
    dict(temperature=0.9, top_k=20, top_p=0.9, repetition_penalty=1.3),
    dict(temperature=0.6),  # temperature alone rides in the same batch
]
REPLAYS = {
    # test_serving.test_greedy_parity_staggered_arrivals' replay
    "greedy": dict(
        serving=dict(max_slots=3, token_budget=8, max_tokens=64),
        lengths=[(3, 6), (12, 4), (7, 8), (5, 5), (9, 3)],
        sampling=[{}] * 5),
    # test_serving.test_sampled_parity_shared_keys' mixes, and one more
    "sampled": dict(
        serving=dict(max_slots=3, token_budget=8, max_tokens=64),
        lengths=[(6, 8), (9, 8), (4, 8), (11, 8)], sampling=SAMPLED),
    "sampled-paged": dict(
        serving=dict(max_slots=3, token_budget=8, max_tokens=64, paged=True,
                     page_size=4),
        lengths=[(6, 8), (9, 8), (4, 8), (11, 8)], sampling=SAMPLED),
    # test_serving_spec's: drafts verified over a window of 5 rows a slot
    "spec": dict(
        serving=dict(max_slots=3, token_budget=16, max_tokens=64,
                     spec={"enabled": True, "max_draft": 4}),
        lengths=[(8, 10), (6, 10), (8, 10), (6, 10)],
        sampling=[{}, dict(temperature=0.8, top_k=10), {},
                  dict(temperature=0.7, top_p=0.85)]),
}


def _replay(eng, serving, lengths, sampling):
    """Two requests up front, the rest arriving while the batch runs."""
    srv = ServingEngine(engine=eng, serving=dict(serving))
    r = np.random.RandomState(0)
    # short cycles, so that the n-gram lookup has drafts to offer
    prompts = [np.resize(r.randint(0, VOCAB, size=(3,)), n)
               for n, _ in lengths]
    states = []
    for i, (p, (_, new), s) in enumerate(zip(prompts, lengths, sampling)):
        if i >= 2:
            srv.step()
        states.append(srv.submit(Request(
            request_id=f"r{i}", prompt=p, max_new_tokens=new,
            rng=jax.random.PRNGKey(100 + i), **s)))
    srv.run_until_idle()
    return srv, [(st.output(), np.asarray(st.rng)) for st in states]


@pytest.mark.parametrize("name", list(REPLAYS))
def test_replay_serves_the_parents_tokens(name, monkeypatch):
    """The same replay through the step as it is and through the step with
    the parent's tail: every request's tokens and the RNG chain it is
    left with, bit for bit; one compile each."""
    eng = deepspeed_tpu.init_inference(
        tiny_llama(), dtype=jnp.float32, max_tokens=64,
        rng=jax.random.PRNGKey(1))
    srv, got = _replay(eng, **REPLAYS[name])
    monkeypatch.setattr(serving_engine, "make_step_fn", parent_step_fn)
    parent, want = _replay(eng, **REPLAYS[name])
    assert srv.step_traces == parent.step_traces == 1
    assert srv.metrics.steps == parent.metrics.steps
    for i, ((tokens, rng), (want_tokens, want_rng)) in enumerate(
            zip(got, want)):
        assert len(tokens) == sum(REPLAYS[name]["lengths"][i])
        np.testing.assert_array_equal(tokens, want_tokens, err_msg=f"r{i}")
        np.testing.assert_array_equal(rng, want_rng, err_msg=f"r{i}")
    if name == "spec":
        assert srv.metrics.draft_tokens_accepted > 0


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------
BATCHES = {
    # greedy and temperature-only slots: the filters are not run
    "plain": dict(temp=[0.0, 0.8, 1.3, 0.0, 0.5, 0.0],
                  top_k=[0, 0, 0, 0, 0, 7], top_p=[1.0] * 5 + [0.5]),
    # one top-k and one top-p slot among them: the filters run for all
    "filtering": dict(temp=[0.0, 0.8, 0.9, 0.7, 0.5, 0.0],
                      top_k=[0, 0, 12, 0, 0, 7],
                      top_p=[1.0, 1.0, 1.0, 0.8, 1.0, 0.5]),
}


@pytest.mark.parametrize("max_draft", [0, 2])
@pytest.mark.parametrize("batch", list(BATCHES))
def test_gated_sampler_is_bitwise_the_old_one(batch, max_draft):
    """``verify_window`` through the gated sampler and through the old
    composition: emitted tokens, emitted counts and RNG chains bit for
    bit. The last slot is not live and asks for both filters: it must not
    take the branch, and nothing of it is emitted either way."""
    mix = BATCHES[batch]
    N, W, kw = len(mix["temp"]), 8, max_draft + 1
    r = np.random.RandomState(11)
    logits = jnp.asarray(r.randn(N, kw, VOCAB) * 3.0, jnp.float32)
    tokens = jnp.asarray(r.randint(0, VOCAB, size=(N, W)), jnp.int32)
    live = jnp.asarray([True] * (N - 1) + [False])
    spec_len = jnp.asarray([0, max_draft, 0, max_draft, 0, 0], jnp.int32)
    num_new = jnp.where(live, spec_len + 1, 0)
    rows = verify_window_rows(num_new, spec_len, max_draft, W)
    args = (
        logits, tokens, rows, jnp.asarray(r.rand(N, VOCAB) < 0.1), spec_len,
        live, jnp.asarray(r.randint(0, 2 ** 31, size=(N, 2)), jnp.uint32),
        jnp.asarray(mix["temp"], jnp.float32),
        jnp.asarray(mix["top_k"], jnp.int32),
        jnp.asarray(mix["top_p"], jnp.float32),
        jnp.asarray([1.0, 1.0, 1.2, 1.0, 1.0, 1.0], jnp.float32),
        jnp.full((N,), -1, jnp.int32),
    )
    new = jax.jit(lambda *a: verify_window(_make_sample_window(VOCAB), *a))
    old = jax.jit(lambda *a: verify_window(parent_sample_window(VOCAB), *a))
    (tok, n_emit, rng), (want_tok, want_emit, want_rng) = new(*args), old(*args)
    np.testing.assert_array_equal(np.asarray(n_emit), np.asarray(want_emit))
    np.testing.assert_array_equal(np.asarray(rng), np.asarray(want_rng))
    assert int(n_emit[-1]) == 0 and np.all(np.asarray(n_emit[:-1]) >= 1)
    emitted = np.arange(kw)[None, :] < np.asarray(n_emit)[:, None]
    np.testing.assert_array_equal(np.asarray(tok)[emitted],
                                  np.asarray(want_tok)[emitted])
    # one conditional a step, outside the per-slot vmap
    assert new.lower(*args).as_text().count("stablehlo.case") == 1


def test_filter_steps_counts_and_one_compile_serves_every_mix():
    """``filter_steps`` counts the steps in which a live slot asked for
    top-k or top-p, not the others; ``head_rows_per_step`` is the slots
    times the window; one compiled step serves both kinds of step."""
    eng = deepspeed_tpu.init_inference(
        tiny_llama(), dtype=jnp.float32, max_tokens=64,
        rng=jax.random.PRNGKey(4))
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 3, "token_budget": 8, "max_tokens": 64,
    })
    r = np.random.RandomState(2)

    def submit(rid, n, new, **kw):
        return srv.submit(Request(
            request_id=rid, prompt=r.randint(0, VOCAB, size=(n,)),
            max_new_tokens=new, rng=jax.random.PRNGKey(7), **kw))

    assert srv.metrics.snapshot()["head_rows_per_step"] == 3
    # the layers' rows: the budget's, packed, not every slot's chunk
    assert srv.metrics.snapshot()["dense_rows_per_step"] == 8
    assert (srv.row_layout, srv.row_layout_reason) == ("packed", None)
    submit("greedy", 5, 6)
    submit("warm", 9, 4, temperature=0.9)
    srv.run_until_idle()
    snap = srv.metrics.snapshot()
    assert snap["steps"] > 0 and snap["filter_steps"] == 0
    # a prompt of two chunks: its first step feeds and does not sample, so
    # it is not counted; each of its 5 tokens is one step that filters
    submit("topk", 12, 5, temperature=0.8, top_k=10)
    submit("greedy2", 4, 9)
    srv.run_until_idle()
    assert srv.metrics.snapshot()["filter_steps"] == 5
    steps = srv.metrics.steps
    submit("topp", 3, 4, temperature=0.7, top_p=0.8)
    srv.run_until_idle()
    snap = srv.metrics.snapshot()
    assert snap["filter_steps"] == 5 + 4 and snap["steps"] == steps + 4
    assert srv.step_traces == 1
    spec = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 64,
        "spec": {"enabled": True, "max_draft": 3},
    })
    assert spec.metrics.snapshot()["head_rows_per_step"] == 2 * 4
