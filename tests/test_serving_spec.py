"""Speculative decoding inside the slot serving engine (ISSUE 9).

The oracle: spec-on reproduces spec-off TOKEN-FOR-TOKEN — greedy and
sampled-with-shared-keys, contiguous and paged arenas, tp=2 and int8-KV —
because acceptance is sample-and-match against each slot's own
deterministic RNG chain (serving/spec.py). Drafts only change how many
verifier steps a generation needs, never its content. Plus: the
scheduler's k+1 budget-row accounting under a fake clock (k shrinks to 0
under pressure — plain decode is the graceful floor), paged-pool
refcount balance across rejection rollback and eviction, the shared
n-gram draft unit, spec metrics (honest multi-token TPOT), and the
shardlint serving trace with spec enabled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import llama
from deepspeed_tpu.serving import (Request, RequestStatus, Scheduler,
                                   ServingEngine, ServingMetrics)


def tiny_llama(**kw):
    d = dict(vocab_size=128, max_seq_len=64, hidden_size=32, num_layers=2,
             num_heads=4, num_kv_heads=2, intermediate_size=64)
    d.update(kw)
    return llama("llama-tiny", **d)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _submit(srv, rid, prompt, **kw):
    return srv.submit(Request(request_id=rid, prompt=prompt, **kw))


def _serve(eng, spec=True, **serving):
    d = dict(max_slots=3, token_budget=16, max_tokens=64)
    d.update(serving)
    d["spec"] = {"enabled": spec, "max_draft": 4}
    return ServingEngine(engine=eng, serving=d)


# repetitive prompts an untrained greedy model cycles on — the n-gram
# lookup finds the cycle, so drafts actually get accepted
REPETITIVE = [
    np.asarray([7, 3, 9, 7, 3, 9, 7, 3]),
    np.asarray([5, 11, 5, 11, 5, 11]),
    np.asarray([2, 2, 2, 2, 2, 2, 2, 2]),
]


# ---------------------------------------------------------------------------
# the losslessness oracle: spec-on == spec-off, bitwise
# ---------------------------------------------------------------------------
def test_spec_greedy_parity_and_acceptance():
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(1)
    )
    news = [24, 28, 24]
    off = _serve(eng, spec=False)
    on = _serve(eng, spec=True)
    sts_off, sts_on = [], []
    for srv, sts in ((off, sts_off), (on, sts_on)):
        for i, (p, n) in enumerate(zip(REPETITIVE, news)):
            sts.append(_submit(srv, f"r{i}", p, max_new_tokens=n))
        srv.run_until_idle()
    for a, b, p, n in zip(sts_off, sts_on, REPETITIVE, news):
        assert a.status is RequestStatus.DONE
        assert b.status is RequestStatus.DONE
        np.testing.assert_array_equal(a.output(), b.output())
        # and both match the lockstep single-request engine bitwise
        want = eng.generate(p[None, :], max_new_tokens=n, temperature=0.0)
        np.testing.assert_array_equal(b.output(), want[0])
    # ONE trace for the whole spec replay: per-slot draft counts are the
    # traced spec_len vector, never a shape
    assert on.step_traces == 1
    m = on.metrics
    assert m.draft_tokens_proposed > 0
    assert m.draft_tokens_accepted > 0, "no draft accepted on cycles"
    assert m.acceptance_rate > 0.0
    assert m.mean_accepted_tokens_per_step > 1.0
    # accepted drafts advance frontiers by >1/step: fewer decode steps
    assert on.metrics.steps < off.metrics.steps


def test_spec_sampled_parity_shared_keys():
    """Sampled decoding with per-request keys: sample-and-match keeps the
    RNG chain exactly where spec-off leaves it, so sampled outputs stay
    bitwise identical across temperature/top-k/top-p mixes — including a
    penalized request, which the scheduler never drafts for."""
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(2)
    )
    cases = [
        dict(temperature=0.8, top_k=10, top_p=1.0),
        dict(temperature=0.7, top_k=0, top_p=0.85),
        dict(temperature=0.9, top_k=20, top_p=0.9, repetition_penalty=1.3),
        dict(temperature=0.0),  # greedy rides in the same batch
    ]
    prompts = REPETITIVE + [np.asarray([7, 3, 9, 7, 3, 9])]
    keys = [jax.random.PRNGKey(200 + i) for i in range(len(cases))]
    outs = {}
    for spec in (False, True):
        srv = _serve(eng, spec=spec, max_slots=4)
        sts = [
            _submit(srv, f"s{i}", p, max_new_tokens=10, rng=keys[i], **c)
            for i, (p, c) in enumerate(zip(prompts, cases))
        ]
        srv.run_until_idle()
        outs[spec] = [st.output() for st in sts]
    for i, (a, b) in enumerate(zip(outs[False], outs[True])):
        np.testing.assert_array_equal(a, b, err_msg=f"case {i}")
        want = eng.generate(prompts[i][None, :], max_new_tokens=10,
                            rng=keys[i], **cases[i])
        np.testing.assert_array_equal(b, want[0], err_msg=f"lockstep {i}")


def test_spec_eos_clamps_advance():
    """An eos emitted mid-window must cut the advance (and the RNG chain)
    exactly where spec-off stops."""
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(3)
    )
    prompt = REPETITIVE[0]
    ref = eng.generate(prompt[None, :], max_new_tokens=16, temperature=0.0)
    eos = int(ref[0, prompt.size + 9])  # eos lands mid-generation
    want = eng.generate(prompt[None, :], max_new_tokens=16, temperature=0.0,
                        eos_token_id=eos)
    for spec in (False, True):
        srv = _serve(eng, spec=spec)
        st = _submit(srv, "e0", prompt, max_new_tokens=16, eos_token_id=eos)
        srv.run_until_idle()
        assert st.status is RequestStatus.DONE
        np.testing.assert_array_equal(st.output(), want[0],
                                      err_msg=f"spec={spec}")


def test_spec_tp2_int8_kv_parity():
    model = tiny_llama(num_heads=4, num_kv_heads=4)
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=jax.devices()[:2])
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, topology=topo,
        kv_cache_dtype="int8", rng=jax.random.PRNGKey(4),
    )
    outs = {}
    for spec in (False, True):
        srv = _serve(eng, spec=spec, max_slots=2)
        sts = [
            _submit(srv, f"q{i}", p, max_new_tokens=18)
            for i, p in enumerate(REPETITIVE[:2])
        ]
        srv.run_until_idle()
        outs[spec] = [st.output() for st in sts]
        assert srv.step_traces == 1
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)


def test_spec_paged_parity_and_page_invariants():
    """Paged arena + spec: rejected-window pages stay slot-owned (the
    scheduler's free+live==num_pages assertion runs every tick), outputs
    match the contiguous spec-off arena bitwise, prefix sharing and COW
    keep working underneath the verify windows."""
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(5)
    )
    news = [20, 24, 20]
    dense = _serve(eng, spec=False)
    paged = _serve(eng, spec=True, paged=True, page_size=8)
    outs = {}
    for key, srv in (("dense-off", dense), ("paged-on", paged)):
        sts = [
            _submit(srv, f"p{i}", p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(REPETITIVE, news))
        ]
        srv.run_until_idle()
        outs[key] = [st.output() for st in sts]
    for a, b in zip(outs["dense-off"], outs["paged-on"]):
        np.testing.assert_array_equal(a, b)
    assert paged.step_traces == 1
    # everything released: the pool drained back to fully free
    paged.scheduler.assert_page_invariants()
    assert paged.metrics.draft_tokens_proposed > 0


def test_spec_paged_pool_pressure_evicts_gracefully():
    """A pool too small for every spec window: draft growth shrinks under
    page pressure first; true starvation force-evicts the newest request
    (progress/RNG rewound) and the pool accounting stays balanced —
    resubmission reproduces the deterministic output."""
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(6)
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 3, "token_budget": 16, "max_tokens": 48,
        "paged": True, "page_size": 8, "num_pages": 10,  # floor is 8
        "spec": {"enabled": True, "max_draft": 4},
    })
    sts = [
        _submit(srv, f"v{i}", p, max_new_tokens=16)
        for i, p in enumerate(REPETITIVE)
    ]
    finished = srv.run_until_idle()
    evicted = [st for st in sts if st.status is RequestStatus.EVICTED]
    done_first = [st for st in sts if st.status is RequestStatus.DONE]
    assert done_first, "nothing finished under pool pressure"
    srv.scheduler.assert_page_invariants()
    # evicted requests resubmit and reproduce the same tokens the
    # unpressured engine produces
    for st in evicted:
        assert st.retry_after is not None
        srv.scheduler.resubmit(st)
    srv.run_until_idle()
    srv.scheduler.assert_page_invariants()
    for st in sts:
        assert st.status is RequestStatus.DONE
        want = eng.generate(st.request.prompt[None, :], max_new_tokens=16,
                            temperature=0.0)
        np.testing.assert_array_equal(st.output(), want[0])


# ---------------------------------------------------------------------------
# scheduler budget accounting (fake clock, no device work)
# ---------------------------------------------------------------------------
def _sched(clock, **kw):
    d = dict(max_slots=3, token_budget=16, queue_limit=8,
             request_timeout_s=1e9, eviction_backoff_s=1.0, max_tokens=64,
             clock=clock, metrics=ServingMetrics(clock=clock),
             spec_max_draft=4)
    d.update(kw)
    return Scheduler(**d)


def _req(rid, plen=4, new=20, **kw):
    return Request(request_id=rid, prompt=np.arange(plen) % 7,
                   max_new_tokens=new, **kw)


def _to_decode(s, rid, **kw):
    """Fast-forward one request to mid-DECODE (prompt cached, first token
    sampled) — the spec-eligible state."""
    st = s.submit(_req(rid, **kw))
    assert st.status is RequestStatus.PREFILL
    st.prompt_pos = st.prompt_len
    st.transition(RequestStatus.DECODE)
    st.tokens.append(1)
    return st


def test_scheduler_spec_decode_claims_k_plus_one_rows():
    clock = FakeClock()
    s = _sched(clock, max_slots=2, token_budget=16)
    st0 = _to_decode(s, "a")
    st1 = _to_decode(s, "b")
    plan = s.plan()
    assert plan is not None
    # both decode slots got their feed + the full k=4 drafts: 5 rows each
    assert sorted(plan.num_new[plan.num_new > 0].tolist()) == [5, 5]
    assert plan.spec_len[st0.slot] == 4 and plan.spec_len[st1.slot] == 4
    assert plan.total_tokens == 10  # (k+1) * 2 <= budget
    for w in plan.work:
        assert w.spec_len == 4 and w.n_tokens == 5 and w.sample


def test_scheduler_spec_verify_windows_count_under_decode_slots():
    """ISSUE 54: a verify window's rows (the committed feed and its drafts)
    are decode rows of a decode slot; none is a prompt row."""
    s = _sched(FakeClock(), max_slots=2, token_budget=16)
    _to_decode(s, "a")
    _to_decode(s, "b")
    plan = s.plan()
    # each slot: a prompt of 4 and one token behind the window's 5 rows
    assert plan.held() == dict(scheduled_tokens=10, prompt_rows=0,
                               prompt_slots=0, decode_slots=2,
                               context_tokens=2 * (4 + 5))
    assert not any(w.chunk for w in plan.work)
    out = np.zeros((2, 5), np.int64)
    s.complete(plan, out, n_emit=np.ones(2, np.int64))
    m = s.metrics
    assert (m.prompt_tokens, m.decode_tokens, m.chunk_steps) == (0, 10, 0)


def test_scheduler_spec_shrinks_k_under_budget_pressure():
    """budget < decodes * (k+1): every decode keeps its committed feed and
    the drafts shrink uniformly — down to plain decode (k=0) when the
    budget only covers the feeds. The fixed step shape never changes;
    only the traced spec_len vector does."""
    clock = FakeClock()
    # 3 decode slots, budget 6: feeds take 3, drafts get 3 → k=1 each
    s = _sched(clock, max_slots=3, token_budget=6)
    sts = [_to_decode(s, f"d{i}") for i in range(3)]
    plan = s.plan()
    assert plan.total_tokens == 6
    assert sorted(plan.num_new[plan.num_new > 0].tolist()) == [2, 2, 2]
    # budget 3 == decode count: graceful degradation to plain decode
    s2 = _sched(clock, max_slots=3, token_budget=3)
    for i in range(3):
        _to_decode(s2, f"p{i}")
    plan2 = s2.plan()
    assert plan2.total_tokens == 3
    assert plan2.spec_len.sum() == 0
    assert sorted(plan2.num_new[plan2.num_new > 0].tolist()) == [1, 1, 1]


def test_scheduler_spec_caps_at_remaining_allowance():
    """Drafts never extend past max_new_tokens - 1 remaining tokens, so
    the device can never emit beyond the allowance (the RNG chain stops
    exactly where spec-off would)."""
    clock = FakeClock()
    s = _sched(clock, max_slots=1, token_budget=16)
    st = _to_decode(s, "tail", new=3)  # 1 emitted, 2 remaining
    plan = s.plan()
    # window may emit at most remaining=2 tokens → at most 1 draft
    assert plan.num_new[st.slot] == 2 and plan.spec_len[st.slot] == 1


def test_scheduler_spec_skips_penalized_requests():
    clock = FakeClock()
    s = _sched(clock, max_slots=2, token_budget=16)
    st_pen = _to_decode(s, "pen", repetition_penalty=1.3)
    st_plain = _to_decode(s, "plain")
    plan = s.plan()
    assert plan.spec_len[st_pen.slot] == 0      # seen-matrix correctness
    assert plan.num_new[st_pen.slot] == 1
    assert plan.spec_len[st_plain.slot] == 4    # unaffected neighbor


def test_scheduler_spec_rejection_rollback_keeps_pages_balanced():
    """Paged + spec on a fake clock: a fully-rejected window (n_emit=1)
    leaves its draft pages slot-owned — no leak, no double free — and
    the rejected targets become the next step's draft fallback; eviction
    afterwards returns every page."""
    clock = FakeClock()
    s = _sched(clock, max_slots=2, token_budget=16, max_tokens=48,
               page_size=4, num_pages=26, pages_per_slot=13,
               prefix_cache=False)
    st = _to_decode(s, "rb", plen=6)
    plan = s.plan()
    k = int(plan.spec_len[st.slot])
    assert k > 0
    s.assert_page_invariants()
    # device says: everything rejected, one (bonus) token emitted
    fake = np.zeros((s.max_slots, 5), np.int64)
    fake[st.slot] = np.asarray([9, 8, 7, 6, 5])
    n_emit = np.zeros(s.max_slots, np.int64)
    n_emit[st.slot] = 1
    s.complete(plan, fake, None, n_emit=n_emit)
    assert st.tokens[-1] == 9 and len(st.tokens) == 2
    assert st.draft_tail == [8, 7, 6, 5][:k]
    s.assert_page_invariants()  # free + live == num_pages still holds
    held = len(st.pages)
    assert held >= 2  # frontier + draft margin pages stay slot-owned
    s._evict(st, clock(), "test eviction")
    s.assert_page_invariants()
    assert s.pool.free_count == s.pool.num_pages  # rollback freed all
    assert st.draft_tail == []  # eviction rewinds draft state too


def test_scheduler_legacy_1d_complete_still_works():
    """Pre-spec callers (and the scheduler unit tests) pass a 1-D token
    vector with no n_emit — one token per sampling slot."""
    clock = FakeClock()
    s = _sched(clock, max_slots=1, token_budget=8, spec_max_draft=0)
    st = s.submit(_req("legacy", plen=4, new=2))
    for _ in range(6):
        plan = s.plan()
        if plan is None:
            break
        s.complete(plan, np.zeros(s.max_slots, np.int64))
    assert st.status is RequestStatus.DONE


# ---------------------------------------------------------------------------
# shared draft + acceptance math units (serving/spec.py)
# ---------------------------------------------------------------------------
def test_ngram_propose_finds_cycle_and_falls_back():
    from deepspeed_tpu.serving.spec import ngram_propose, propose_drafts

    buf = np.asarray([7, 3, 9, 7, 3, 9, 7, 3, 0, 0, 0, 0], np.int32)
    # trailing 3-gram at pos=7 is (9, 7, 3); its earlier occurrence ends
    # at index 4 → continuation 9, 7, 3 ...
    out = np.asarray(ngram_propose(buf, 7, 3, 3))
    np.testing.assert_array_equal(out, [9, 7, 3])
    # no match → the slice past pos (the stale-predictions fallback)
    buf2 = np.asarray([1, 2, 3, 4, 5, 6, 42, 43, 44], np.int32)
    out2 = np.asarray(ngram_propose(buf2, 5, 3, 3))
    np.testing.assert_array_equal(out2, [42, 43, 44])
    # the host wrapper builds the same buffer from request state parts
    out3 = propose_drafts([7, 3, 9, 7], [3, 9, 7, 3], [], 3, 3)
    np.testing.assert_array_equal(out3, [9, 7, 3])
    # draft_tail seeds the fallback when nothing matches
    out4 = propose_drafts([1, 2, 3], [4, 5, 6], [42, 43, 44], 3, 3)
    np.testing.assert_array_equal(out4, [42, 43, 44])


def test_acceptance_math_units():
    from deepspeed_tpu.serving.spec import (clamp_advance_at_eos,
                                            longest_accepted_prefix)

    lap = lambda m: int(longest_accepted_prefix(jnp.asarray(m)))
    assert lap([True, True, False, True]) == 2
    assert lap([False, True, True]) == 0
    assert lap([True, True, True]) == 3
    assert lap(np.zeros((0,), bool)) == 0  # k=0 window (plain decode)
    # batched form agrees
    batched = longest_accepted_prefix(
        jnp.asarray([[True, False], [True, True]])
    )
    np.testing.assert_array_equal(np.asarray(batched), [1, 2])
    # eos clamp: eos at emitted index 1 cuts a 3-advance to 2
    targets = jnp.asarray([5, 9, 7])
    adv, has = clamp_advance_at_eos(targets, 3, 9)
    assert int(adv) == 2 and bool(has)
    # eos beyond the advance does not fire
    adv, has = clamp_advance_at_eos(targets, 2, 7)
    assert int(adv) == 2 and not bool(has)
    # eos_id -1 never matches (token ids are non-negative)
    adv, has = clamp_advance_at_eos(targets, 3, -1)
    assert int(adv) == 3 and not bool(has)


# ---------------------------------------------------------------------------
# metrics / config / lint / streams
# ---------------------------------------------------------------------------
def test_spec_metrics_counts_tokens_not_steps():
    """TPOT and tokens/s divide by tokens actually emitted: a verify
    window emitting 3 tokens books 3 on_token calls, and the acceptance
    counters aggregate per window."""
    clock = FakeClock()
    m = ServingMetrics(clock=clock)
    from deepspeed_tpu.serving.request import RequestState

    st = RequestState(request=_req("m0", new=8), arrival_t=0.0)
    clock.advance(1.0)
    st.first_token_t = clock()
    for _ in range(3):
        st.tokens.append(1)
        m.on_token(st, clock())
    m.on_spec(st, proposed=4, accepted=2, emitted=3)
    clock.advance(2.0)
    for _ in range(3):
        st.tokens.append(1)
        m.on_token(st, clock())
    m.on_spec(st, proposed=4, accepted=2, emitted=3)
    st.finish_t = clock()
    m.on_finish(st, clock())
    assert m.tokens_out == 6
    assert m.acceptance_rate == pytest.approx(0.5)
    assert m.mean_accepted_tokens_per_step == pytest.approx(3.0)
    # TPOT: 2.0s from first token to finish over (6 - 1) tokens
    assert m.tpot_s[-1] == pytest.approx(2.0 / 5)
    snap = m.snapshot()
    assert snap["draft_tokens_accepted"] == 4
    assert snap["mean_accepted_tokens_per_step"] == pytest.approx(3.0)


def test_spec_config_validation():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError

    cfg = DeepSpeedConfig({
        "serving": {"enabled": True, "token_budget": 32,
                    "spec": {"enabled": True, "max_draft": 6}},
    })
    assert cfg.serving.spec.enabled and cfg.serving.spec.max_draft == 6
    with pytest.raises(DeepSpeedConfigError, match="max_draft"):
        DeepSpeedConfig({"serving": {
            "token_budget": 4, "spec": {"enabled": True, "max_draft": 4},
        }})
    with pytest.raises(DeepSpeedConfigError, match="draft"):
        DeepSpeedConfig({"serving": {
            "spec": {"enabled": True, "draft": "model"},
        }})


def test_spec_analytic_stream_and_lint():
    """The verify-window traffic is declared through analytic_streams
    (shardplan/R8 pricing) and the spec-enabled serving step lints clean
    on a tp=2 CPU mesh."""
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.analysis import lint_config

    model = tiny_llama(num_heads=4, num_kv_heads=4)
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(7)
    )
    srv = _serve(eng, spec=True)
    streams = srv.analytic_streams()
    sv = streams["spec_verify"]
    assert sv["kind"] == "hbm" and sv["bytes_per_step"] > 0
    assert sv["max_draft"] == 4 and sv["spec"]
    # spec-off engines declare no spec stream
    assert "spec_verify" not in _serve(eng, spec=False).analytic_streams()

    comm.destroy_process_group()
    report = lint_config(
        {
            "tensor_parallel": {"tp_size": 2},
            "serving": {"enabled": True, "max_slots": 2, "token_budget": 8,
                        "max_tokens": 64, "kv_cache_dtype": "int8",
                        "spec": {"enabled": True, "max_draft": 3}},
        },
        model=model,
        source="serving-spec-unit",
    )
    assert report.ok, report.format()
