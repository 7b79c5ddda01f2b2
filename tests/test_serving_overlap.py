"""The serving host works while the device does (ISSUE 37).

``ServingEngine.step()`` plans and dispatches step n+1 before it fetches
step n; the sampled token and the RNG key of a decode row stay on the
device between the two. The oracle is the same engine forced into the
order it had before (plan, dispatch, fetch, fold: ``serial``, kept here as
one attribute set after construction): every request's tokens and the key
it is left with, the counters and the page pool must come out alike. Plus
the order of calls and fetches, where a token is delivered, what happens
to a row whose request went while its step flew, and the engines that
cannot project and say so.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import deepseek, llama, mellum
from deepspeed_tpu.serving import Request, RequestStatus, ServingEngine

VOCAB = 128


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def eng():
    model = llama("llama-tiny", vocab_size=VOCAB, max_seq_len=64,
                  hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
                  intermediate_size=64)
    return deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(1))


def _prompts(lengths, seed=0, vocab=VOCAB):
    r = np.random.RandomState(seed)
    return [r.randint(0, vocab, size=(n,)).astype(np.int32) for n in lengths]


def _replay(make, order, prompts, news, sampling=None, eos=None, lead=2):
    """``lead`` requests up front, each further one after one more turn;
    then drain. ``order`` "serial" forces the oracle's order."""
    srv = make()
    assert srv.step_order == "overlapped" and srv.step_order_reason is None
    srv.step_order = order
    states = []
    for i, (p, new) in enumerate(zip(prompts, news)):
        if i >= lead:
            srv.step()
        states.append(srv.submit(Request(
            request_id=f"r{i}", prompt=p, max_new_tokens=new,
            eos_token_id=-1 if eos is None else eos[i],
            rng=jax.random.PRNGKey(100 + i),
            **(sampling[i] if sampling else {}))))
    srv.run_until_idle()
    return srv, states


def _same(got, want, discarded=0):
    """An overlapped replay against its oracle: requests, counters, pool."""
    (srv, states), (oracle, wanted) = got, want
    for st, ws in zip(states, wanted):
        rid = st.request.request_id
        assert st.status is ws.status is RequestStatus.DONE, rid
        assert st.tokens == ws.tokens, rid
        np.testing.assert_array_equal(
            np.asarray(st.rng), np.asarray(ws.rng), err_msg=rid)
    a, b = srv.metrics.snapshot(), oracle.metrics.snapshot()
    for k in ("scheduled_tokens", "tokens_out", "finished", "evicted"):
        assert a[k] == b[k], k
    assert a["discarded_rows"] == discarded and b["discarded_rows"] == 0
    # every step but the first after idle went out over one in flight
    assert 0 < a["overlapped_steps"] < a["steps"]
    assert b["overlapped_steps"] == 0
    assert srv.step_traces == oracle.step_traces == 1
    for s in (srv, oracle):  # nothing in flight, held or slotted is left
        assert s._flying is None and not s.scheduler.has_work
        assert not s.scheduler._held and not s.scheduler._in_flight
        assert s.scheduler.active_count == 0
        if s.paged:
            s.scheduler.assert_page_invariants()
    if srv.paged:  # what is not free is what the prefix cache keeps
        assert (srv.scheduler.pool.free_count
                == oracle.scheduler.pool.free_count)
        if srv.scheduler.prefix_cache is None:
            assert srv.scheduler.pool.free_count == srv.num_pages


SAMPLED = [
    dict(temperature=0.8, top_k=10),
    dict(temperature=0.7, top_p=0.85),
    dict(temperature=0.9, top_k=20, top_p=0.9, repetition_penalty=1.3),
    dict(temperature=0.6),
    {},  # a greedy row rides in the same batch
]
SLOTS = dict(max_slots=3, token_budget=8, max_tokens=64)
PAGED = dict(SLOTS, paged=True, page_size=4)
REPLAYS = {
    "greedy": dict(serving=SLOTS, lengths=[3, 12, 7, 5, 9],
                   news=[6, 4, 8, 5, 3]),
    "sampled": dict(serving=SLOTS, lengths=[6, 9, 4, 11, 5],
                    news=[8, 8, 8, 8, 8], sampling=SAMPLED),
    "sampled-paged": dict(serving=PAGED, lengths=[6, 9, 4, 11, 5],
                          news=[8, 8, 8, 8, 8], sampling=SAMPLED),
    # a prompt of several chunks: the final chunk's slot is planned as a
    # decode row while the chunk is still in flight
    "final-chunk-into-decode": dict(
        serving=PAGED, lengths=[30, 17, 41], news=[5, 6, 4]),
    # ended by max_new_tokens at the first sample, and at the second: such
    # a slot is not planned again, so no row is computed for nobody
    "one-token-answers": dict(serving=PAGED, lengths=[9, 13, 5, 20],
                              news=[1, 2, 1, 3], sampling=SAMPLED[:4]),
    # more decode slots than the budget feeds: the round-robin skips a
    # slot, whose next row is fed from the host and not from the flight
    "budget-below-decodes": dict(
        serving=dict(max_slots=4, token_budget=2, max_tokens=64),
        lengths=[2, 2, 2, 2], news=[6, 6, 6, 6], sampling=SAMPLED[:4],
        lead=4),
}


@pytest.mark.parametrize("name", list(REPLAYS))
def test_overlapped_replay_is_the_serial_oracles(eng, name):
    case = dict(REPLAYS[name])
    serving, lengths = case.pop("serving"), case.pop("lengths")

    def make():
        return ServingEngine(engine=eng, serving=dict(serving))

    prompts = _prompts(lengths)
    _same(_replay(make, "overlapped", prompts, **case),
          _replay(make, "serial", prompts, **case))


@pytest.mark.parametrize("serving", [SLOTS, PAGED], ids=["slots", "paged"])
def test_eos_in_mid_flight_discards_the_row_computed_for_nobody(eng, serving):
    """A request whose eos is sampled by step n has a decode row in step
    n+1, planned before anyone could know: that row's token, key advance
    and count reach nobody, and its slot and pages are held until step
    n+1 is folded."""
    def make():
        return ServingEngine(engine=eng, serving=dict(serving))

    prompts, news = _prompts([6, 9, 4, 11]), [10, 10, 10, 10]
    sampling = [{}, SAMPLED[0], {}, SAMPLED[1]]
    _, free = _replay(make, "serial", prompts, news, sampling)
    # r0 and r3 end by an eos they really sample, r0 at its first token
    eos = [free[0].tokens[0], -1, -1, free[3].tokens[4]]
    cut = [1, 10, 10, free[3].tokens.index(eos[3]) + 1]
    got = _replay(make, "overlapped", prompts, news, sampling, eos)
    want = _replay(make, "serial", prompts, news, sampling, eos)
    assert [len(st.tokens) for st in want[1]] == cut
    _same(got, want, discarded=2)


def test_a_held_slot_stays_out_of_reach_until_its_step_is_folded(eng):
    """The fold that finds the eos: the request is DONE and returned, but
    the step in flight still names its slot and its pages. Neither is
    free, published or handed on before that step is folded."""
    srv = ServingEngine(engine=eng, serving=dict(
        PAGED, max_slots=1, prefix_cache=True))
    p = _prompts([9])[0]
    probe = srv.submit(Request(request_id="probe", prompt=p,
                               max_new_tokens=4))
    srv.run_until_idle()
    srv.scheduler.prefix_cache.clear()
    free = srv.scheduler.pool.free_count
    st = srv.submit(Request(request_id="a", prompt=p, max_new_tokens=4,
                            eos_token_id=probe.tokens[1]))
    waiter = srv.submit(Request(request_id="b", prompt=p[:5],
                                max_new_tokens=2))
    done = []
    while not done:
        done = srv.step()
    assert done == [st] and st.status is RequestStatus.DONE
    assert st.tokens == probe.tokens[:2]
    sched = srv.scheduler
    # the fold of the eos: a husk holds slot 0 and the pages
    assert list(sched._held) == [0] and sched.slots[0] is not st
    assert sched.slots[0].pages and not st.pages and not sched._free
    assert sched.pool.free_count < free and len(sched.prefix_cache) == 0
    assert waiter.status is RequestStatus.QUEUED and sched.has_work
    sched.assert_page_invariants()  # the husk's pages count as held
    assert srv.step() == []  # folds the row computed for nobody
    assert srv.metrics.discarded_rows == 1 and not sched._held
    assert len(sched.prefix_cache) > 0  # published now, and not before
    srv.run_until_idle()
    assert waiter.status is RequestStatus.DONE and len(waiter.tokens) == 2
    assert srv.metrics.discarded_rows == 1


def test_shared_prefix_and_copy_on_write(eng):
    """A finished request's pages serve the next prompts' prefix; the one
    that diverges in mid-page copies on write, under a step in flight as
    in turn."""
    base = _prompts([22], seed=3)[0]
    tails = _prompts([5, 9], seed=4)
    prompts = [base, np.concatenate([base[:18], tails[0]]),
               np.concatenate([base[:21], tails[1]]), base.copy()]

    def run(order):
        srv = ServingEngine(engine=eng, serving=dict(
            PAGED, prefix_cache=True))
        srv.step_order = order
        states = []
        for i, p in enumerate(prompts):
            states.append(srv.submit(Request(
                request_id=f"r{i}", prompt=p, max_new_tokens=5,
                **SAMPLED[i])))
            if i == 0:
                srv.run_until_idle()  # its pages are in the cache
        srv.run_until_idle()
        return srv, states

    got, want = run("overlapped"), run("serial")
    _same(got, want)
    snap = got[0].metrics.snapshot()
    assert snap["cow_copies"] == want[0].metrics.cow_copies > 0
    # (the penalised request feeds its whole prompt: its seen row)
    assert snap["prefix_hits"] == want[0].metrics.prefix_hits == 2


@pytest.fixture(scope="module", params=["mellum", "deepseek"])
def routed(request):
    """Tiny Mellum (window and full layers: two page pools) and tiny
    DeepSeek (a latent pool and the indexer's keys), routed experts in
    both."""
    if request.param == "mellum":
        model = mellum("mellum-tiny", initializer_range=0.2)
        serving = dict(max_slots=3, token_budget=16, max_tokens=256,
                       paged=True, page_size=4)
    else:
        model = deepseek("deepseek-tiny")
        serving = dict(max_slots=4, token_budget=16, max_tokens=384,
                       paged=True, page_size=16, prefix_cache=False)
    params = model.init(jax.random.PRNGKey(7), dtype=jnp.float32)
    return model, params, serving


def test_two_pools_and_latent_pools_replay_the_oracle(routed):
    model, params, serving = routed

    def make():
        return deepspeed_tpu.init_serving(
            model, serving=dict(serving), params=params, dtype=jnp.float32)

    prompts = _prompts([70, 20, 45, 33], seed=5, vocab=512)
    news = [6, 9, 1, 7]
    sampling = [{}, SAMPLED[0], {}, SAMPLED[3]]
    got = _replay(make, "overlapped", prompts, news, sampling)
    want = _replay(make, "serial", prompts, news, sampling)
    _same(got, want)
    srv = got[0]
    assert srv.kinds_paged or "latent rows" in srv.cache.rows
    assert srv.metrics.moe_steps == srv.metrics.steps
    if srv.kinds_paged:
        pool = srv.scheduler.window_pool
        assert pool.free_count == pool.num_pages


# ---------------------------------------------------------------------------
# the order of a turn, and where a token is delivered
# ---------------------------------------------------------------------------
def test_the_next_step_is_called_before_the_last_is_fetched(eng, monkeypatch):
    srv = ServingEngine(engine=eng, serving=dict(SLOTS))
    log = []
    step, get = srv._step_exec, jax.device_get

    def called(*a):
        log.append("call")
        return step(*a)

    def fetched(x):
        log.append("fetch")
        return get(x)

    srv._step_exec = called
    monkeypatch.setattr(jax, "device_get", fetched)
    states = [srv.submit(Request(request_id=f"r{i}", prompt=p,
                                 max_new_tokens=4))
              for i, p in enumerate(_prompts([5, 11]))]
    assert srv.step() == [] and log == ["call"]  # first after idle
    assert all(not st.tokens for st in states)
    srv.run_until_idle()
    n = srv.metrics.steps
    # call 1, then (call n+1, fetch n) in every turn, then the last fetch
    assert log == ["call"] + ["call", "fetch"] * (n - 1) + ["fetch"]
    assert srv.metrics.overlapped_steps == n - 1
    # the oracle's order
    log.clear()
    srv.step_order = "serial"
    srv.submit(Request(request_id="s", prompt=_prompts([5])[0],
                       max_new_tokens=3))
    srv.run_until_idle()
    assert log == ["call", "fetch"] * 3


def test_a_token_reaches_the_request_in_the_fold_and_nowhere_else(eng):
    """What the load generator stamps (``len(tokens)``, the status, and
    ``first_token_t``) changes only inside the fold, when the value is on
    the host; nothing is appended for a step that is merely in flight."""
    clock = FakeClock()
    srv = ServingEngine(engine=eng, serving=dict(PAGED), clock=clock)
    states = []
    folding = []

    def seen():
        return [(len(st.tokens), st.first_token_t, st.finish_t,
                 st.status in (RequestStatus.DECODE, RequestStatus.DONE),
                 st.status is RequestStatus.DONE,
                 srv.metrics.tokens_out) for st in states]

    fold = srv._fold

    def watched(fl):
        assert seen() == folding[-1] if folding else True
        clock.t += 1.0  # a fold's time is its own
        out = fold(fl)
        folding.append(seen())
        return out

    srv._fold = watched
    for i, p in enumerate(_prompts([13, 4, 9])):
        states.append(srv.submit(Request(
            request_id=f"r{i}", prompt=p, max_new_tokens=3 + i,
            eos_token_id=-1)))
    folding.append(seen())
    turns = 0
    while srv.scheduler.has_work:
        srv.step()
        turns += 1
        assert seen() == folding[-1]  # as the last fold left it
    assert turns == srv.metrics.steps + 1  # one call more than steps
    for st in states:
        assert st.status is RequestStatus.DONE
        # stamped with the time of the fold that delivered them
        assert st.first_token_t == float(int(st.first_token_t)) >= 1.0
        assert st.finish_t - st.first_token_t == len(st.tokens) - 1


def test_step_returns_what_the_folded_step_finished(eng):
    srv = ServingEngine(engine=eng, serving=dict(SLOTS))
    assert srv.step() == [] and srv._flying is None  # idle: nothing begun
    st = srv.submit(Request(request_id="a", prompt=_prompts([4])[0],
                            max_new_tokens=1))
    assert srv.step() == [] and srv._flying is not None
    assert st.status is RequestStatus.PREFILL and srv.scheduler.has_work
    # nothing to plan (its one token is in flight): the call folds it
    assert srv.step() == [st] and srv._flying is None
    assert st.status is RequestStatus.DONE and len(st.tokens) == 1
    assert not srv.scheduler.has_work and srv.step() == []
    assert srv.metrics.steps == 1 and srv.metrics.overlapped_steps == 0


# ---------------------------------------------------------------------------
# a request that goes while a step that names it is in flight
# ---------------------------------------------------------------------------
def test_an_eviction_under_a_step_in_flight_drops_that_steps_row(eng):
    srv = ServingEngine(engine=eng, serving=dict(PAGED, prefix_cache=False))
    prompts = _prompts([10, 7])
    keep, gone = [srv.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=8,
        rng=jax.random.PRNGKey(100 + i), **SAMPLED[i]))
        for i, p in enumerate(prompts)]
    while len(gone.tokens) < 2:
        srv.step()
    sched = srv.scheduler
    slot = gone.slot
    assert any(w.state is gone for w in srv._flying.plan.work)
    sched._evict(gone, srv.clock(), "operator")
    # rewound at once; slot and pages held by a husk until the fold
    assert gone.status is RequestStatus.EVICTED and gone.tokens == []
    assert gone.slot is None and not gone.pages
    assert slot in sched._held and slot not in sched._free
    sched.assert_page_invariants()
    before = srv.metrics.scheduled_tokens
    srv.step()  # plans without it, folds the step that named it
    assert srv.metrics.discarded_rows == 1 and gone.tokens == []
    assert srv.metrics.scheduled_tokens - before == 1  # keep's row alone
    assert slot in sched._free and not sched._held
    # its retry takes another turn at the same chain: the same tokens
    again = sched.resubmit(gone)
    srv.run_until_idle()
    oracle = ServingEngine(engine=eng, serving=dict(PAGED))
    oracle.step_order = "serial"
    want = [oracle.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=8,
        rng=jax.random.PRNGKey(100 + i), **SAMPLED[i]))
        for i, p in enumerate(prompts)]
    oracle.run_until_idle()
    assert again is gone and gone.status is RequestStatus.DONE
    assert [keep.tokens, gone.tokens] == [w.tokens for w in want]
    assert sched.pool.free_count == srv.num_pages


def test_a_queue_timeout_under_a_step_in_flight(eng):
    clock = FakeClock()

    def run(order):
        clock.t = 0.0
        srv = ServingEngine(engine=eng, clock=clock, serving=dict(
            SLOTS, max_slots=1, request_timeout_s=5.0))
        srv.step_order = order
        first, late = [srv.submit(Request(
            request_id=f"r{i}", prompt=p, max_new_tokens=6))
            for i, p in enumerate(_prompts([9, 6]))]
        srv.step()
        srv.step()
        clock.t = 6.0  # the waiter is past its timeout, a step in flight
        srv.run_until_idle()
        return srv, first, late

    (srv, first, late), (_, want, _) = run("overlapped"), run("serial")
    assert late.status is RequestStatus.EVICTED
    assert late.evict_reason == "queue timeout" and not late.tokens
    assert first.status is RequestStatus.DONE and first.tokens == want.tokens
    assert srv.metrics.discarded_rows == 0 and srv._flying is None


def test_starvation_with_a_step_in_flight_evicts_as_the_oracle_does(eng):
    """The pool at its liveness floor. Nobody is evicted on a projection:
    the empty plan folds the step in flight first, and the next plan judges
    starvation on what is really there, as the oracle does."""
    def run(order):
        srv = ServingEngine(engine=eng, serving=dict(
            SLOTS, paged=True, page_size=16, num_pages=5,
            prefix_cache=False))
        srv.step_order = order
        # two answers of four pages each over a pool of five: both stall
        # in decode on their next page, and the newer one has to go
        states = [srv.submit(Request(request_id=f"x{i}", prompt=p,
                                     max_new_tokens=30))
                  for i, p in enumerate(_prompts([30, 30], seed=7))]
        srv.run_until_idle()
        return srv, states

    (srv, got), (oracle, want) = run("overlapped"), run("serial")
    assert [s.status for s in got] == [s.status for s in want]
    assert [s.tokens for s in got] == [s.tokens for s in want]
    assert {s.status for s in got} == {RequestStatus.DONE,
                                       RequestStatus.EVICTED}
    assert all(s.evict_reason == "page pool exhausted" for s in got
               if s.status is RequestStatus.EVICTED)
    assert srv.metrics.evicted == oracle.metrics.evicted > 0
    assert srv.metrics.discarded_rows == 0
    assert srv.scheduler.pool.free_count == srv.num_pages
    assert srv._flying is None and not srv.scheduler._held


# ---------------------------------------------------------------------------
# engines that cannot project say so, and serve as before
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name, extra, why", [
    ("speculative", dict(spec={"enabled": True, "max_draft": 4}), "n_emit"),
    ("tiered", dict(paged=True, page_size=4, num_pages=24, host_pages=32),
     "staging buffer"),
])
def test_an_engine_that_cannot_project_runs_in_the_serial_order(
        eng, name, extra, why):
    srv = ServingEngine(engine=eng, serving=dict(SLOTS, **extra))
    assert srv.step_order == "serial" and why in srv.step_order_reason
    r = np.random.RandomState(0)
    prompts = [np.resize(r.randint(0, VOCAB, size=(3,)), n)
               for n in (8, 6, 9)]
    states = [srv.submit(Request(request_id=f"r{i}", prompt=p,
                                 max_new_tokens=10, temperature=0.0))
              for i, p in enumerate(prompts)]
    # in this order a step's tokens are there when its call returns
    srv.step()
    assert srv._flying is None and srv.metrics.steps == 1
    srv.run_until_idle()
    assert srv.metrics.overlapped_steps == 0 and srv.step_traces == 1
    assert srv.metrics.discarded_rows == 0
    for st, p in zip(states, prompts):
        want = eng.generate(p[None, :], max_new_tokens=10, temperature=0.0)
        np.testing.assert_array_equal(st.output(), want[0])
    with pytest.raises(ValueError, match="only at the fold"):
        srv.scheduler.plan(ahead_of=object())
