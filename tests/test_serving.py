"""Continuous-batching serving runtime (ISSUE 5).

The oracle: with identical params and per-request RNG, the slot engine
must reproduce single-request ``InferenceEngine.generate`` outputs for
staggered arrivals — greedy bitwise, sampled with shared keys, including
tp>1 and int8 KV cache configs. Plus scheduler invariants under a fake
clock (admission rejection, timeout eviction with backoff, slot
recycling), the per-slot decode-attention kernel, and the recompile
counters (zero serving recompiles after warmup; one lockstep compile per
128-bucket).
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


# ---------------------------------------------------------------------------
# token-parity oracle: slot engine == N independent single-request runs
# ---------------------------------------------------------------------------
def test_greedy_parity_staggered_arrivals():
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(1)
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 3, "token_budget": 8, "max_tokens": 64,
    })
    r = np.random.RandomState(0)
    specs = [(3, 6), (12, 4), (7, 8), (5, 5), (9, 3)]
    prompts = [r.randint(0, 128, size=(n,)) for n, _ in specs]
    states = []
    # staggered: two up front, the rest arrive while the batch is running
    states.append(_submit(srv, "r0", prompts[0], max_new_tokens=specs[0][1]))
    states.append(_submit(srv, "r1", prompts[1], max_new_tokens=specs[1][1]))
    srv.step()
    srv.step()
    states.append(_submit(srv, "r2", prompts[2], max_new_tokens=specs[2][1]))
    srv.step()
    states.append(_submit(srv, "r3", prompts[3], max_new_tokens=specs[3][1]))
    states.append(_submit(srv, "r4", prompts[4], max_new_tokens=specs[4][1]))
    srv.run_until_idle()
    for st, p, (_, new) in zip(states, prompts, specs):
        assert st.status is RequestStatus.DONE
        want = eng.generate(p[None, :], max_new_tokens=new, temperature=0.0)
        np.testing.assert_array_equal(st.output(), want[0],
                                      err_msg=st.request.request_id)
    # zero recompiles after warmup: one trace for the whole ragged trace
    assert srv.step_traces == 1


def test_sampled_parity_shared_keys():
    """Sampled decoding with per-request keys: the slot engine's traced
    where-gates reproduce the lockstep sampler bitwise — same key, same
    tokens — across temperature/top-k/top-p/penalty mixes IN ONE BATCH."""
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(2)
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 3, "token_budget": 8, "max_tokens": 64,
    })
    r = np.random.RandomState(1)
    cases = [
        dict(temperature=0.8, top_k=10, top_p=1.0),
        dict(temperature=0.7, top_k=0, top_p=0.85),
        dict(temperature=0.9, top_k=20, top_p=0.9, repetition_penalty=1.3),
    ]
    prompts = [r.randint(0, 128, size=(n,)) for n in (6, 9, 4)]
    keys = [jax.random.PRNGKey(100 + i) for i in range(len(cases))]
    states = [
        _submit(srv, f"s{i}", p, max_new_tokens=8, rng=keys[i], **cases[i])
        for i, p in enumerate(prompts)
    ]
    srv.run_until_idle()
    for i, (st, p) in enumerate(zip(states, prompts)):
        want = eng.generate(p[None, :], max_new_tokens=8, rng=keys[i],
                            **cases[i])
        np.testing.assert_array_equal(st.output(), want[0], err_msg=f"s{i}")


def test_eos_parity_and_padding():
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(3)
    )
    prompt = np.random.RandomState(2).randint(0, 128, size=(4,))
    ref = eng.generate(prompt[None, :], max_new_tokens=8, temperature=0.0)
    eos = int(ref[0, 6])  # force eos mid-generation
    want = eng.generate(prompt[None, :], max_new_tokens=8, temperature=0.0,
                        eos_token_id=eos)
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 64,
    })
    st = _submit(srv, "e0", prompt, max_new_tokens=8, eos_token_id=eos)
    srv.run_until_idle()
    assert st.status is RequestStatus.DONE
    np.testing.assert_array_equal(st.output(), want[0])


def test_tp_and_int8_kv_parity():
    """tp>1 + int8 KV arena: the sharded slot step (cache heads over tp,
    per-slot frontier vector through the shard-mapped decode kernel path)
    matches the tp-sharded single-request engine token-for-token."""
    model = tiny_llama(num_heads=4, num_kv_heads=4)
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=jax.devices()[:2])
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, topology=topo,
        kv_cache_dtype="int8", rng=jax.random.PRNGKey(4),
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 64,
    })
    r = np.random.RandomState(3)
    prompts = [r.randint(0, 128, size=(n,)) for n in (5, 11)]
    states = [
        _submit(srv, f"q{i}", p, max_new_tokens=6)
        for i, p in enumerate(prompts)
    ]
    srv.run_until_idle()
    for i, (st, p) in enumerate(zip(states, prompts)):
        want = eng.generate(p[None, :], max_new_tokens=6, temperature=0.0)
        np.testing.assert_array_equal(st.output(), want[0], err_msg=f"q{i}")
    assert srv.step_traces == 1


def test_chunked_prefill_respects_token_budget():
    """Dynamic SplitFuse: a prompt longer than the budget prefills across
    steps (chunked), decodes interleave, and no step schedules more than
    token_budget real tokens."""
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(5)
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 4, "max_tokens": 64,
    })
    r = np.random.RandomState(4)
    long_p = r.randint(0, 128, size=(11,))   # 3 chunks at budget 4
    short_p = r.randint(0, 128, size=(3,))
    st_long = _submit(srv, "long", long_p, max_new_tokens=4)
    st_short = _submit(srv, "short", short_p, max_new_tokens=6)
    per_step = []
    while srv.scheduler.has_work:
        before = srv.metrics.scheduled_tokens
        srv.step()
        per_step.append(srv.metrics.scheduled_tokens - before)
    assert max(per_step) <= 4
    assert st_long.status is RequestStatus.DONE
    assert st_short.status is RequestStatus.DONE
    for st, p, new in ((st_long, long_p, 4), (st_short, short_p, 6)):
        want = eng.generate(p[None, :], max_new_tokens=new, temperature=0.0)
        np.testing.assert_array_equal(st.output(), want[0])


# ---------------------------------------------------------------------------
# recompile counters
# ---------------------------------------------------------------------------
def test_lockstep_compile_cache_buckets_lengths():
    """Satellite: _build_decode programs are keyed on 128-bucketed
    (B, prompt, total) — a ragged length sweep compiles ONCE per bucket,
    observable via the new num_compiles counter."""
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(6)
    )
    r = np.random.RandomState(5)
    outs = {}
    for plen, new in [(4, 6), (7, 3), (11, 8), (5, 12), (9, 2)]:
        p = r.randint(0, 128, size=(1, plen))
        outs[(plen, new)] = eng.generate(p, max_new_tokens=new,
                                         temperature=0.0)
    assert eng.num_compiles == 1, eng.num_compiles  # one (1,128,128) bucket
    # greedy outputs still match the no-cache oracle for one of the legs
    p = r.randint(0, 128, size=(1, 6))
    out = eng.generate(p, max_new_tokens=5, temperature=0.0)
    ids = jnp.asarray(p)
    for _ in range(5):
        logits, _ = model.apply(eng.params, ids, dtype=jnp.float32)
        ids = jnp.concatenate(
            [ids, jnp.argmax(logits[:, -1], axis=-1)[:, None]], axis=1
        )
    np.testing.assert_array_equal(out, np.asarray(ids))
    assert eng.num_compiles == 1  # same bucket again


def test_spec_decode_compile_cache_buckets_lengths():
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, draft_model="ngram",
        rng=jax.random.PRNGKey(7),
    )
    plain = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, params=eng.params
    )
    r = np.random.RandomState(6)
    for plen, new in [(4, 8), (9, 5), (6, 10)]:
        p = r.randint(0, 128, size=(1, plen))
        got = eng.generate(p, max_new_tokens=new, num_draft_tokens=3)
        want = plain.generate(p, max_new_tokens=new, temperature=0.0)
        np.testing.assert_array_equal(got, want)
    assert eng.num_compiles == 1, eng.num_compiles


# ---------------------------------------------------------------------------
# scheduler invariants (fake clock, no device work)
# ---------------------------------------------------------------------------
def _sched(clock, **kw):
    d = dict(max_slots=2, token_budget=8, queue_limit=2,
             request_timeout_s=10.0, eviction_backoff_s=1.0, max_tokens=64,
             clock=clock, metrics=ServingMetrics(clock=clock))
    d.update(kw)
    return Scheduler(**d)


def _req(rid, plen=4, new=4, **kw):
    return Request(request_id=rid, prompt=np.arange(plen) % 7,
                   max_new_tokens=new, **kw)


def test_scheduler_admission_rejection_bounded_queue():
    clock = FakeClock()
    s = _sched(clock, max_slots=1, queue_limit=2)
    st0 = s.submit(_req("a"))
    assert s.plan() is not None        # admits "a" to the only slot
    st1 = s.submit(_req("b"))          # queue 1
    st2 = s.submit(_req("c"))          # queue 2 (the limit)
    st3 = s.submit(_req("d"))          # over the bound → graceful reject
    assert st0.status is RequestStatus.PREFILL
    assert st1.status is RequestStatus.QUEUED
    assert st2.status is RequestStatus.QUEUED
    assert st3.status is RequestStatus.EVICTED
    assert st3.evict_reason == "queue full"
    assert st3.retry_after == clock() + 1.0  # backoff hint, attempt 1
    assert s.metrics.rejected == 1


def test_scheduler_rejects_over_capacity_request():
    clock = FakeClock()
    s = _sched(clock, max_tokens=16)
    st = s.submit(_req("big", plen=14, new=8))  # 22 > 16
    assert st.status is RequestStatus.EVICTED
    assert "max_tokens" in st.evict_reason


def test_scheduler_timeout_eviction_with_backoff():
    clock = FakeClock()
    s = _sched(clock, max_slots=1, queue_limit=4, request_timeout_s=10.0)
    s.submit(_req("hog", new=30))
    assert s.plan() is not None        # hog takes the only slot
    st = s.submit(_req("waiter"))
    clock.advance(11.0)                # past request_timeout_s
    evicted = s.evict_timeouts()
    assert evicted == [st]
    assert st.status is RequestStatus.EVICTED
    assert st.evict_reason == "queue timeout"
    assert st.retry_after == pytest.approx(clock() + 1.0)
    # resubmission doubles the backoff (exponential)
    st2 = s.resubmit(st)
    assert st2 is st and st.status is RequestStatus.QUEUED
    assert st.attempts == 2
    clock.advance(11.0)
    s.evict_timeouts()
    assert st.status is RequestStatus.EVICTED
    assert st.retry_after == pytest.approx(clock() + 2.0)


def test_scheduler_slot_recycling():
    clock = FakeClock()
    s = _sched(clock, max_slots=1, queue_limit=4)
    st0 = s.submit(_req("first", plen=4, new=2))
    st1 = s.submit(_req("second", plen=3, new=2))
    slots_seen = []
    for _ in range(20):
        plan = s.plan()
        if plan is None:
            break
        clock.advance(0.01)
        for w in plan.work:
            slots_seen.append((w.state.request.request_id, w.slot))
        s.complete(plan, np.zeros(s.max_slots, np.int64))
    assert st0.status is RequestStatus.DONE
    assert st1.status is RequestStatus.DONE
    # both requests used the SAME recycled slot, one after the other
    assert {slot for _, slot in slots_seen} == {0}
    assert s.slots == [None] and len(s._free) == 1
    # the recycled slot arrives fresh both times (seen-row reset flag)
    first_steps = [r for r, _ in slots_seen]
    assert first_steps.index("second") > first_steps.index("first")


def test_scheduler_decode_round_robin_under_tight_budget():
    """token_budget < concurrent decodes: the rotating decode start must
    round-robin the budget so no slot starves (every request's token
    count keeps growing across a window of steps)."""
    clock = FakeClock()
    s = _sched(clock, max_slots=3, token_budget=1, queue_limit=8,
               max_tokens=64)
    # three slots mid-DECODE (fast-forward the lifecycle: prompt cached,
    # first token sampled) — the pure decode-contention scenario
    states = [s.submit(_req(f"d{i}", plen=2, new=20)) for i in range(3)]
    for st in states:
        assert st.status is RequestStatus.PREFILL  # eager admission
        st.prompt_pos = st.prompt_len
        st.transition(RequestStatus.DECODE)
        st.tokens.append(0)
    for _ in range(9):  # 3 full rotations of budget 1 over 3 decode slots
        plan = s.plan()
        assert plan is not None and plan.total_tokens == 1
        clock.advance(0.01)
        s.complete(plan, np.zeros(s.max_slots, np.int64))
    gains = [len(st.tokens) - 1 for st in states]
    assert gains == [3, 3, 3], gains  # perfectly fair, nobody starved


def _drive(s, overlapped):
    """Plan and fold until idle, every plan's ``held()`` in order; the
    overlapped order plans step n+1 ``ahead_of`` step n in flight."""
    held, flying = [], None
    for _ in range(20):
        plan = s.plan(ahead_of=flying if overlapped else None)
        if plan is not None:
            held.append(plan.held())
            assert plan.prompt_rows + sum(
                w.n_tokens for w in plan.work if not w.chunk
            ) == plan.total_tokens == held[-1]["scheduled_tokens"]
        due, flying = (flying, plan) if overlapped else (plan, None)
        if due is not None:
            s.complete(due, np.ones(s.max_slots, np.int64))
        if plan is None and flying is None:
            return held
    raise AssertionError("the scheduler never ran dry")


@pytest.mark.parametrize("overlapped", [False, True],
                         ids=["serial", "ahead_of"])
def test_a_plan_says_what_it_holds(overlapped):
    """ISSUE 54, by hand: a prompt of 11 and one of 5 under a budget of 8,
    answers of 3 and 2. A plan made ``ahead_of`` a step in flight counts
    the rows it dispatches: the same steps as the serial order's."""
    s = _sched(FakeClock())
    s.submit(_req("a", plen=11, new=3))
    s.submit(_req("b", plen=5, new=2))
    rows = ("scheduled_tokens", "prompt_rows", "prompt_slots",
            "decode_slots", "context_tokens")
    assert [tuple(h[k] for k in rows) for h in _drive(s, overlapped)] == [
        (8, 8, 1, 0, 8),         # a's first chunk takes the budget
        (8, 8, 2, 0, 11 + 5),    # a's last 3 rows and the whole of b
        (2, 0, 0, 2, 12 + 6),    # both decode: the prompt and one token
        (1, 0, 0, 1, 13),        # b is done (2 tokens); a's third
    ]
    m = s.metrics
    assert (m.prompt_tokens, m.decode_tokens, m.chunk_steps) == (16, 3, 2)
    assert m.scheduled_tokens == 19 and m.discarded_rows == 0
    snap = m.snapshot()
    assert (snap["prompt_tokens"], snap["decode_tokens"],
            snap["chunk_steps"]) == (16, 3, 2)
    assert "2 with a prompt chunk" in m.summary()
    assert "16 prompt + 3 decode" in m.summary()


def test_a_discarded_row_is_in_neither_counter():
    """The eos comes out of step 1 while step 2, planned ahead of it, holds
    the request's decode row: the plan counted the row it dispatched, the
    fold books it nowhere but ``discarded_rows``."""
    s = _sched(FakeClock())
    st = s.submit(_req("a", plen=4, new=5, eos_token_id=1))
    first = s.plan()
    second = s.plan(ahead_of=first)
    assert second.held() == dict(scheduled_tokens=1, prompt_rows=0,
                                 prompt_slots=0, decode_slots=1,
                                 context_tokens=5)
    s.complete(first, np.ones(s.max_slots, np.int64))
    assert st.status is RequestStatus.DONE
    s.complete(second, np.ones(s.max_slots, np.int64))
    m = s.metrics
    assert (m.scheduled_tokens, m.prompt_tokens, m.decode_tokens,
            m.chunk_steps, m.discarded_rows) == (4, 4, 0, 1, 1)


def test_fleet_metrics_sum_the_split_counters():
    from deepspeed_tpu.serving.metrics import FleetMetrics

    replicas = [ServingMetrics(), ServingMetrics()]
    replicas[0].on_rows(8, prompt_tokens=6, chunk_step=True)
    replicas[1].on_rows(3)
    replicas[1].on_rows(5, discarded=1, prompt_tokens=5, chunk_step=True)
    snap = FleetMetrics(replicas).snapshot()
    assert (snap["scheduled_tokens"], snap["prompt_tokens"],
            snap["decode_tokens"], snap["chunk_steps"]) == (16, 11, 5, 2)


def test_overlap_budget_hbm_stream_window_excludes_hbm_roofline():
    """R8 for kind='hbm': an overlapped HBM stream shares the link that
    produces the HBM roofline term, so it may only hide under the MXU
    window — a stream that fits hbm_s but not compute_s must be flagged."""
    from deepspeed_tpu.analysis import lint_jaxpr

    def tiny(x):
        return (x * 2.0).sum()

    closed = jax.make_jaxpr(tiny)(jnp.zeros((8, 8), jnp.float32))
    # a tiny program's MXU window is ~0: any real HBM stream is exposed
    streams = {
        "kv": {"kind": "hbm", "bytes_per_step": 64 * (1 << 30),
               "overlapped": True},
    }
    findings = lint_jaxpr(closed, streams=streams, source="hbm-r8")
    assert any(f.rule == "R8" for f in findings), [f.format() for f in findings]
    # the serving engine's actual declaration (overlapped: False) is silent
    streams["kv"]["overlapped"] = False
    assert lint_jaxpr(closed, streams=streams, source="hbm-r8-off") == []


def test_request_lifecycle_rejects_illegal_transition():
    from deepspeed_tpu.serving.request import RequestState

    st = RequestState(request=_req("x"))
    with pytest.raises(ValueError, match="illegal transition"):
        st.transition(RequestStatus.DECODE)  # QUEUED -> DECODE skips PREFILL
    st.transition(RequestStatus.PREFILL)
    st.transition(RequestStatus.DECODE)
    st.transition(RequestStatus.DONE)
    with pytest.raises(ValueError, match="illegal transition"):
        st.transition(RequestStatus.QUEUED)


def test_request_rng_deterministic():
    from deepspeed_tpu.serving.request import request_rng

    k1 = np.asarray(request_rng("req-1"))
    k1b = np.asarray(request_rng("req-1"))
    k2 = np.asarray(request_rng("req-2"))
    np.testing.assert_array_equal(k1, k1b)
    assert (k1 != k2).any()


# ---------------------------------------------------------------------------
# per-slot kernel + sampling-hazard units
# ---------------------------------------------------------------------------
def test_decode_attention_kernel_per_slot_cache_len():
    """The kernel's [B] frontier vector: every row predicates at its own
    length — matches the per-row masked fp32 reference."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention_kernel,
    )

    B, Smax, H, KV, hd = 3, 512, 4, 2, 64
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, 1, H, hd), jnp.float32)
    kc = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32)
    vc = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32)
    lens = jnp.asarray([5, 300, 0], jnp.int32)
    out = decode_attention_kernel(q, kc, vc, lens)
    kf = jnp.repeat(kc, H // KV, axis=2)
    vf = jnp.repeat(vc, H // KV, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(hd)
    kpos = jnp.arange(Smax)[None, None, None, :]
    logits = jnp.where(kpos <= lens[:, None, None, None], logits, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ragged_forward_per_slot_cache_len_matches_scalar():
    """forward_with_cache with a [B] frontier == per-row scalar runs (the
    cross-cutting model change), incl. the int8 scale caches."""
    from deepspeed_tpu.models.decoding import forward_with_cache, init_cache

    model = tiny_llama()
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    r = np.random.RandomState(7)
    toks = jnp.asarray(r.randint(0, 128, size=(3, 4)))
    lens = [0, 5, 9]
    for quant in (False, True):
        # ragged: one batched call with per-row frontiers over a shared
        # pre-seeded cache
        seed = jnp.asarray(r.randint(0, 128, size=(3, 16)))
        cache = init_cache(cfg, 3, 32, jnp.float32, quantized=quant)
        _, cache = forward_with_cache(cfg, params, seed, cache, 0,
                                      dtype=jnp.float32)
        ragged_logits, _ = forward_with_cache(
            cfg, params, toks, cache, jnp.asarray(lens, jnp.int32),
            dtype=jnp.float32,
        )
        for b, ln in enumerate(lens):
            cache_b = init_cache(cfg, 1, 32, jnp.float32, quantized=quant)
            _, cache_b = forward_with_cache(
                cfg, params, seed[b:b + 1], cache_b, 0, dtype=jnp.float32
            )
            # traced scalar frontier: keeps the reference on the same
            # cache-read attention path as the ragged call (a python int 0
            # would take the fresh-prefill branch, which attends the exact
            # unquantized k/v instead of the int8 cache)
            row_logits, _ = forward_with_cache(
                cfg, params, toks[b:b + 1], cache_b,
                jnp.asarray(ln, jnp.int32), dtype=jnp.float32,
            )
            np.testing.assert_allclose(
                np.asarray(ragged_logits[b]), np.asarray(row_logits[0]),
                rtol=2e-4, atol=2e-4, err_msg=f"quant={quant} row={b}",
            )


def test_unscheduled_active_slot_never_clobbers_live_cache():
    """An ACTIVE slot the plan leaves idle (num_new=0) must not write its
    padded chunk over live cache rows: the engine repoints idle rows'
    start_pos at the dead tail margin. Guards future scheduling policies
    (preemption, priority) that may skip a live slot mid-flight."""
    from deepspeed_tpu.serving.scheduler import StepPlan

    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(9)
    )
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 4, "max_tokens": 64,
    })
    # one prefill chunk lands tokens at slot-0 positions 0..3
    _submit(srv, "p0", np.random.RandomState(8).randint(0, 128, (6,)),
            max_new_tokens=4)
    srv.step()
    live = srv.capacity - srv.token_budget
    before = np.asarray(srv._caches["k"])[:, 0, :live].copy()
    # adversarial plan: slot 0 is active but unscheduled (all zeros — the
    # plan-default start_pos of 0 would point straight at live rows)
    N, W = srv.max_slots, srv.token_budget
    idle = StepPlan(
        tokens=np.zeros((N, W), np.int32), num_new=np.zeros(N, np.int32),
        start_pos=np.zeros(N, np.int32), fresh=np.zeros(N, np.bool_),
        sample=np.zeros(N, np.bool_),
    )
    srv._run_plan(idle)
    after = np.asarray(srv._caches["k"])[:, 0, :live]
    np.testing.assert_array_equal(before, after)


def test_metrics_submitted_counts_rejections():
    """Every submission counts as submitted — including graceful
    rejections — so 'submitted >= rejected' always holds."""
    clock = FakeClock()
    s = _sched(clock, max_slots=1, queue_limit=1, max_tokens=16)
    s.submit(_req("a"))                      # straight to the slot
    s.submit(_req("b"))                      # queued (limit 1)
    s.submit(_req("c"))                      # queue full → rejected
    s.submit(_req("big", plen=14, new=8))    # over capacity → evicted
    m = s.metrics
    assert m.submitted == 4
    assert m.rejected == 1 and m.evicted == 2
    assert m.submitted >= m.rejected


def test_apply_repetition_penalty_active_mask():
    """Satellite: inactive/padded slots keep their logits untouched."""
    from deepspeed_tpu.inference.engine import apply_repetition_penalty

    logits = jnp.asarray([[2.0, -2.0], [2.0, -2.0]])
    seen = jnp.asarray([[True, True], [True, True]])
    out = np.asarray(apply_repetition_penalty(
        logits, seen, 2.0, active=jnp.asarray([True, False])
    ))
    np.testing.assert_allclose(out, [[1.0, -4.0], [2.0, -2.0]])


# ---------------------------------------------------------------------------
# config / metrics / analytic streams
# ---------------------------------------------------------------------------
def test_serving_config_section_parses_and_validates():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError

    cfg = DeepSpeedConfig({
        "serving": {"enabled": True, "max_slots": 4, "token_budget": 32,
                    "kv_cache_dtype": "int8"},
    })
    assert cfg.serving.enabled and cfg.serving.max_slots == 4
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"serving": {"token_budget": 0}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"serving": {"kv_cache_dtype": "fp8"}})


def test_serving_metrics_and_kv_stream_intake():
    """Metrics TTFT/TPOT populate and the analytic KV stream flows
    through comm_logger.record_streams (the shared intake)."""
    from deepspeed_tpu.profiling.comm_logger import CommsLogger

    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(8)
    )
    logger = CommsLogger()
    try:
        srv = ServingEngine(engine=eng, comm_logger=logger, serving={
            "max_slots": 2, "token_budget": 8, "max_tokens": 64,
        })
        _submit(srv, "m0", np.arange(5) % 7, max_new_tokens=4)
        srv.run_until_idle()
    finally:
        logger.stop()
    m = srv.metrics.snapshot()
    assert m["finished"] == 1 and m["tokens_out"] == 4
    assert m["ttft_p50_s"] >= 0 and m["tpot_p50_s"] >= 0
    assert "tok/s" in srv.metrics.summary()
    # the KV arena stream was recorded per step through the ONE intake
    assert logger.kv_steps == srv.metrics.steps > 0
    assert logger.kv_bytes > 0
    assert "serving kv arena" in logger.summary()
    # the declared stream itself carries the schema the planner reads
    streams = srv.analytic_streams()
    kv = streams["kv_cache"]
    assert kv["kind"] == "hbm" and kv["bytes_per_step"] > 0
    assert kv["per_device_bytes_per_step"] <= kv["bytes_per_step"]


def test_lint_serving_config_traces_and_passes():
    """shardlint's serving branch: the slot step traces abstractly on a
    tp=2 CPU mesh and lints clean (R1–R8), with the KV stream attached."""
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.analysis import lint_config

    comm.destroy_process_group()
    model = tiny_llama(num_heads=4, num_kv_heads=4)
    report = lint_config(
        {
            "tensor_parallel": {"tp_size": 2},
            "serving": {"enabled": True, "max_slots": 2, "token_budget": 8,
                        "max_tokens": 64, "kv_cache_dtype": "int8"},
        },
        model=model,
        source="serving-unit",
    )
    assert report.ok, report.format()
    assert report.sources and report.sources[0]["source"] == "serving-unit"
