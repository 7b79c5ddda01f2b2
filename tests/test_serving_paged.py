"""Block-paged, prefix-shared KV arena (ISSUE 6).

The oracle: the PAGED serving engine must reproduce the contiguous slot
arena BITWISE token-for-token (greedy and sampled, tp=2, int8-KV) — the
gathered per-slot views hold byte-for-byte what the dense arena holds at
every mapped position, so outputs cannot drift. Plus: prefix-cache reuse
(an identical prompt decodes with ZERO prefill chunks scheduled, its
pages shared read-only), copy-on-write on divergence, the page-pool leak
invariant after every scheduler tick, forced eviction under pool
exhaustion (liveness), the paged Pallas decode kernel, and the static
analysis surface (lint clean, R6 fires when --hbm-gb undercuts the pool,
paged KV traffic declared via analytic_streams).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import llama
from deepspeed_tpu.serving import (PagePool, PrefixCache, Request,
                                   RequestStatus, ServingEngine)


def tiny_llama(**kw):
    d = dict(vocab_size=128, max_seq_len=64, hidden_size=32, num_layers=2,
             num_heads=4, num_kv_heads=2, intermediate_size=64)
    d.update(kw)
    return llama("llama-tiny", **d)


def _engine(model, **kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("max_tokens", 64)
    kw.setdefault("rng", jax.random.PRNGKey(1))
    return deepspeed_tpu.init_inference(model, **kw)


def _serving(eng, paged, **over):
    serving = {"max_slots": 3, "token_budget": 8, "max_tokens": 64}
    if paged:
        serving.update({"paged": True, "page_size": 16})
    serving.update(over)
    return ServingEngine(engine=eng, serving=serving)


def _drive(srv, prompts, news, **req_kw):
    """One fixed staggered-arrival schedule, shared by both arenas."""
    states = []

    def sub(i):
        kw = {k: (v[i] if isinstance(v, list) else v)
              for k, v in req_kw.items()}
        states.append(srv.submit(Request(
            request_id=f"r{i}", prompt=prompts[i], max_new_tokens=news[i],
            **kw,
        )))

    sub(0)
    sub(1)
    srv.step()
    srv.step()
    for i in range(2, len(prompts)):
        sub(i)
    srv.run_until_idle()
    return states


# ---------------------------------------------------------------------------
# the bitwise oracle: paged == contiguous arena, token for token
# ---------------------------------------------------------------------------
def test_paged_equals_contiguous_greedy_bitwise():
    model = tiny_llama()
    eng = _engine(model)
    r = np.random.RandomState(0)
    prompts = [r.randint(0, 128, size=(n,)) for n in (3, 12, 7, 5, 9)]
    news = [6, 4, 8, 5, 3]
    dense = _drive(_serving(eng, paged=False), prompts, news)
    srv_p = _serving(eng, paged=True)
    paged = _drive(srv_p, prompts, news)
    for i, (d, p) in enumerate(zip(dense, paged)):
        assert d.status is RequestStatus.DONE
        assert p.status is RequestStatus.DONE
        np.testing.assert_array_equal(d.output(), p.output(),
                                      err_msg=f"r{i}")
        want = eng.generate(prompts[i][None, :], max_new_tokens=news[i],
                            temperature=0.0)
        np.testing.assert_array_equal(p.output(), want[0], err_msg=f"r{i}")
    # ONE trace for the whole ragged paged replay (zero recompiles)
    assert srv_p.step_traces == 1


def test_paged_equals_contiguous_sampled_tp2_int8_bitwise():
    """Sampled decoding with shared keys on a tp=2 mesh with an int8
    paged pool: the sharded gather/scatter path reproduces the dense
    arena bitwise across a temperature/top-k/top-p mix in one batch."""
    model = tiny_llama(num_heads=4, num_kv_heads=4)
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=jax.devices()[:2])
    eng = _engine(model, topology=topo, kv_cache_dtype="int8",
                  rng=jax.random.PRNGKey(4))
    r = np.random.RandomState(3)
    prompts = [r.randint(0, 128, size=(n,)) for n in (5, 11, 4)]
    news = [6, 5, 6]
    keys = [jax.random.PRNGKey(100 + i) for i in range(3)]
    cases = dict(
        temperature=[0.8, 0.0, 0.7],
        top_k=[10, 0, 0],
        top_p=[1.0, 1.0, 0.85],
        rng=keys,
    )
    dense = _drive(_serving(eng, paged=False), prompts, news, **cases)
    srv_p = _serving(eng, paged=True)
    paged = _drive(srv_p, prompts, news, **cases)
    for i, (d, p) in enumerate(zip(dense, paged)):
        np.testing.assert_array_equal(d.output(), p.output(),
                                      err_msg=f"r{i}")
    assert srv_p.step_traces == 1


def test_paged_attention_kernel_tp2_serves_the_dense_tokens():
    """The [slots, chunk] Pallas attention under the kernel impl on a
    tp=2 mesh (heads over tp through shard_map, pools sharded on their
    head axis): greedy tokens equal the XLA path's, one trace, and the
    engine says which attention its step took."""
    from deepspeed_tpu.ops.attention import attention_impl

    model = tiny_llama(num_heads=4, num_kv_heads=4)
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=jax.devices()[:2])
    eng = _engine(model, topology=topo, rng=jax.random.PRNGKey(4))
    r = np.random.RandomState(8)
    prompts = [r.randint(0, 128, size=(n,)) for n in (5, 19, 11)]
    news = [6, 4, 5]
    dense_srv = _serving(eng, paged=True)
    dense = _drive(dense_srv, prompts, news)
    assert dense_srv.attention_path == "dense"
    with attention_impl("flash"):  # the step is compiled when it is built
        srv = _serving(eng, paged=True)
    kernel = _drive(srv, prompts, news)
    for i, (d, k) in enumerate(zip(dense, kernel)):
        np.testing.assert_array_equal(d.output(), k.output(),
                                      err_msg=f"r{i}")
    assert srv.attention_path == "paged_kernel"
    assert srv.step_traces == 1


def test_decoding_slots_take_the_small_tile_and_the_step_says_so():
    """4 query heads a KV head under a 16-row budget: a [64, hd] stack a
    slot, of which a decoding slot computes the first 32 rows alone
    (ops/pallas/paged_attention.py SMALL_ROWS). Greedy tokens equal the XLA
    path's through steps that mix a prompt chunk with decoding slots, and
    the step's counts say how many slots took the small tile: none on the
    dense path."""
    from deepspeed_tpu.ops.attention import attention_impl

    # (on a tp=2 mesh: heads over tp divide the KV heads, not the group)
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=jax.devices()[:2])
    eng = _engine(tiny_llama(num_heads=8, num_kv_heads=2), topology=topo,
                  rng=jax.random.PRNGKey(4))
    r = np.random.RandomState(8)
    prompts = [r.randint(0, 128, size=(n,)) for n in (5, 37, 11)]
    news = [6, 4, 5]
    dense_srv = _serving(eng, paged=True, token_budget=16)
    dense = _drive(dense_srv, prompts, news)
    with attention_impl("flash"):
        srv = _serving(eng, paged=True, token_budget=16)
    kernel = _drive(srv, prompts, news)
    for i, (d, k) in enumerate(zip(dense, kernel)):
        np.testing.assert_array_equal(d.output(), k.output(),
                                      err_msg=f"r{i}")
    assert srv.attention_path == "paged_kernel"
    # 8 rows of 4 heads fit the small tile, 9 do not, an idle slot has none
    plan = type("P", (), dict(start_pos=np.array([16, 30, 7, 0]),
                              num_new=np.array([9, 1, 8, 0])))
    assert srv._count_keys(plan)["small_tile_slots_full"] == 2
    assert srv.metrics.snapshot()["small_tile_slots_full"] == 2
    assert dense_srv._count_keys(plan)["small_tile_slots_full"] == 0


# ---------------------------------------------------------------------------
# prefix cache + copy-on-write
# ---------------------------------------------------------------------------
def test_prefix_cache_skips_prefill_and_cow_diverges():
    """Two requests share a prompt: the second one's entire prompt (but
    the final sampling feed) comes from the cache — ZERO prefill chunks
    scheduled — and it emits identical tokens. Divergence happens inside
    a shared partial page, so the step copies-on-write instead of
    touching the shared page; a third identical request afterwards proves
    the shared pages were never corrupted."""
    model = tiny_llama()
    eng = _engine(model)
    srv = _serving(eng, paged=True, max_slots=2)
    prompt = np.random.RandomState(5).randint(0, 128, size=(20,))
    want = eng.generate(prompt[None, :], max_new_tokens=6, temperature=0.0)

    a = srv.submit(Request(request_id="a", prompt=prompt, max_new_tokens=6))
    srv.run_until_idle()
    np.testing.assert_array_equal(a.output(), want[0])
    chunks_before = srv.metrics.prefill_chunks
    booked = (srv.metrics.prompt_tokens, srv.metrics.chunk_steps)
    held, plan = [], srv.scheduler.plan

    def planned(**kw):
        held.append(plan(**kw))
        return held[-1]

    srv.scheduler.plan = planned
    b = srv.submit(Request(request_id="b", prompt=prompt, max_new_tokens=6))
    srv.run_until_idle()
    srv.scheduler.plan = plan
    held = [p.held() for p in held if p is not None]
    assert b.status is RequestStatus.DONE
    np.testing.assert_array_equal(b.output(), want[0])
    # the entire prompt but its final token came from shared pages …
    assert b.cached_tokens == prompt.size - 1
    # … so NO prefill chunk was scheduled (only the cached-tail feed)
    assert srv.metrics.prefill_chunks == chunks_before
    assert srv.metrics.cached_tail_feeds >= 1
    # … and that feed is one sampling row with the prompt behind it, a
    # decode row to the device: no prompt row, no chunk step (ISSUE 54)
    assert held[0] == dict(scheduled_tokens=1, prompt_rows=0, prompt_slots=0,
                           decode_slots=1, context_tokens=prompt.size)
    m = srv.metrics
    assert (m.prompt_tokens, m.chunk_steps) == booked
    assert m.prompt_tokens == prompt.size  # a's: b's length less its cache
    assert m.decode_tokens == 5 + (1 + 5)  # less its cached-tail feed
    assert srv.metrics.prefix_hits >= 1
    # b's first write landed inside a's shared partial page → COW fired
    assert srv.metrics.cow_copies >= 1

    # divergence safety: a third identical request still reproduces the
    # reference — b's copy-on-write never touched the shared pages
    c = srv.submit(Request(request_id="c", prompt=prompt, max_new_tokens=6))
    srv.run_until_idle()
    np.testing.assert_array_equal(c.output(), want[0])


def test_prefix_cache_partial_hit_then_divergent_suffix():
    """Requests sharing only a prefix: the common pages are reused, the
    divergent suffixes prefill independently, and BOTH reproduce the
    single-request reference bitwise."""
    model = tiny_llama()
    eng = _engine(model)
    srv = _serving(eng, paged=True, max_slots=2)
    r = np.random.RandomState(6)
    common = r.randint(0, 128, size=(16,))  # exactly one full page
    tails = [r.randint(0, 128, size=(5,)), r.randint(0, 128, size=(7,))]
    prompts = [np.concatenate([common, t]) for t in tails]
    wants = [
        eng.generate(p[None, :], max_new_tokens=5, temperature=0.0)
        for p in prompts
    ]
    s0 = srv.submit(Request(request_id="p0", prompt=prompts[0],
                            max_new_tokens=5))
    srv.run_until_idle()
    s1 = srv.submit(Request(request_id="p1", prompt=prompts[1],
                            max_new_tokens=5))
    srv.run_until_idle()
    np.testing.assert_array_equal(s0.output(), wants[0][0])
    np.testing.assert_array_equal(s1.output(), wants[1][0])
    # the shared page covered at least the first full page of p1's prompt
    assert s1.cached_tokens >= 16


# ---------------------------------------------------------------------------
# page pool: leak invariant, exhaustion liveness, forced eviction
# ---------------------------------------------------------------------------
def test_page_pool_refcounts_and_leak_check():
    pool = PagePool(4)
    a, b = pool.alloc(), pool.alloc()
    pool.incref(a)
    assert pool.free_count == 2 and pool.live_count == 2
    pool.check_leaks([a, b, a])
    pool.decref(a)
    pool.decref(a)
    assert pool.free_count == 3
    with pytest.raises(AssertionError, match="dead page"):
        pool.decref(a)
    with pytest.raises(AssertionError, match="refcount drift"):
        pool.check_leaks([b, b])


@pytest.mark.parametrize("held, drift", [
    ("a a b", None),          # one id a reference: what the holders name
    ("a b", "a"),             # a reference the holders do not know
    ("a a b b", "b"),         # one the pool does not know
    ("a a b c", "c"),         # a dead page held
    ("a a b 9", "9"),         # an id past the pool
])
def test_page_pool_leak_check_by_held_ids(held, drift):
    """The scheduler's audit of every tick: the holders' page ids, counted
    in numpy, against the refcounts."""
    pool = PagePool(4)
    a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
    pool.incref(a)
    pool.decref(c)
    ids = {"a": a, "b": b, "c": c, "9": 9}
    view = (ids[x] for x in held.split())  # any iterable, read once
    if drift is None:
        pool.check_leaks(view)
        return
    with pytest.raises(AssertionError, match="refcount drift") as err:
        pool.check_leaks(view)
    theirs = str(err.value).split("holders")[1]
    assert f"{ids[drift]}:" in theirs


def test_prefix_cache_eviction_frees_pages():
    pool = PagePool(4)
    cache = PrefixCache(pool, page_size=4)
    pages = [pool.alloc(), pool.alloc()]
    toks = np.arange(6)  # one full page + a 2-token tail
    # 3 entries: the full-page hash, its partial-match run, and the tail
    assert cache.insert(toks, pages) == 3
    for p in pages:  # caller drops its own refs; cache keeps the pages
        pool.decref(p)
    assert pool.free_count == 2 and len(cache) == 3
    got, covered = cache.match(np.arange(6))
    assert covered == 6 and got == pages
    # mismatching tail: only the full page matches
    got, covered = cache.match(np.asarray([0, 1, 2, 3, 9, 9]))
    assert covered == 4 and got == pages[:1]
    while cache.evict_lru():
        pass
    assert pool.free_count == 4 and len(cache) == 0


def test_pool_exhaustion_evicts_newest_and_drains():
    """num_pages at the liveness floor: concurrent requests contend for
    pages; the scheduler force-evicts the newest under starvation and
    every surviving request still finishes with correct output. The leak
    invariant (checked after every tick inside the scheduler) holds."""
    model = tiny_llama()
    eng = _engine(model)
    srv = _serving(eng, paged=True, max_slots=3, token_budget=8,
                   num_pages=5, prefix_cache=False)  # 5 == pages_per_slot
    r = np.random.RandomState(7)
    prompts = [r.randint(0, 128, size=(n,)) for n in (30, 30, 30)]
    states = [
        srv.submit(Request(request_id=f"x{i}", prompt=p, max_new_tokens=4))
        for i, p in enumerate(prompts)
    ]
    finished = srv.run_until_idle()
    assert any(s.status is RequestStatus.DONE for s in states)
    for s in states:
        if s.status is RequestStatus.DONE:
            want = eng.generate(s.request.prompt[None, :], max_new_tokens=4,
                                temperature=0.0)
            np.testing.assert_array_equal(s.output(), want[0])
        else:
            assert s.status is RequestStatus.EVICTED
            assert s.evict_reason == "page pool exhausted"
            assert s.retry_after is not None
    # pool fully drained once everything released
    assert srv.scheduler.pool.free_count == srv.scheduler.pool.num_pages
    assert len(finished) == sum(
        1 for s in states if s.status is RequestStatus.DONE
    )


def test_evicted_request_resubmits_and_reproduces():
    """A page-starved eviction rewinds the request; resubmission after
    the pool frees reproduces the deterministic output."""
    model = tiny_llama()
    eng = _engine(model)
    srv = _serving(eng, paged=True, max_slots=2, num_pages=5,
                   prefix_cache=False)
    r = np.random.RandomState(8)
    p0, p1 = r.randint(0, 128, size=(30,)), r.randint(0, 128, size=(30,))
    # each request runs to 64 tokens = 4 pages; 5 pages for two slots
    # strands both mid-decode → forced eviction of the newest
    s0 = srv.submit(Request(request_id="k0", prompt=p0, max_new_tokens=34))
    s1 = srv.submit(Request(request_id="k1", prompt=p1, max_new_tokens=34))
    srv.run_until_idle()
    evicted = [s for s in (s0, s1) if s.status is RequestStatus.EVICTED]
    done = [s for s in (s0, s1) if s.status is RequestStatus.DONE]
    assert len(evicted) == 1 and len(done) == 1
    st = srv.scheduler.resubmit(evicted[0])
    srv.run_until_idle()
    assert st.status is RequestStatus.DONE
    want = eng.generate(st.request.prompt[None, :], max_new_tokens=34,
                        temperature=0.0)
    np.testing.assert_array_equal(st.output(), want[0])
    # the retry's TTFT was measured from ITS OWN first token (the
    # pre-eviction timestamp was cleared) — never negative
    assert all(t >= 0 for t in srv.metrics.ttft_s)


# ---------------------------------------------------------------------------
# the paged Pallas decode kernel
# ---------------------------------------------------------------------------
def test_paged_decode_attention_kernel_matches_reference():
    """Pages physically shuffled through the table, per-row frontiers:
    the scalar-prefetch paged kernel matches the masked fp32 reference."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_kernel,
    )

    B, mp, ps, H, KV, hd = 3, 4, 16, 4, 2, 64
    P1 = 9  # 8 pages + NULL
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, 1, H, hd), jnp.float32)
    k_pool = jnp.asarray(r.randn(P1, ps, KV, hd), jnp.float32)
    v_pool = jnp.asarray(r.randn(P1, ps, KV, hd), jnp.float32)
    # shuffled physical pages; unmapped entries point at NULL (page 8)
    pt = np.full((B, mp), 8, np.int32)
    pt[0, :3] = [5, 2, 7]
    pt[1, :1] = [0]
    pt[2, :4] = [1, 3, 4, 6]
    lens = jnp.asarray([37, 3, 60], jnp.int32)
    out = paged_decode_attention_kernel(
        q, k_pool, v_pool, lens, jnp.asarray(pt)
    )
    # dense reference over the gathered views
    kc = np.asarray(k_pool)[pt].reshape(B, mp * ps, KV, hd)
    vc = np.asarray(v_pool)[pt].reshape(B, mp * ps, KV, hd)
    kf = np.repeat(kc, H // KV, axis=2)
    vf = np.repeat(vc, H // KV, axis=2)
    logits = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), kf) / np.sqrt(hd)
    kpos = np.arange(mp * ps)[None, None, None, :]
    logits = np.where(kpos <= np.asarray(lens)[:, None, None, None],
                      logits, -1e30)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", probs, vf)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


# ---------------------------------------------------------------------------
# config + static analysis surface
# ---------------------------------------------------------------------------
def test_serving_paged_config_validation():
    from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError

    cfg = DeepSpeedConfig({
        "serving": {"enabled": True, "paged": True, "page_size": 16,
                    "num_pages": 64, "max_tokens": 64, "token_budget": 32},
    })
    assert cfg.serving.paged and cfg.serving.pages_per_slot() == 6
    # the engine-clamped max_tokens is authoritative for the page math
    assert cfg.serving.pages_per_slot(32) == 4
    with pytest.raises(DeepSpeedConfigError, match="page_size"):
        DeepSpeedConfig({"serving": {"page_size": 0}})
    # the num_pages liveness floor is enforced by the ENGINE (it knows
    # the model-clamped max_tokens; config validation alone does not)
    model = tiny_llama()
    eng = _engine(model)
    with pytest.raises(DeepSpeedConfigError, match="liveness floor"):
        ServingEngine(engine=eng, serving={
            "max_slots": 2, "token_budget": 8, "max_tokens": 64,
            "paged": True, "page_size": 16, "num_pages": 2,
        })


def test_prefix_cache_bypassed_for_repetition_penalty():
    """A penalized request's ``seen`` matrix is built from FED tokens, so
    it must never take a prefix-cache hit (sampling would depend on cache
    warmth): it re-prefills and still reproduces the oracle bitwise."""
    model = tiny_llama()
    eng = _engine(model)
    srv = _serving(eng, paged=True, max_slots=2)
    prompt = np.random.RandomState(11).randint(0, 128, size=(20,))
    a = srv.submit(Request(request_id="a", prompt=prompt, max_new_tokens=6))
    srv.run_until_idle()  # a's pages are now in the prefix cache
    kw = dict(max_new_tokens=6, temperature=0.9, repetition_penalty=1.3,
              rng=jax.random.PRNGKey(42))
    b = srv.submit(Request(request_id="b", prompt=prompt, **kw))
    srv.run_until_idle()
    assert b.cached_tokens == 0  # penalty bypasses the cache entirely
    want = eng.generate(prompt[None, :], **kw)
    np.testing.assert_array_equal(b.output(), want[0])


def test_lint_paged_serving_config_and_r6_page_budget():
    """The paged slot step traces abstractly on a tp=2 CPU mesh and lints
    clean; arming R6 with a budget the page pool cannot fit turns it into
    an error BEFORE anything compiles — the static page-budget gate."""
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.analysis import lint_serving_config

    comm.destroy_process_group()
    model = tiny_llama(num_heads=4, num_kv_heads=4)
    cfg = {
        "tensor_parallel": {"tp_size": 2},
        "serving": {"enabled": True, "max_slots": 2, "token_budget": 8,
                    "max_tokens": 64, "kv_cache_dtype": "int8",
                    "paged": True, "page_size": 16, "num_pages": 12},
    }
    report = lint_serving_config(cfg, model=model, source="paged-unit")
    assert report.ok, report.format()
    # undercut the budget: params + the page pool cannot fit in 64 KiB
    tight = lint_serving_config(
        cfg, model=model, source="paged-tight", hbm_budget_bytes=64 * 1024,
    )
    assert any(f.rule == "R6" for f in tight.findings), tight.format()


def test_paged_analytic_stream_schema():
    """analytic_streams declares the paged KV traffic (R8 schema: hbm
    kind, per-device bytes) with the page geometry attached."""
    from deepspeed_tpu.profiling.comm_logger import CommsLogger

    model = tiny_llama()
    eng = _engine(model, rng=jax.random.PRNGKey(9))
    logger = CommsLogger()
    try:
        srv = _serving(eng, paged=True, max_slots=2)
        srv.comm_logger = logger
        srv.submit(Request(request_id="m0", prompt=np.arange(5) % 7,
                           max_new_tokens=3))
        srv.run_until_idle()
    finally:
        logger.stop()
    kv = srv.analytic_streams()["kv_cache"]
    assert kv["kind"] == "hbm" and kv["paged"] is True
    assert kv["bytes_per_step"] > 0 and kv["pool_bytes"] > 0
    assert kv["page_size"] == 16 and kv["num_pages"] == srv.num_pages
    assert kv["per_device_bytes_per_step"] <= kv["bytes_per_step"]
    assert logger.kv_steps == srv.metrics.steps > 0
