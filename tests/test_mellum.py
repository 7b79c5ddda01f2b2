"""Mellum2 (window and full attention layers mixed, YaRN on the full ones,
QK-norm, 64-expert-style routing) at a small size on the CPU, seeded random
weights, against the benchmark's plain reference
(``benchmarks/families/mellum.py``): ``transformer.apply``, the cached
forward through the two paged pools, the windowed paged kernel, the serving
engine and its host plane (pages kept by layer kind)."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.families import mellum as fam
from layer_loop_oracle import (assert_bitwise, layer_loop_forward,
                               random_cache)
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import RopeTable, mellum
from deepspeed_tpu.models.decoding import (
    WIN,
    _dense_cached_attention,
    _paged_gather,
    forward_with_cache,
    init_paged_cache,
)
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.ops.pallas.paged_attention import (
    key_counts,
    paged_attention_kernel,
)
from deepspeed_tpu.serving import Request
from deepspeed_tpu.serving.request import RequestStatus
from slot_program import ids_of, jit_init, paged_forward, reference_logits

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on both sides, the same equations in another order of summation:
# the largest difference seen is 3e-6 of the logits' range; a fault moves
# them by a hundred times the tolerance or more
RTOL = 1e-4
WINDOW, PS = 24, 16  # the window is not a multiple of the page
logits_of = reference_logits(fam)


def tiny_config():
    """The benchmark's configuration at its rehearsal size."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse")
    rope = cfg["rope_parameters"]
    rope["full_attention"] = dict(rope["full_attention"],
                                  **tiny.pop("rope_parameters")["full_attention"])
    return dict(cfg, **tiny)


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(tiny_config())


@pytest.fixture(scope="module")
def model():
    # wide initial weights, so that attention and the experts move the
    # logits by as much as the embedding does
    return mellum("mellum-tiny", initializer_range=0.2)


@pytest.fixture(scope="module")
def params(model):
    p = jit_init(model, jax.random.PRNGKey(0))
    attn = p["layers"]["attn"]
    for i, name in enumerate(("q_norm", "k_norm")):  # not all ones
        s = attn[name]["scale"]
        attn[name]["scale"] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(7 + i), s.shape)
    return p


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= rtol * np.abs(want).max()


def test_the_preset_is_the_published_model_and_the_tiny_one_its_shape(shape):
    big = mellum().config
    assert (big.hidden_size, big.num_layers, big.num_heads, big.kv_heads,
            big.hd, big.ffn, big.num_experts, big.moe_top_k, big.vocab_size,
            big.attn_window, big.max_seq_len) == (
        2304, 28, 32, 4, 128, 896, 64, 8, 98304, 1024, 131072)
    assert big.layer_pattern == ("window", "window", "window", "full")
    assert big.kind_count("window") == 21 and big.kind_count("full") == 7
    assert big.qk_norm and not big.tie_embeddings and big.norm_eps == 1e-6
    tiny = mellum("mellum-tiny").config
    assert (tiny.hidden_size, tiny.num_layers, tiny.num_heads, tiny.kv_heads,
            tiny.hd, tiny.ffn, tiny.vocab_size, tiny.num_experts,
            tiny.moe_top_k) == (
        shape.d, shape.layers, shape.heads, shape.kv_heads, shape.hd,
        shape.ffn, shape.vocab, shape.experts, shape.top_k)
    assert tiny.attn_window == shape.window == WINDOW
    assert deepspeed_tpu.models.MODEL_REGISTRY["mellum"] is mellum


def test_yarn_table_against_numbers_worked_by_hand():
    # head size 128, theta 500000, factor 16 over 8192, beta 32 and 1:
    # dim(n) = 128 ln(8192 / (2 pi n)) / (2 ln 500000)
    #   dim(32) = 128 x ln(40.7437) / 26.2447 = 18.079 -> low 18
    #   dim(1)  = 128 x ln(1303.80) / 26.2447 = 34.981 -> high 35
    table = RopeTable(theta=500000.0, factor=16.0, original_len=8192,
                      beta_fast=32.0, beta_slow=1.0,
                      attention_factor=1.2772588722239782)
    inv = table.inv_freq(128)
    extra = lambda i: 500000.0 ** (-2 * i / 128)
    assert inv.shape == (64,)
    np.testing.assert_allclose(inv[0], 1.0, rtol=1e-6)
    # below the ramp the plain frequency, above it a sixteenth
    np.testing.assert_allclose(inv[18], extra(18), rtol=1e-6)
    np.testing.assert_allclose(inv[35], extra(35) / 16, rtol=1e-6)
    np.testing.assert_allclose(inv[63], extra(63) / 16, rtol=1e-6)
    # inside it: i = 26 has ramp (26 - 18) / 17 = 0.470588
    ramp = 8 / 17
    np.testing.assert_allclose(
        inv[26], extra(26) * (1 - ramp) + extra(26) / 16 * ramp, rtol=1e-6)
    # 500000^(-52/128) = exp(-0.40625 x 13.1224) = 4.8394e-3, x 0.558824
    np.testing.assert_allclose(extra(26), 4.8394e-3, rtol=2e-4)
    np.testing.assert_allclose(inv[26], 2.7044e-3, rtol=2e-4)
    assert math.isclose(0.1 * math.log(16) + 1, table.attention_factor)
    # the reference works its table from the configuration file's section
    ref_inv, mscale = fam.rope_table(
        dict(rope_type="yarn", rope_theta=500000, factor=16,
             original_max_position_embeddings=8192, beta_fast=32,
             beta_slow=1, attention_factor=table.attention_factor), 128)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert mscale == table.attention_factor
    plain, one = fam.rope_table(dict(rope_type="default", rope_theta=500000),
                                128)
    np.testing.assert_allclose(plain, RopeTable(500000.0).inv_freq(128),
                               rtol=1e-6)
    assert one == 1.0


def test_apply_computes_the_reference(model, params, shape):
    ids = ids_of(100)  # past the window (24) and YaRN's original length (32)
    got, _ = jax.jit(lambda p, i: model.apply(p, i, dtype=jnp.float32))(
        params, jnp.asarray(ids[None]))
    want = logits_of(params, ids, shape)
    assert close(got[0], want)


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference_beyond_the_tolerance(
        model, params, shape, fault):
    ids = ids_of(100, seed=3)
    want = fam.logits(params, ids, shape)
    broken = fam.logits(ids=ids, shape=shape,
                        **fam.faulted(params, fault, shape))
    assert not close(broken, want, rtol=100 * RTOL), fault


def test_the_cached_forward_through_both_pools_is_the_reference(
        model, params, shape):
    # a slot shorter than the window beside one far longer; chunks of 16
    # straddle the window's edge (24) in the long one
    prompts = [ids_of(13, seed=1), ids_of(150, seed=2)]
    rows, seqs = paged_forward(model, params, prompts, 16, PS)
    for got, seq in zip(rows, seqs):
        want = logits_of(params, np.asarray(seq, np.int32), shape)
        assert got.shape == want.shape
        assert close(got, want)


@pytest.mark.parametrize("impl", ["flash", "xla"])
def test_two_carried_pools_are_bitwise_the_layer_loop(impl):
    """Two periods, so a layer's index inside its pool is not its place in
    the period: window layers 0-2 and 3-5 of a pool of six, full layers 0
    and 1 of a pool of two, both pools carried through the scan and
    written in place. Against the plain loop over layers, each on a cache
    of its own: logits and all four leaves bit for bit, over two chunks,
    ragged frontiers on both sides of the window, the pools filled with
    noise; through the two named kernels (``flash``) and the dense lines."""
    model = mellum("mellum-tiny", num_layers=8, initializer_range=0.2)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(2), dtype=jnp.float32)
    B, S, mp = 3, 8, 12
    pages = B * mp
    rng = np.random.default_rng(5)
    cache = random_cache(init_paged_cache(
        cfg, pages, PS, jnp.float32, window_pages=pages), seed=5)
    assert cache["k"].shape[0] == 2 and cache["k" + WIN].shape[0] == 6
    frontier = jnp.asarray([0, 21, 150], jnp.int32)
    num_new = jnp.asarray([S, 3, S], jnp.int32)
    kw = dict(
        page_table=jnp.asarray(rng.permutation(pages).reshape(B, mp)),
        page_table_win=jnp.asarray(rng.permutation(pages).reshape(B, mp)),
        num_new=num_new, token_valid=jnp.arange(S)[None, :] < num_new[:, None])
    got = want = (None, cache)
    fwd = jax.jit(lambda ids, c, cl: forward_with_cache(
        cfg, params, ids, c, cl, dtype=jnp.float32, **kw))
    with attention_impl(impl):
        for seed in (1, 2):
            args = (cfg, params, jnp.asarray(
                np.stack([ids_of(S, seed=10 * seed + b) for b in range(B)])))
            got = fwd(args[2], got[1], frontier)
            want = layer_loop_forward(*args, want[1], frontier, **kw)
            assert_bitwise(got, want)
            frontier = frontier + S
    for n in cache:
        assert not np.array_equal(np.asarray(got[1][n]), np.asarray(cache[n]))


@pytest.mark.parametrize("window", [None, 24, 30, 7, 64])
@pytest.mark.parametrize("block_k", [16, 32])
def test_windowed_paged_kernel_against_the_dense_lines(window, block_k):
    B, S, H, KV, hd, ps, mp = 4, 8, 4, 2, 32, 4, 40
    cfg = mellum("mellum-tiny", num_heads=H, num_kv_heads=KV,
                 head_dim=hd).config
    rng = np.random.default_rng(0)
    P = B * mp
    k = jnp.asarray(rng.normal(size=(P + 1, ps, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P + 1, ps, KV, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    pt = jnp.asarray(rng.permutation(P).reshape(B, mp), jnp.int32)
    kernel = jax.jit(lambda cl, nn: paged_attention_kernel(
        q, k[None], v[None], cl, pt, layer=0, num_new=nn, block_k=block_k,
        interpret=True, window=window))
    dense = jax.jit(lambda cl: _dense_cached_attention(
        cfg, q, _paged_gather(k, pt), _paged_gather(v, pt), cl,
        window=window))
    for cl, nn in (([0, 5, 77, 140], [8, 8, 3, 8]),
                   ([20, 33, 150, 0], [1, 8, 8, 0]),
                   ([23, 24, 25, 100], [8, 1, 2, 8])):
        cl, nn = jnp.asarray(cl, jnp.int32), jnp.asarray(nn, jnp.int32)
        out, want = kernel(cl, nn), dense(cl)
        for b in range(B):
            n = int(nn[b])
            if n:
                np.testing.assert_allclose(out[b, :n], want[b, :n],
                                           atol=2e-5, rtol=2e-5)


def test_key_counts_are_the_kernel_loops_arithmetic():
    rng = np.random.default_rng(0)
    for _ in range(50):
        cl = rng.integers(0, 3000, size=5)
        nn = rng.integers(0, 129, size=5)
        for window in (None, 1024, 24):
            brute = sum(
                min(c + i + 1, window or 10 ** 9)
                for c, n in zip(cl, nn) for i in range(n))
            attended, fetched = key_counts(cl, nn, 16, 200, window)
            assert attended == brute
            assert fetched >= attended / max(nn.max(), 1)
    # a slot far past the window reads two or three blocks, not its context
    _, full = key_counts([16000], [128], 16, 1048)
    _, win = key_counts([16000], [128], 16, 1048, 1024)
    assert full == 32 * 512 and win == 3 * 512
    # what the engine books (block_k = the page): the pages that hold a
    # visible key, 14,977..16,127 here, whatever block the kernel reads in
    _, win = key_counts([16000], [128], 16, 1048, 1024, block_k=16)
    assert win == (16127 // 16 - 14977 // 16 + 1) * 16


SERVING = dict(max_slots=3, token_budget=16, max_tokens=256, paged=True,
               page_size=4)


@pytest.fixture(scope="module")
def idle_engine(model, params):
    """ONE engine on ``SERVING`` (a build compiles the step) for the tests
    that read no counter of it and leave it idle and whole."""
    return deepspeed_tpu.init_serving(model, serving=dict(SERVING),
                                      params=params, dtype=jnp.float32)


def serve(model, params, prompts, new_tokens=10, srv=None, **over):
    srv = srv or deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, **over), params=params,
        dtype=jnp.float32)
    states = [srv.submit(Request(request_id=f"r{i}", prompt=p,
                                 max_new_tokens=new_tokens, temperature=0.0))
              for i, p in enumerate(prompts)]
    srv.run_until_idle()
    return srv, states


def test_the_engine_serves_generates_tokens_and_the_references_argmax(
        model, params, shape):
    prompts = [ids_of(90, seed=4), ids_of(30, seed=5), ids_of(61, seed=6)]
    srv, states = serve(model, params, prompts)
    assert srv.kinds_paged and srv.step_traces == 1
    assert srv.scheduler.prefix_cache is None  # off, and said so in the log
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32, max_tokens=256)
    for st, p in zip(states, prompts):
        assert st.status is RequestStatus.DONE and len(st.tokens) == 10
        out = np.asarray(eng.generate(p[None], max_new_tokens=10,
                                      temperature=0.0))[0]
        assert list(out[len(p):len(p) + 10]) == st.tokens
        full = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        want = logits_of(params, full[:-1], shape, last=10)
        assert list(np.asarray(want).argmax(-1)) == st.tokens
    snap = srv.metrics.snapshot()
    assert snap["window_pages_released"] > 0
    assert snap["attended_keys_window"] < snap["attended_keys_full"]
    assert snap["fetched_keys_window"] <= snap["fetched_keys_full"]
    assert snap["attention_paged_kernel_window"] == 0.0  # the CPU's lines


def test_the_engine_with_the_kernel_serves_the_dense_tokens(model, params,
                                                            idle_engine):
    prompts = [ids_of(70, seed=8), ids_of(9, seed=9)]
    _, dense = serve(model, params, prompts, new_tokens=6, srv=idle_engine)
    with attention_impl("flash"):  # the kernels, in interpret mode here
        srv, kernel = serve(model, params, prompts, new_tokens=6)
    assert srv.attention_path == "paged_kernel"
    assert srv.metrics.attention_paged_kernel_kinds == {
        "window": 1.0, "full": 1.0}
    assert [s.tokens for s in kernel] == [s.tokens for s in dense]
    srv.lower_step()  # the kinds' two tables are among its operands


def tick_invariants(srv):
    sch = srv.scheduler
    for pool in (sch.pool, sch.window_pool):
        assert pool.free_count + pool.live_count == pool.num_pages
    sch.assert_page_invariants()


@pytest.mark.parametrize("which", ["pool", "window_pool"])
def test_the_ticks_audit_names_a_page_that_drifted(idle_engine, which):
    """Each pool is audited every tick against the ids its holders name
    (counted in numpy, PagePool.check_leaks): a reference nobody
    holds is refused, in the window layers' pool as in the full layers'."""
    srv = idle_engine
    srv.submit(Request(request_id="r", prompt=ids_of(40, seed=3),
                       max_new_tokens=4, temperature=0.0))
    srv.step()
    tick_invariants(srv)
    pool = getattr(srv.scheduler, which)
    page = int(np.nonzero(pool.refcount)[0][0])
    pool.refcount[page] += 1
    with pytest.raises(AssertionError, match=f"refcount drift.*{page}: 2"):
        srv.scheduler.assert_page_invariants()
    pool.refcount[page] -= 1
    srv.run_until_idle()
    tick_invariants(srv)


def test_a_request_twenty_times_the_window_holds_no_more_window_pages(
        model, params):
    srv = deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, max_tokens=512), params=params,
        dtype=jnp.float32)
    most = -(-(WINDOW + 16) // 4) + 1
    assert srv.window_pages_per_slot == most == 11
    st = srv.submit(Request(request_id="long", prompt=ids_of(20 * WINDOW),
                            max_new_tokens=8, temperature=0.0))
    held = 0
    while srv.scheduler.has_work:
        srv.step()
        tick_invariants(srv)
        held = max(held, len(st.win_pages),
                   srv.metrics.window_pages_in_use)
    assert st.status is RequestStatus.DONE
    assert 0 < held <= most
    assert len(st.pages) == 0 and srv.scheduler.window_pool.live_count == 0
    # the full layers held the whole context meanwhile
    assert srv.metrics.window_pages_released >= 20 * WINDOW // 4 - most


def test_every_slot_at_max_tokens_exhausts_neither_pool(model, params):
    # auto-sized pools: pages_per_slot a slot in the full layers' pool, the
    # reckoned window pages a slot in the window layers'
    N, W, cap = 4, 16, 200
    srv = deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, max_slots=N, token_budget=W,
                            max_tokens=cap),
        params=params, dtype=jnp.float32)
    assert srv.num_pages == N * -(-(cap + W) // 4)
    assert srv.window_num_pages == N * srv.window_pages_per_slot
    states = [srv.submit(Request(request_id=f"m{i}",
                                 prompt=ids_of(cap - 8, seed=20 + i),
                                 max_new_tokens=8, temperature=0.0))
              for i in range(2 * N)]  # a second wave takes the freed slots
    while srv.scheduler.has_work:
        srv.step()
        tick_invariants(srv)
    assert all(s.status is RequestStatus.DONE for s in states)
    assert srv.metrics.evicted == 0 and srv.step_traces == 1


def test_a_shared_prefix_changes_no_ones_tokens(model, params, idle_engine):
    # three pages of shared prefix: with window layers the prefix cache is
    # off, so each request computes its own keys, together as alone
    prefix = ids_of(3 * 4, seed=30)
    a = np.concatenate([prefix, ids_of(40, seed=31)])
    b = np.concatenate([prefix, ids_of(55, seed=32)])
    srv, both = serve(model, params, [a, b], prefix_cache=True)
    assert srv.metrics.prefix_hits == 0
    for st, p in zip(both, (a, b)):
        _, alone = serve(model, params, [p], srv=idle_engine)
        assert st.tokens == alone[0].tokens
    # served one after the other on one engine, the second still misses
    later = srv.submit(Request(request_id="later", prompt=a,
                               max_new_tokens=10, temperature=0.0))
    srv.run_until_idle()
    assert later.tokens == both[0].tokens and srv.metrics.prefix_hits == 0


def test_what_moves_pages_is_refused_by_name(model, params, idle_engine):
    for over, word in ((dict(host_pages=8), "host_pages"),
                       (dict(fleet=dict(enabled=True, replicas=2,
                                        prefill_replicas=1)),
                        "prefill_replicas")):
        with pytest.raises(DeepSpeedConfigError, match=word) as e:
            deepspeed_tpu.init_serving(
                model, serving=dict(SERVING, **over), params=params,
                dtype=jnp.float32)
        assert "window layers" in str(e.value)
    srv, states = serve(model, params, [ids_of(20, seed=40)],
                        srv=idle_engine)
    with pytest.raises(RuntimeError, match="window layers"):
        srv.export_kv_pages([0])
    with pytest.raises(RuntimeError, match="window layers"):
        srv.import_kv_pages({}, [0])
    with pytest.raises(RuntimeError, match="window layers"):
        srv.scheduler.adopt(states[0])
    # a model of one kind keeps its prefix cache and its one pool
    from deepspeed_tpu.models import mixtral

    plain = mixtral("mixtral-tiny")
    one = deepspeed_tpu.init_serving(
        plain, serving=dict(SERVING), dtype=jnp.float32,
        params=plain.init(jax.random.PRNGKey(1)))
    assert not one.kinds_paged and one.scheduler.prefix_cache is not None
    assert one.scheduler.window_pool is None
    assert set(one._caches) == {"k", "v"}
    assert WIN == "_win" and set(srv._caches) == {"k", "v", "k_win", "v_win"}


@pytest.mark.parametrize("feature", ["plain", "layer_drop", "random_ltd"])
def test_a_period_of_one_kind_is_the_one_kind_stack(feature):
    """``apply_layer_stack`` has one body, a period of the pattern; a
    period of two full layers has to compute what the stack of single
    layers does, layer drop's gates and random-LTD's token subsets (drawn
    from the same per-layer keys) included."""
    from deepspeed_tpu.models import llama

    kw = dict(num_layers=4, hidden_size=32, num_heads=4, num_kv_heads=2,
              intermediate_size=64, vocab_size=128, max_seq_len=64)
    one, two = llama(**kw), llama(layer_pattern=("full", "full"), **kw)
    p = one.init(jax.random.PRNGKey(3), dtype=jnp.float32)
    ids = jnp.asarray(ids_of(48)[None] % 128)
    extra = dict(plain={}, layer_drop=dict(pld_keep=jnp.linspace(1.0, 0.3, 4)),
                 random_ltd=dict(ltd_keep=16, ltd_layers=(0, 2)))[feature]
    run = lambda m: m.apply(p, ids, dtype=jnp.float32, train=True,
                            rng=jax.random.PRNGKey(5), **extra)
    got, want = run(two), run(one)
    np.testing.assert_array_equal(np.asarray(got[0] if isinstance(got, tuple) else got),
                                  np.asarray(want[0] if isinstance(want, tuple) else want))


def test_layer_features_work_on_whole_periods_or_refuse_by_name(
        model, params):
    from deepspeed_tpu.runtime.zero import prefetch

    ids = jnp.asarray(ids_of(32)[None])
    kw = dict(dtype=jnp.float32, train=True, rng=jax.random.PRNGKey(0))
    L = model.config.num_layers
    plain = model.apply(params, ids, **kw)
    kept = model.apply(params, ids, pld_keep=jnp.ones(L), **kw)
    np.testing.assert_array_equal(np.asarray(kept[0]), np.asarray(plain[0]))
    dropped = model.apply(params, ids, pld_keep=jnp.zeros(L), **kw)
    assert not np.array_equal(np.asarray(dropped[0]), np.asarray(plain[0]))
    ltd = model.apply(params, ids, ltd_keep=16, ltd_layers=(0, L), **kw)
    assert np.isfinite(np.asarray(ltd[0])).all()
    with pytest.raises(ValueError, match="cut a period"):
        model.apply(params, ids, ltd_keep=16, ltd_layers=(1, 3), **kw)
    with prefetch.prefetch_scope(object()):
        with pytest.raises(NotImplementedError, match="scans periods"):
            model.apply(params, ids, **kw)
    with pytest.raises(ValueError, match="whole periods"):
        mellum("mellum-tiny", num_layers=6)


def test_modelchecks_invariants_hold_for_each_pool(idle_engine):
    from deepspeed_tpu.analysis.modelcheck.invariants import (
        CheckFailure,
        _check_pool,
    )

    srv = idle_engine
    srv.submit(Request(request_id="a", prompt=ids_of(100, seed=50),
                       max_new_tokens=4, temperature=0.0))
    for _ in range(4):
        srv.step()
        _check_pool(None, 0, srv.scheduler)
    leaked = srv.scheduler.window_pool.alloc()  # a reference no slot holds
    with pytest.raises(CheckFailure, match="window pool") as e:
        _check_pool(None, 0, srv.scheduler)
    assert e.value.invariant == "H3"
    srv.scheduler.window_pool.decref(leaked)
    srv.run_until_idle()
    _check_pool(None, 0, srv.scheduler)
