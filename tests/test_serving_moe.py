"""MoE-native serving: expert-parallel decode inside the ONE slot step
(ISSUE 14).

The oracle: an ep-sharded ServingEngine replays token-for-token BITWISE
equal to a dense-replicated engine of the same params across ragged
arrival/occupancy sweeps — greedy, sampled-with-shared-keys, paged,
spec-on and int8-expert mixes — with ``step_traces == 1`` on both sides,
for BOTH exchange forms (stock collectives and the decode-shaped
chunked-ppermute ring). Plus the null-expert gating contract, the static
capacity rule, the load-balance metrics, the serving moe-a2a planner
axis and the MoE serving lint example.

Heavy CPU-mesh legs are marked ``slow`` (out of the 1-core tier-1
budget) and everything here carries ``-m moe_serve``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.serving import Request, ServingEngine, ServingMetrics

pytestmark = pytest.mark.moe_serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_mixtral(**kw):
    d = dict(vocab_size=64, max_seq_len=64, hidden_size=32, num_layers=2,
             num_heads=4, num_kv_heads=2, intermediate_size=64,
             num_experts=4, moe_top_k=2)
    d.update(kw)
    return mixtral("mixtral-tiny", **d)


def _engine(ep=1, model=None, **kw):
    topo = None
    if ep > 1:
        topo = MeshTopology(
            dims=ParallelDims(ep=ep), devices=jax.devices()[:ep]
        )
    return deepspeed_tpu.init_inference(
        model or tiny_mixtral(), dtype=jnp.float32, max_tokens=64,
        topology=topo, rng=jax.random.PRNGKey(1), **kw
    )


def _replay(srv, cases, prompts):
    """Staggered ragged replay; returns per-request token lists."""
    states = []
    states.append(srv.submit(Request(request_id="r0", prompt=prompts[0],
                                     **cases[0])))
    states.append(srv.submit(Request(request_id="r1", prompt=prompts[1],
                                     **cases[1])))
    srv.step()
    srv.step()
    for i in range(2, len(cases)):
        states.append(srv.submit(Request(
            request_id=f"r{i}", prompt=prompts[i], **cases[i]
        )))
        srv.step()
    srv.run_until_idle()
    assert srv.step_traces == 1, srv.step_traces
    return [list(s.tokens) for s in states]


CASES = [
    dict(max_new_tokens=6),
    dict(max_new_tokens=4, temperature=0.8, top_k=10),
    dict(max_new_tokens=8),
    dict(max_new_tokens=5, temperature=0.7, top_p=0.9),
]


def _prompts(seed=0, vocab=64):
    r = np.random.RandomState(seed)
    return [r.randint(0, vocab, size=(n,)) for n in (3, 12, 7, 5)]


# ---------------------------------------------------------------------------
# the tentpole oracle: ep-sharded slot decode == dense-replicated decode
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("form", ["stock", "chunked"])
def test_ep_parity_greedy_and_sampled(form, devices8):
    serving = {"max_slots": 3, "token_budget": 8, "max_tokens": 64,
               "moe_a2a": form}
    dense = _replay(ServingEngine(engine=_engine(ep=1), serving=serving),
                    CASES, _prompts())
    srv = ServingEngine(engine=_engine(ep=2), serving=serving)
    assert srv.moe_a2a_form == form
    ep = _replay(srv, CASES, _prompts())
    assert ep == dense
    # load-balance counters rode along, NaN-free
    snap = srv.metrics.snapshot()
    assert snap["moe_steps"] > 0
    assert snap["moe_routed_tokens"] > 0
    assert all(np.isfinite(v) for v in snap.values())


@pytest.mark.slow
def test_ep_parity_paged_spec_int8kv(devices8):
    """The full mix: block-paged arena + speculative decoding + int8 KV
    cache, ep-sharded vs dense-replicated, bitwise."""
    serving = {
        "max_slots": 3, "token_budget": 12, "max_tokens": 48,
        "paged": True, "page_size": 8, "kv_cache_dtype": "int8",
        "spec": {"enabled": True, "max_draft": 3},
    }
    # repetitive prompts so the n-gram drafts land acceptances
    r = np.random.RandomState(3)
    prompts = [np.tile(r.randint(0, 64, size=(3,)), 6)[:n]
               for n in (9, 14, 11, 8)]
    cases = [dict(max_new_tokens=n) for n in (8, 6, 7, 5)]
    dense = _replay(
        ServingEngine(engine=_engine(ep=1), serving=serving), cases, prompts
    )
    ep = _replay(
        ServingEngine(engine=_engine(ep=2), serving=serving), cases, prompts
    )
    assert ep == dense


@pytest.mark.slow
def test_ep_parity_int8_experts_stream(devices8):
    """Packed int8 expert banks stream through the per-shard Pallas
    matvec (the PR-3 tp treatment applied to experts) and reproduce the
    dense-replicated packed engine bitwise."""
    from deepspeed_tpu.ops.pallas import quantized_matmul as qm
    from deepspeed_tpu.ops.quantizer import PackedWeight

    # lanes must tile (f % 128 == 0) for the kernel; capacity (= W here)
    # must fit the matvec row threshold
    model_kw = dict(hidden_size=256, intermediate_size=512)
    serving = {"max_slots": 2, "token_budget": 8, "max_tokens": 32}
    cases = [dict(max_new_tokens=4), dict(max_new_tokens=3),
             dict(max_new_tokens=5), dict(max_new_tokens=2)]
    prompts = _prompts(seed=5)

    qm.reset_streaming_trace_counts()
    eng_d = _engine(ep=1, model=tiny_mixtral(**model_kw), quantize_bits=8)
    dense = _replay(ServingEngine(engine=eng_d, serving=serving),
                    cases, prompts)
    assert qm.streaming_trace_counts()["expert_single"] > 0

    qm.reset_streaming_trace_counts()
    eng_e = _engine(ep=2, model=tiny_mixtral(**model_kw), quantize_bits=8)
    packed4 = [
        l for l in jax.tree_util.tree_leaves(
            eng_e.params, is_leaf=lambda a: isinstance(a, PackedWeight))
        if isinstance(l, PackedWeight) and len(l.shape) == 4
    ]
    assert packed4, "expert banks must pack"
    ep = _replay(ServingEngine(engine=eng_e, serving=serving),
                 cases, prompts)
    assert qm.streaming_trace_counts()["expert_sharded"] > 0
    assert ep == dense


@pytest.mark.slow
def test_serving_matches_lockstep_generate(devices8):
    """With the no-drop capacity rule (cap_factor·k >= E) per-token
    routing is batch-independent, so the MoE slot engine reproduces
    single-request lockstep generate token-for-token — the same oracle
    the dense serving tests pin."""
    eng = _engine(ep=2)
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 3, "token_budget": 8, "max_tokens": 64,
    })
    prompts = _prompts(seed=7)
    states = [srv.submit(Request(request_id=f"g{i}", prompt=p,
                                 max_new_tokens=n))
              for i, (p, n) in enumerate(zip(prompts, (6, 4, 8, 5)))]
    srv.run_until_idle()
    for st, p, n in zip(states, prompts, (6, 4, 8, 5)):
        want = eng.generate(p[None, :], max_new_tokens=n, temperature=0.0)
        np.testing.assert_array_equal(st.output(), want[0])


# ---------------------------------------------------------------------------
# satellites (light — these stay in tier-1)
# ---------------------------------------------------------------------------
def test_gating_valid_mask_null_expert():
    """Invalid rows occupy no capacity, shift no positions and carry
    zero weight — and real rows route identically whatever the
    occupancy mix (the zero-recompile/no-drift contract)."""
    from deepspeed_tpu.moe.sharded_moe import top_k_gating_indices

    logits = np.asarray(
        jax.random.normal(jax.random.PRNGKey(0), (8, 4)), np.float32
    )
    full = top_k_gating_indices(jnp.asarray(logits), 2, 8, None, False)
    valid = jnp.ones((8,), bool).at[3].set(False).at[6].set(False)
    masked = top_k_gating_indices(jnp.asarray(logits), 2, 8, None, False,
                                  valid=valid)
    tof, sv, sot, w, metrics = masked
    # invalid rows: zero combine weight
    assert float(jnp.abs(w[3]).sum()) == 0.0
    assert float(jnp.abs(w[6]).sum()) == 0.0
    # capacity accounting excludes them
    assert int(metrics["routed_tokens"]) == 6 * 2
    assert int(metrics["tokens_per_expert"].sum()) == 6 * 2
    assert float(metrics["drop_fraction"]) == 0.0
    # real rows keep their expert choice and weights bitwise
    full_w = np.asarray(full[3])
    for r in (0, 1, 2, 4, 5, 7):
        np.testing.assert_array_equal(np.asarray(w[r]), full_w[r])


def test_gating_eval_accepts_rng_none_bitwise():
    """ISSUE 14 satellite: gating at eval never consumes a key — with
    and without an rng the outputs are bitwise equal, so serving's
    deterministic per-request RNG discipline is untouched."""
    from deepspeed_tpu.moe.sharded_moe import top_k_gating

    logits = jax.random.normal(jax.random.PRNGKey(2), (16, 4))
    with_key = top_k_gating(logits, 2, 8, rng=jax.random.PRNGKey(3),
                            train=False, noise_std=0.1)
    without = top_k_gating(logits, 2, 8, rng=None, train=False,
                           noise_std=0.1)
    for a, b in zip(with_key[:2], without[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gating_eval_keyfree_certified_statically():
    """The R9(d) arm on the REAL PR-14 surface: eval gating claims
    key-free bitwiseness (the runtime twin above proves it bitwise);
    tracing it with a key handed in and linting under
    ``claims_keyfree=True`` certifies statically that NO key-consuming
    site exists on the path — and a gating variant that sneaks eval
    noise back in (split + sample) is flagged."""
    import jax.numpy as jnp

    from deepspeed_tpu.analysis import lint_jaxpr
    from deepspeed_tpu.moe.sharded_moe import top_k_gating

    logits = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    key = jax.random.PRNGKey(0)

    closed = jax.make_jaxpr(
        lambda lg, k: top_k_gating(lg, 2, 8, rng=k, train=False,
                                   noise_std=0.1)
    )(logits, key)
    findings = lint_jaxpr(closed, source="gating-eval",
                          claims_keyfree=True)
    assert findings == [], [f.format() for f in findings]

    def noisy_eval_gating(lg, k):
        k, sub = jax.random.split(k)
        noisy = lg + jax.random.normal(sub, lg.shape) * 0.1
        return top_k_gating(noisy, 2, 8, rng=None, train=False)

    closed = jax.make_jaxpr(noisy_eval_gating)(logits, key)
    findings = lint_jaxpr(closed, source="gating-eval-noisy",
                          claims_keyfree=True)
    assert any(f.rule == "R9" and "key-free" in f.message
               for f in findings), [f.format() for f in findings]


def test_eval_capacity_static_rule():
    from deepspeed_tpu.moe.sharded_moe import eval_capacity

    cfg = tiny_mixtral().config
    # max(cap_factor, 2.0) * k * W / E, floored at 4
    assert eval_capacity(cfg, 16) == 16  # 2.0 * 2 * 16 / 4
    assert eval_capacity(cfg, 1) == 4    # the floor
    # no-drop guarantee at this preset: capacity >= budget
    for w in (4, 8, 16, 64):
        assert eval_capacity(cfg, w) >= w


def test_metrics_on_moe_nan_hardened():
    m = ServingMetrics()
    m.on_moe([4, float("nan"), 3, 1], float("nan"), a2a_bytes=float("inf"))
    m.on_moe([1, 1, 1, 1], 0.25, a2a_bytes=1024)
    snap = m.snapshot()
    assert snap["moe_steps"] == 2
    assert snap["moe_dropped_fraction"] == 0.25
    assert snap["moe_a2a_bytes"] == 1024
    assert snap["moe_tokens_expert_1"] == 1  # the NaN became 0
    assert all(np.isfinite(v) for v in snap.values())
    assert "moe serving" in m.summary()
    assert m.moe_load_imbalance > 0


def test_serving_config_moe_a2a_validation():
    from deepspeed_tpu.config import DeepSpeedConfigError, ServingConfig

    ServingConfig(moe_a2a="chunked").validate()
    with pytest.raises(DeepSpeedConfigError):
        ServingConfig(moe_a2a="ring").validate()


def test_resolve_moe_a2a_form(devices8):
    from deepspeed_tpu.serving.engine import resolve_moe_a2a_form

    cfg = tiny_mixtral().config
    dense_topo = MeshTopology(devices=jax.devices()[:1])
    ep_topo = MeshTopology(dims=ParallelDims(ep=2),
                           devices=jax.devices()[:2])
    llama_cfg = type("C", (), {"is_moe": False})()
    assert resolve_moe_a2a_form("auto", llama_cfg, ep_topo, 8, 4) == "off"
    assert resolve_moe_a2a_form("chunked", cfg, dense_topo, 8, 4) == "stock"
    assert resolve_moe_a2a_form("chunked", cfg, ep_topo, 8, 4) == "chunked"
    # packed experts always take the stock exchange
    assert resolve_moe_a2a_form(
        "chunked", cfg, ep_topo, 8, 4, packed_experts=True
    ) == "stock"
    # auto: latency-bound small steps pick stock
    assert resolve_moe_a2a_form("auto", cfg, ep_topo, 8, 4) == "stock"
    # the slot grid must divide ep or the ring cannot run — the resolved
    # form must describe the exchange that actually executes (review
    # fix: a declared-chunked stream over an actually-stock program
    # would mis-price R8)
    assert resolve_moe_a2a_form(
        "chunked", cfg, ep_topo, 5, 4, max_slots=3
    ) == "stock"
    assert resolve_moe_a2a_form(
        "chunked", cfg, ep_topo, 8, 4, max_slots=3
    ) == "chunked"


def test_planner_axis_skipped_on_undividable_ep(devices8):
    """ep_size that does not divide the experts serves dense-replicated:
    the serving moe-a2a axis must collapse (identical duplicate plans
    otherwise — the PR-12 grad_wire-axis lesson)."""
    from deepspeed_tpu.autotuning.planner_search import PlannerSearch

    with open(os.path.join(REPO, "examples",
                           "ds_config_serving_moe.json")) as f:
        cfg = json.load(f)
    cfg["moe"]["ep_size"] = 3  # 4 experts % 3 != 0
    ps = PlannerSearch(tiny_mixtral(vocab_size=512), cfg,
                       token_budgets=(8,))
    labels = [c.label() for c in ps.candidates()]
    assert labels == ["serve-tb8"]
    # the gate reads the MODEL config (the source of truth), not the
    # config-side moe.num_experts — omitting it must not collapse the
    # axis (review fix)
    cfg["moe"]["ep_size"] = 2
    del cfg["moe"]["num_experts"]
    ps2 = PlannerSearch(tiny_mixtral(vocab_size=512), cfg,
                        token_budgets=(8,))
    assert sorted(c.label() for c in ps2.candidates()) == [
        "serve-tb8/a2achunk", "serve-tb8/a2astock",
    ]


def test_lint_serving_moe_example(devices8):
    """examples/ds_config_serving_moe.json lints CLEAN through
    lint_serving_config tracing the MoE slot step abstractly on the ep
    mesh (the chunked ring's perms pass R3; the moe_decode_a2a stream is
    declared for R8)."""
    from deepspeed_tpu.analysis import lint_config

    with open(os.path.join(REPO, "examples",
                           "ds_config_serving_moe.json")) as f:
        cfg = json.load(f)
    model = tiny_mixtral(vocab_size=512)
    report = lint_config(cfg, model=model)
    assert report.ok, report.format()


@pytest.mark.slow
def test_planner_serving_moe_a2a_axis(devices8):
    """The serving-side moe-a2a axis (stock vs chunked) enumerates on
    mixtral serving configs, statically only — no compile, and the
    PR-7 measurement refusal still stands for serving configs."""
    from deepspeed_tpu.autotuning.planner_search import PlannerSearch

    with open(os.path.join(REPO, "examples",
                           "ds_config_serving_moe.json")) as f:
        cfg = json.load(f)
    ps = PlannerSearch(tiny_mixtral(vocab_size=512), cfg,
                       token_budgets=(8, 16))
    res = ps.search()
    labels = [pc.cand.label() for pc in res.planned]
    assert sorted(labels) == sorted([
        "serve-tb8/a2astock", "serve-tb16/a2astock",
        "serve-tb8/a2achunk", "serve-tb16/a2achunk",
    ])
    assert len(res.survivors) == 4  # all traceable, none compiled
    with pytest.raises(NotImplementedError, match="static-only"):
        ps.tuner._tune_planner()


def test_moe_decode_stream_declared(devices8):
    """The serving engine declares the moe_decode_a2a analytic stream
    under ep > 1 (R8 prices it; the comms logger records it)."""
    srv = ServingEngine(engine=_engine(ep=2), serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 32,
    })
    streams = srv.analytic_streams()
    assert "moe_decode_a2a" in streams
    s = streams["moe_decode_a2a"]
    assert s["kind"] == "ici" and s["bytes_per_step"] > 0
    assert s["ep"] == 2 and s["form"] in ("stock", "chunked")
    # dense-replicated: no exchange on the wire
    srv1 = ServingEngine(engine=_engine(ep=1), serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 32,
    })
    assert "moe_decode_a2a" not in srv1.analytic_streams()


# ---------------------------------------------------------------------------
# the paged attention kernel through the engine (ISSUE 28)
# ---------------------------------------------------------------------------
def _mixed_replay(srv):
    """Chunked prefills beside decodes, a shared prefix that diverges
    inside a page (copy-on-write) and speculative verify windows, on one
    paged tiny-Mixtral engine. Returns every request's greedy tokens."""
    r = np.random.RandomState(11)
    # repetitive prompts so the n-gram drafts land verify windows; 20 and
    # 27 tokens prefill over 3-4 chunks of the 8-token budget
    base = np.tile(r.randint(0, 64, size=(4,)), 8)
    prompts = [base[:20], base[:27], r.randint(0, 64, size=(5,)), base[:20]]
    states = [srv.submit(Request(request_id=f"k{i}", prompt=prompts[i],
                                 max_new_tokens=n))
              for i, n in ((0, 7), (1, 5))]
    srv.step()
    srv.step()
    states.append(srv.submit(Request(request_id="k2", prompt=prompts[2],
                                     max_new_tokens=6)))
    srv.run_until_idle()
    # the same prompt again: its pages come from the prefix cache and its
    # first write lands inside a shared partial page
    states.append(srv.submit(Request(request_id="k3", prompt=prompts[3],
                                     max_new_tokens=7)))
    srv.run_until_idle()
    traces = srv.step_traces
    states.append(srv.submit(Request(request_id="k4", prompt=prompts[1],
                                     max_new_tokens=4)))
    srv.run_until_idle()
    assert srv.step_traces == traces == 1, "recompiled after warm-up"
    assert srv.metrics.cow_copies >= 1
    assert srv.metrics.spec_steps >= 1
    assert srv.metrics.prefill_chunks >= 6
    return [list(s.tokens) for s in states]


@pytest.mark.parametrize(
    "fallback", [None, "int8"], ids=["paged_kernel", "int8-falls-back"]
)
def test_paged_attention_kernel_through_engine(fallback):
    """Under the kernel attention impl the compiled paged step takes the
    Pallas paged attention and serves the dense path's greedy tokens; an
    int8 KV cache falls back to the dense lines and says why. The path is
    recorded on the engine and in the metrics snapshot."""
    from deepspeed_tpu.ops.attention import attention_impl

    serving = {"max_slots": 3, "token_budget": 8, "max_tokens": 48,
               "paged": True, "page_size": 8,
               "spec": {"enabled": True, "max_draft": 3}}
    kw = {"kv_cache_dtype": "int8"} if fallback == "int8" else {}
    dense_srv = ServingEngine(engine=_engine(**kw), serving=serving)
    want = _mixed_replay(dense_srv)
    assert dense_srv.attention_path == "dense"
    assert "not the kernel one" in dense_srv.attention_fallback[0]
    assert dense_srv.metrics.snapshot()["attention_paged_kernel"] == 0.0

    with attention_impl("flash"):  # the step is compiled when it is built
        srv = ServingEngine(engine=_engine(**kw), serving=serving)
    assert srv.step_traces == 1
    got = _mixed_replay(srv)
    assert got == want
    if fallback is None:
        assert srv.attention_path == "paged_kernel"
        assert srv.attention_fallback == ()
        assert srv.metrics.snapshot()["attention_paged_kernel"] == 1.0
    else:
        assert srv.attention_path == "dense"
        assert srv.attention_fallback == ("int8 KV cache",)
        assert srv.metrics.snapshot()["attention_paged_kernel"] == 0.0


def test_paged_attention_alibi_falls_back_to_dense():
    """BLOOM's ALiBi positions stay on the dense lines under the kernel
    impl, with the reason on the engine."""
    from deepspeed_tpu.models import bloom
    from deepspeed_tpu.ops.attention import attention_impl

    model = bloom("bloom-tiny", vocab_size=64, max_seq_len=64)
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=48, rng=jax.random.PRNGKey(2)
    )
    with attention_impl("flash"):
        srv = ServingEngine(engine=eng, serving={
            "max_slots": 2, "token_budget": 8, "max_tokens": 48,
            "paged": True, "page_size": 8,
        })
    st = srv.submit(Request(request_id="b0", prompt=np.arange(11) % 64,
                            max_new_tokens=3))
    srv.run_until_idle()
    assert len(st.tokens) == 3
    assert srv.attention_path == "dense"
    assert srv.attention_fallback == ("ALiBi positions",)
    assert srv.step_traces == 1


# ------------------------------------------------- the bank products' path
# One case a configuration the benchmark serves, at its ratio of expected
# rows an expert at a full step (budget x top-k / routed experts), every
# bank lane-wide so that nothing but the ratio, the mesh and the caller's
# stack decide (moe/sharded_moe.expert_bank_path).
def _shaped(name):
    from deepspeed_tpu.models import deepseek, ling, mellum

    wide = dict(hidden_size=128, intermediate_size=128)
    if name == "mixtral":   # 8 experts, top-2: 32 rows
        return tiny_mixtral(num_experts=8, moe_top_k=2, max_seq_len=512,
                            **wide)
    if name == "mellum":    # 64 of 64, top-8: 16 rows
        return mellum("mellum-tiny", num_experts=64, moe_top_k=8, **wide)
    if name == "deepseek":  # 16 held of 256, top-8: 4 rows
        return deepseek("deepseek-tiny", num_experts=16,
                        moe_routed_experts=256, moe_top_k=8, moe_groups=8,
                        moe_groups_kept=4, **wide)
    # 64 held of 512, top-8: 2 rows
    return ling("ling-tiny", layer_ids=[0, 1, 2, 5], num_experts=64,
                moe_routed_experts=512, moe_top_k=8, moe_groups=8,
                moe_groups_kept=4, num_heads=8, **wide)


def _lowered_text(srv):
    """The step's lowered text with its locations. A helper traced earlier in
    this process is cached with ITS caller's locations (tests/
    test_expert_bank.py's, where a worker ran that file first), so the
    caches go before the step is lowered."""
    jax.clear_caches()
    return srv.lower_step().as_text(debug_info=True)


def _served(name, **kw):
    eng = deepspeed_tpu.init_inference(
        _shaped(name), dtype=jnp.float32, max_tokens=256,
        rng=jax.random.PRNGKey(1), **kw)
    return ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 128, "max_tokens": 256,
        "paged": True, "page_size": 16})


@pytest.mark.parametrize("name, rows", [
    ("mixtral", 32), ("mellum", 16), ("deepseek", 4)])
def test_banks_of_a_layer_whose_experts_are_all_touched_take_the_einsum(
        name, rows):
    srv = _served(name)
    assert srv.expert_path == "einsum"
    assert srv.expert_path_reason.startswith(f"{rows} rows an expert")
    assert srv.describe()["expert_path"] == "einsum"
    assert srv.describe()["expert_path_reason"] == srv.expert_path_reason
    assert srv.metrics.snapshot()["expert_touched_kernel"] == 0.0
    assert "expert_bank" not in _lowered_text(srv)


def test_banks_of_a_ling_shaped_layer_take_the_touched_kernel():
    srv = _served("ling")
    assert (srv.expert_path, srv.expert_path_reason) == (
        "touched_kernel", None)
    assert srv.describe()["expert_path"] == "touched_kernel"
    assert srv.metrics.snapshot()["expert_touched_kernel"] == 1.0
    assert "expert_bank" in _lowered_text(srv)
    st = srv.submit(Request(request_id="a", prompt=np.arange(150) % 64,
                            max_new_tokens=3))
    srv.run_until_idle()
    assert srv.step_traces == 1 and len(st.tokens) == 3
    # the experts that got a row, as the device counted them: not all
    assert 0 < srv.metrics.moe_experts_touched


def test_banks_sharded_over_ep_take_the_einsum_and_serve_the_same(devices8):
    topo = MeshTopology(dims=ParallelDims(ep=2), devices=jax.devices()[:2])
    srv = _served("ling", topology=topo)
    assert srv.expert_path == "einsum"
    assert "sharded over the mesh (ep 2" in srv.expert_path_reason
    assert "expert_bank" not in _lowered_text(srv)
    whole = _served("ling")
    tokens = []
    for s in (srv, whole):
        st = s.submit(Request(request_id="a", prompt=np.arange(40) % 64,
                              max_new_tokens=4))
        s.run_until_idle()
        tokens.append(list(st.tokens))
    assert tokens[0] == tokens[1]


@pytest.mark.parametrize("case", ["packed", "no_stack", "narrow", "ling",
                                  "ling_full_width"])
def test_expert_bank_path_reads_the_static_shapes(case):
    """The rule alone, at the published shapes where it matters."""
    from deepspeed_tpu.models import ling
    from deepspeed_tpu.moe.sharded_moe import (TOUCHED_KERNEL_MAX_ROWS,
                                               expert_bank_path)
    from deepspeed_tpu.ops.quantizer import pack_quantize_blockwise

    assert 2 < TOUCHED_KERNEL_MAX_ROWS <= 4
    cfg = _shaped("ling").config
    bank = jnp.zeros((64, 128, 128), jnp.float32)
    if case == "packed":
        path, why = expert_bank_path(
            cfg, {"wi": pack_quantize_blockwise(bank)}, 128, True)
        assert path == "einsum" and "packed" in why
    elif case == "no_stack":
        path, why = expert_bank_path(cfg, {"wi": bank}, 128, False)
        assert path == "einsum" and "not the stack" in why
    elif case == "narrow":
        path, why = expert_bank_path(cfg, {"wi": bank[..., :64]}, 128, True)
        assert path == "einsum" and "128 lanes" in why
    elif case == "ling":
        assert expert_bank_path(cfg, {"wi": bank}, 128, True) == (
            "touched_kernel", None)
        # a budget under which a step would touch every expert: the einsum
        path, why = expert_bank_path(cfg, {"wi": bank}, 256, True)
        assert path == "einsum" and why.startswith("4 rows")
    else:
        full = ling("ling-3.0-flash", layer_ids=[0, *range(6, 18)],
                    num_experts=64, moe_routed_experts=512).config
        wi = jax.ShapeDtypeStruct((64, 2560, 768), jnp.bfloat16)
        assert expert_bank_path(full, {"wi": wi}, 128, True) == (
            "touched_kernel", None)
