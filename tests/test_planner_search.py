"""Planner-driven autotuning (ISSUE 7): enumerate → R6-prune → rank →
compile only a top-k, with the drift ledger keeping the cost model
honest.

Acceptance exercised here on the CPU mesh with tiny models (the
full-size 410M drift gate is ``tools/autoplan.py --check``, wired into
CI): the planner search compiles at most top-k candidates yet selects
the same winner as the exhaustive compile-and-measure ladder, statically
pruned rungs carry their reasons, larger micro-batches at a pruned
(stage, remat) rung are derived without re-tracing, and every measured
survivor banks a (predicted, measured) pair."""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import gpt2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model():
    return gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16, hidden_size=32,
                num_layers=2, num_heads=2)


def _topo():
    return MeshTopology(dims=ParallelDims(dp=8))


def _search(base, **kw):
    from deepspeed_tpu.autotuning import PlannerSearch

    return PlannerSearch(_model(), base, _topo(), **kw)


# ------------------------------------------------------------ enumeration
def test_candidate_space_enumeration():
    """The full space: zero ladder × remat × micro when the zero section
    is untuned; a pinned section collapses the zero axis; tp>1 adds the
    overlap on/off axis; serving configs swap to the token_budget axis."""
    from deepspeed_tpu.autotuning import PlannerSearch
    from deepspeed_tpu.autotuning.autotuner import REMAT_POLICIES, ZERO_LADDER

    base = {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "autotuning": {"max_train_micro_batch_size_per_gpu": 4}}
    cands = _search(base).candidates()
    labels = {c.label() for c in cands}
    # per-rung axis multipliers: stage-3 rungs carry the layer-prefetch
    # on/off axis AND both wire-codec axes (grad x param, 2 codecs each);
    # stage-1/2 rungs carry the grad-wire axis only (ISSUE 12)
    def units(stage):
        if stage == 3:
            return 2 * 2 * 2  # z3pf x grad_wire x param_wire
        if stage >= 1:
            return 2          # grad_wire
        return 1
    ladder_units = sum(units(z["stage"]) for z in ZERO_LADDER)
    assert len(cands) == ladder_units * len(REMAT_POLICIES) * 3
    assert "z0/none/mb1" in labels and "z3off/full/mb4/z3pf" in labels
    assert "z3/none/mb1/z3pf/gw-int8/pw-int8" in labels
    assert {c.z3_prefetch for c in cands if c.stage == 3} == {False, True}
    assert all(c.z3_prefetch is None for c in cands if c.stage != 3)
    assert {c.grad_wire for c in cands if c.stage >= 1} == {"fp32", "int8"}
    assert all(c.grad_wire is None for c in cands if c.stage == 0)
    assert all(c.param_wire is None for c in cands if c.stage != 3)
    # the wire axis collapses on request (heavier tests keep trace
    # counts flat with wire_codecs=("fp32",))
    collapsed = _search(base, wire_codecs=("fp32",)).candidates()
    n_stage3 = sum(1 for z in ZERO_LADDER if z["stage"] == 3)
    assert len(collapsed) == (
        (len(ZERO_LADDER) + n_stage3) * len(REMAT_POLICIES) * 3
    )

    pinned = dict(base, zero_optimization={"stage": 1})
    cands = _search(pinned).candidates()
    assert len(cands) == len(REMAT_POLICIES) * 3 * 2  # x grad_wire
    assert all(c.zero is None for c in cands)

    tp = dict(pinned, tensor_parallel={"tp_size": 2})
    cands = _search(tp, wire_codecs=("fp32",)).candidates()
    assert len(cands) == len(REMAT_POLICIES) * 3 * 2
    assert {c.tp_overlap for c in cands} == {False, True}

    # expert parallelism adds the decomposed-a2a on/off axis (ISSUE 10)
    moe = dict(pinned, moe={"enabled": True, "ep_size": 2,
                            "num_experts": 4})
    cands = _search(moe, wire_codecs=("fp32",)).candidates()
    assert len(cands) == len(REMAT_POLICIES) * 3 * 2
    assert {c.moe_a2a for c in cands} == {False, True}
    assert any("a2aov" in c.label() for c in cands)

    serving = dict(base, serving={"enabled": True})
    cands = _search(serving, token_budgets=(8, 32)).candidates()
    assert [c.token_budget for c in cands] == [8, 32]


def test_new_overlap_axes_reach_plans_and_configs(devices8):
    """The ISSUE-10 axes are real: the built candidate config carries the
    flags, the abstract trace prices both settings (R6/R8 run before any
    compile), and the a2a-on plan declares the overlapped moe_a2a stream
    while the off leg declares it serial."""
    from deepspeed_tpu.autotuning import PlannerSearch
    from deepspeed_tpu.models import mixtral

    model = mixtral("mixtral-tiny", vocab_size=64, max_seq_len=16,
                    num_experts=2)
    base = {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 1},
        "moe": {"enabled": True, "ep_size": 2, "num_experts": 2},
        "autotuning": {"max_train_micro_batch_size_per_gpu": 1,
                       "tune_zero": False},
    }
    search = PlannerSearch(model, base, None, top_k=1,
                           wire_codecs=("fp32",))
    cands = search.candidates()
    assert {(c.moe_a2a, c.z3_prefetch) for c in cands} == {
        (False, False), (False, True), (True, False), (True, True),
    }
    on = next(c for c in cands if c.moe_a2a and c.z3_prefetch)
    cfg = search._candidate_config(on)
    assert cfg["moe"]["overlap_a2a"]["enabled"]
    assert cfg["zero_optimization"]["stage3_layer_prefetch"]
    res = search.search()
    by_label = {p.cand.label(): p for p in res.planned}
    p_on = next(p for p in res.planned
                if p.cand.moe_a2a and p.cand.z3_prefetch)
    p_off = next(p for p in res.planned
                 if not p.cand.moe_a2a and not p.cand.z3_prefetch)
    assert p_on.plan is not None and p_off.plan is not None, by_label
    assert p_on.plan.streams["moe_a2a"]["overlapped"]
    assert p_on.plan.streams["zero3_prefetch"]["overlapped"]
    assert not p_off.plan.streams["moe_a2a"]["overlapped"]
    assert "zero3_prefetch" not in p_off.plan.streams


# --------------------------------------------------- prune + rank + explain
def test_static_prune_rank_and_explain(devices8):
    """A tight budget prunes fat rungs BEFORE any compile, every pruned
    rung names why it lost, survivors rank by predicted throughput, and
    the top-k respects k."""
    base = {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "autotuning": {"max_train_micro_batch_size_per_gpu": 8}}
    res = _search(base, top_k=2, hbm_budget_bytes=1_200_000,
                  wire_codecs=("fp32",)).search()
    assert res.pruned and res.survivors
    assert len(res.top_k) == 2
    for pc in res.pruned:
        assert "exceeds" in pc.reason or "GiB" in pc.reason, pc.reason
    tputs = [p.predicted_tput for p in res.survivors]
    assert tputs == sorted(tputs, reverse=True)
    text = res.explain()
    assert "pruned:" in text and "compile+measure" in text
    # machine-readable spelling carries the same evidence
    payload = res.to_dict()
    assert payload["n_traced"] == res.n_traced
    assert len(payload["pruned"]) == len(res.pruned)


def test_memoized_scaling_skips_retrace(devices8):
    """The _is_oom hardening: once a (stage, remat) rung is statically
    pruned at micro=m, larger micros derive their plan by scaling the
    traced one — never a second trace — and still land in pruned with
    the derivation recorded."""
    base = {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0},
            "autotuning": {"max_train_micro_batch_size_per_gpu": 8}}
    res = _search(base, hbm_budget_bytes=1_200_000,
                  wire_codecs=("fp32",)).search()
    by_group = {}
    for pc in res.planned:
        by_group.setdefault(pc.cand.group_key(), []).append(pc)
    derived = [p for p in res.planned if not p.traced]
    assert derived, "expected at least one derived (non-traced) candidate"
    for pcs in by_group.values():
        pruned_traced = [p.cand.micro for p in pcs if p.pruned and p.traced]
        if not pruned_traced:
            continue
        m = min(pruned_traced)
        for pc in pcs:
            if pc.cand.micro > m:
                assert not pc.traced, (
                    f"{pc.cand.label()} re-traced although mb={m} was "
                    "already statically pruned"
                )
                assert pc.derived_from_micro == m
                assert pc.pruned
    # a derived plan's batch-linear terms scaled, state did not
    d = derived[0]
    src = next(p for p in by_group[d.cand.group_key()]
               if p.cand.micro == d.derived_from_micro)
    f = d.cand.micro / src.cand.micro
    assert d.plan.act_peak_bytes == pytest.approx(
        src.plan.act_peak_bytes * f)
    assert d.plan.param_bytes == src.plan.param_bytes


# ------------------------------------------------------- tune() integration
def test_planner_tune_matches_exhaustive_winner(devices8, monkeypatch,
                                                tmp_path):
    """ISSUE 7 acceptance shape: with a deterministic measurement oracle
    the planner-driven tune (compile ≤ top-k) picks the same winner as
    the exhaustive compile-and-measure ladder."""
    from deepspeed_tpu.autotuning import Autotuner

    base = {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "autotuning": {"max_train_micro_batch_size_per_gpu": 4,
                       "trials": 1, "top_k": 3,
                       "drift_ledger": str(tmp_path / "drift.jsonl")},
    }
    # measured truth the roofline agrees with directionally: bigger micro
    # amortizes overhead, lighter remat wins when it fits
    weight = {"none": 4.0, "dots_flash": 3.0, "attn_mlp": 2.0, "full": 1.0}

    def fake_measure(self, mb, pol, blocks=(0, 0), cfg=None):
        return 100.0 * mb * weight[pol]

    monkeypatch.setattr(Autotuner, "_measure", fake_measure)
    monkeypatch.setattr(Autotuner, "_flash_tunable", lambda self: False)

    exhaustive = Autotuner(_model(), dict(base), topology=_topo(),
                           sample_batch_fn=lambda g: None)
    exhaustive.planner = False
    best_ex = exhaustive.tune()

    planned = Autotuner(_model(), dict(base), topology=_topo(),
                        sample_batch_fn=lambda g: None)
    planned.planner = True
    best_pl = planned.tune()
    assert planned.last_search is not None
    assert len(planned.last_search.top_k) <= 3
    assert (best_pl["micro_batch"], best_pl["remat_policy"]) == (
        best_ex["micro_batch"], best_ex["remat_policy"])
    # planner recs carry the prediction they were ranked on
    assert best_pl["predicted_step_s"] > 0


def test_planner_tune_end_to_end_real_measure(devices8, tmp_path):
    """Planner mode with real compiles on the CPU mesh: at most top-k
    engines are built, the winner is the max measured record, the patch
    round-trips into a runnable config, and the drift ledger banks one
    (predicted, measured) pair per measured survivor."""
    import deepspeed_tpu
    from deepspeed_tpu.autotuning import Autotuner, result_to_config_patch

    ledger_path = str(tmp_path / "drift.jsonl")
    r = np.random.RandomState(0)
    base = {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "autotuning": {
            "max_train_micro_batch_size_per_gpu": 2,
            "start_profile_step": 1, "end_profile_step": 2, "trials": 1,
            "planner": True, "top_k": 2, "drift_ledger": ledger_path,
        },
    }
    tuner = Autotuner(
        _model(), base, topology=_topo(),
        sample_batch_fn=lambda g: {
            "input_ids": r.randint(0, 64, size=(g, 16))
        },
    )
    best = tuner.tune()
    assert tuner.n_compiles <= 2  # the prune-before-compile contract
    assert tuner.last_search is not None
    top = max(tuner.results, key=lambda rec: rec["throughput"])
    assert best == top
    entries = [json.loads(line) for line in
               open(ledger_path).read().splitlines()]
    assert len(entries) == len(tuner.results)
    for e in entries:
        assert e["ratio"] and e["ratio"] > 0
        assert e["gen"] == "cpu"
        assert e["source"].startswith("autotune:")
    patch = result_to_config_patch(best)
    cfg = {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 0}}
    cfg.update(patch)
    engine, *_ = deepspeed_tpu.initialize(model=_model(), config=cfg,
                                          topology=_topo())
    B = cfg["train_micro_batch_size_per_gpu"] * 8
    loss = float(engine.train_batch(batch={
        "input_ids": r.randint(0, 64, size=(B, 16))
    }))
    assert np.isfinite(loss)
    engine.destroy()


def test_planner_tune_measures_full_candidate_config(devices8, monkeypatch,
                                                     tmp_path):
    """The tp-overlap axis survives measurement: each top-k candidate is
    measured with its EXACT planned config (not a (micro, remat)-only
    rebuild), the winning record carries the full tensor_parallel
    section, and the patch round-trips it without wiping tp_size."""
    from deepspeed_tpu.autotuning import Autotuner, result_to_config_patch

    base = {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "tensor_parallel": {"tp_size": 2},
        "autotuning": {"max_train_micro_batch_size_per_gpu": 1,
                       "trials": 1, "top_k": 4, "planner": True,
                       "drift_ledger": str(tmp_path / "drift.jsonl")},
    }
    tuner = Autotuner(_model(), base, topology=None,
                      sample_batch_fn=lambda g: None)
    measured_cfgs = []

    def fake_measure(mb, pol, blocks=(0, 0), cfg=None):
        assert cfg is not None, "planner must pass the candidate's config"
        measured_cfgs.append(cfg)
        overlap = (cfg.get("tensor_parallel", {})
                   .get("overlap_comm", {}).get("enabled", False))
        return 100.0 + (7.0 if overlap else 0.0)

    monkeypatch.setattr(tuner, "_measure", fake_measure)
    monkeypatch.setattr(tuner, "_flash_tunable", lambda: False)
    best = tuner.tune()
    overlaps = [
        c.get("tensor_parallel", {}).get("overlap_comm", {}).get("enabled",
                                                                 False)
        for c in measured_cfgs
    ]
    assert True in overlaps and False in overlaps, overlaps
    assert best["tensor_parallel"]["overlap_comm"]["enabled"] is True
    assert best["tensor_parallel"]["tp_size"] == 2
    patch = result_to_config_patch(best)
    assert patch["tensor_parallel"]["tp_size"] == 2
    assert patch["tensor_parallel"]["overlap_comm"]["enabled"] is True


def test_planner_tune_refuses_serving_configs(devices8):
    """Serving token_budget search is static-only: planner-mode tune
    must refuse loudly instead of timing a train step per budget."""
    import pytest as _pytest

    from deepspeed_tpu.autotuning import Autotuner

    tuner = Autotuner(
        _model(),
        {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "serving": {"enabled": True},
         "autotuning": {"planner": True}},
        sample_batch_fn=lambda g: None,
    )
    with _pytest.raises(NotImplementedError, match="static-only"):
        tuner.tune()


def test_planner_tune_all_pruned_raises(devices8):
    """Every candidate statically over budget → a loud explain-carrying
    error, not a silent fallback to compiling doomed rungs."""
    from deepspeed_tpu.autotuning import Autotuner

    tuner = Autotuner(
        _model(),
        {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "zero_optimization": {"stage": 0},
         "autotuning": {"max_train_micro_batch_size_per_gpu": 1,
                        "planner": True}},
        topology=_topo(), sample_batch_fn=lambda g: None,
    )
    tuner.hbm_gb = 1e-6  # ~1 KiB: nothing fits
    with pytest.raises(RuntimeError, match="statically over the HBM"):
        tuner.tune()
    assert tuner.n_compiles == 0


# ------------------------------------------------------------ drift ledger
def test_drift_ledger_roundtrip_check_and_bands(tmp_path):
    from deepspeed_tpu.analysis.cost import drift

    path = str(tmp_path / "ledger.jsonl")
    ledger = drift.DriftLedger(path)
    ledger.append({"source": "a", "gen": "v5e", "ratio": 1.1,
                   "bound": "compute", "ts": 1.0})
    ledger.append({"source": "b", "gen": "v5e", "ratio": 0.9,
                   "bound": "compute", "ts": 2.0})
    rows = ledger.load(gen="v5e")
    assert len(rows) == 2
    ok, problems = drift.check(rows)
    assert ok, problems
    s = drift.summarize(rows)
    assert s["n"] == 2 and s["median_ratio"] == 1.0

    # out-of-band entry: named violation
    bad = rows + [{"source": "c", "gen": "v5e", "ratio": 3.0,
                   "bound": "compute"}]
    ok, problems = drift.check(bad)
    assert not ok and any("outside" in p for p in problems)
    # spread violation even when each entry is in its (wide cpu) band
    spread = [{"source": "d", "gen": "cpu", "ratio": 0.2, "bound": "compute"},
              {"source": "e", "gen": "cpu", "ratio": 4.0, "bound": "compute"}]
    ok, problems = drift.check(spread)
    assert not ok and any("relative pricing" in p for p in problems)
    # peak band rides along when present
    ok, problems = drift.check([{"source": "f", "gen": "v5e", "ratio": 1.0,
                                 "bound": "compute", "peak_ratio": 1.3}])
    assert not ok and any("HBM peak" in p for p in problems)
    assert drift.band_for("cpu")[1] > drift.band_for("v5e")[1]


def test_drift_recalibration_suggestion():
    """Systematic drift (median outside RECAL_BAND, >= 3 samples) names
    the binding cost/hardware.py constant and the centering value."""
    from deepspeed_tpu.analysis.cost import drift
    from deepspeed_tpu.analysis.cost.hardware import gen_defaults

    rows = [{"source": f"s{i}", "gen": "v5e", "ratio": 0.5,
             "bound": "compute"} for i in range(3)]
    note = drift.recalibration_suggestion(rows)
    assert note and "peak_flops" in note and "v5e" in note
    expected = gen_defaults("v5e")["peak_flops"] * 0.5
    assert f"{expected:.3g}" in note
    # hbm-bound drift points at hbm_bw instead
    rows = [{"source": f"s{i}", "gen": "v5e", "ratio": 2.0, "bound": "hbm"}
            for i in range(3)]
    assert "hbm_bw" in drift.recalibration_suggestion(rows)
    # centered ledgers stay quiet
    rows = [{"source": f"s{i}", "gen": "v5e", "ratio": 1.0,
             "bound": "compute"} for i in range(5)]
    assert drift.recalibration_suggestion(rows) is None


def test_scale_plan_micro_batch_linear_terms():
    from deepspeed_tpu.analysis.cost import HardwareModel, Plan, \
        scale_plan_micro

    hw = HardwareModel(gen="test", peak_flops=1e9, hbm_bytes=1 << 30,
                       hbm_bw=1e9, ici_bw=1e9, host_bw=1e9)
    plan = Plan(source="mb1", hardware=hw, param_bytes=100.0,
                opt_bytes=50.0, act_peak_bytes=10.0, peak_hbm_bytes=160.0,
                flops=1e9, hbm_traffic_bytes=5e8,
                ici_bytes={"dp": 2e8}, ici_hops={"dp": 7})
    plan.compute_s, plan.hbm_s, plan.ici_s = 1.0, 0.5, 0.2
    plan.est_step_s = 1.0
    scaled = scale_plan_micro(plan, 4.0)
    assert scaled.act_peak_bytes == 40.0
    assert scaled.peak_hbm_bytes == 160.0 + 30.0  # + act * (f - 1)
    assert scaled.param_bytes == 100.0 and scaled.opt_bytes == 50.0
    assert scaled.flops == 4e9 and scaled.hbm_traffic_bytes == 2e9
    assert scaled.ici_bytes == {"dp": 8e8}
    assert scaled.est_step_s == pytest.approx(4.0)  # compute-bound x4
    # the original is untouched (dataclasses.replace semantics)
    assert plan.act_peak_bytes == 10.0 and plan.flops == 1e9


# ----------------------------------------------------------------- the CLI
@pytest.mark.shardlint
def test_autoplan_cli_static_search(devices8, tmp_path):
    """tools/autoplan.py static mode on a shipped config: exit 0, ranked
    table, --json payload; a tiny --hbm-gb prunes and --explain says
    why. One micro-batch size: the 32 candidates of the smallest space walk
    every stage (enumerate, prune, rank, trace, compile the top k) that the
    64 of two sizes do, in half the time."""
    import subprocess
    import sys

    cfg = os.path.join(REPO, "examples", "ds_config_zero3.json")
    out = tmp_path / "autoplan.json"
    cli = [sys.executable, os.path.join(REPO, "tools", "autoplan.py"), cfg,
           "--max-micro", "1"]
    # the two runs side by side, each a process of its own, their output in
    # files (a full pipe would stall the one not waited on)
    logs = [tmp_path / "ranked.log", tmp_path / "pruned.log"]
    procs = [subprocess.Popen(cli + flags, stdout=log.open("w"),
                              stderr=subprocess.STDOUT, cwd=REPO)
             for log, flags in zip(logs, (
                 ["--top-k", "2", "--json", str(out)],
                 ["--hbm-gb", "0.0001", "--explain"]))]
    codes = [p.wait(timeout=300) for p in procs]
    ranked, pruned = (log.read_text() for log in logs)
    assert codes[0] == 0, ranked
    assert "compile+measure" in ranked
    payload = json.loads(out.read_text())
    assert payload["survivors"] and len(payload["top_k"]) <= 2

    assert "pruned: " in pruned
