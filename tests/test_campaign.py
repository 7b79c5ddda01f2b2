"""Campaign machinery (ISSUE 16): device-kind gen detection, the
per-(gen, topology, model-class) knob-default table, ``"auto"``
resolution with the parity/staleness gates, drift-tag separation, and
the end-to-end CPU campaign with its bitwise closing oracle."""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as comm
from deepspeed_tpu.analysis.cost import drift
from deepspeed_tpu.analysis.cost import hardware as hw
from deepspeed_tpu.config import (
    AUTO,
    DeepSpeedConfig,
    _jax_major_minor,
    resolve_auto_knobs,
)


def tiny_llama(num_layers=2):
    from deepspeed_tpu.models import llama

    return llama(
        "llama-tiny", vocab_size=128, max_seq_len=32, hidden_size=64,
        num_layers=num_layers, num_heads=4, num_kv_heads=4, head_dim=16,
        intermediate_size=128,
    )


def table_row(knobs, gen="cpu", topo="dp8", mclass="unknown",
              jax_mm=None, evidence=None):
    """A well-formed table row with fresh evidence for every knob unless
    overridden."""
    ev = {path: {"predicted_step_s": 1.0, "measured_step_s": 1.0,
                 "parity": "test"}
          for path in knobs}
    ev.update(evidence or {})
    return {
        "gen": gen, "topology": topo, "model_class": mclass,
        "knobs": dict(knobs), "evidence": ev,
        "jax": jax_mm if jax_mm is not None else _jax_major_minor(),
        "winner": "test", "created": 0.0,
    }


def base_cfg_dict(**over):
    d = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
    }
    d.update(over)
    return d


# ------------------------------------------------------- gen detection
@pytest.mark.parametrize("kind,gen", [
    ("TPU v4", "v4"),
    ("TPU v5e", "v5e"),
    ("TPU v5 lite", "v5e"),
    ("TPU v5litepod-16", "v5e"),
    ("TPU v5p", "v5p"),
    ("TPU v5", "v5p"),
    ("TPU v6e", "v6e"),
    ("TPU v6 lite", "v6e"),
])
def test_gen_from_device_kind(kind, gen):
    assert hw.gen_from_device_kind(kind) == gen


@pytest.mark.parametrize("kind", [None, "", "TPU v3", "Interpreter",
                                  "future-chip-x9"])
def test_gen_from_device_kind_unknown(kind):
    assert hw.gen_from_device_kind(kind) is None


def test_detect_gen_env_pin(monkeypatch):
    monkeypatch.setenv("DSTPU_TPU_GEN", "v6e")
    assert hw.detect_gen() == "v6e"


def test_detect_gen_cpu_backend(monkeypatch):
    monkeypatch.delenv("DSTPU_TPU_GEN", raising=False)
    assert hw.detect_gen() == "cpu"  # the test mesh is the CPU backend


def test_detect_gen_mocked_tpu_kind(monkeypatch):
    import jax

    class FakeDev:
        device_kind = "TPU v5p"

    monkeypatch.delenv("DSTPU_TPU_GEN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [FakeDev()])
    assert hw.detect_gen() == "v5p"
    assert hw.HardwareModel.detect().gen == "v5p"


def test_detect_gen_unknown_kind_is_an_error(monkeypatch):
    import jax

    class FakeDev:
        device_kind = "TPU v99 prototype"

    monkeypatch.delenv("DSTPU_TPU_GEN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [FakeDev()])
    with pytest.raises(ValueError, match="TPU v99 prototype"):
        hw.detect_gen()
    with pytest.raises(ValueError, match="not in the peak table"):
        hw.HardwareModel.detect()


def test_unknown_generation_pin_is_an_error(monkeypatch):
    monkeypatch.setenv("DSTPU_TPU_GEN", "v7x")
    with pytest.raises(ValueError, match="v7x"):
        hw.HardwareModel.detect()
    with pytest.raises(ValueError, match="no peak-table row"):
        hw.gen_defaults("v5")


# --------------------------------------------------------- table lookup
def test_lookup_hit():
    row = table_row({"tensor_parallel.overlap_comm": True})
    table = {"version": 1, "entries": [row]}
    got, prov = hw.lookup_knob_row(table, "cpu", "dp8", "unknown")
    assert got is row
    assert prov == "table:cpu/dp8/unknown"


def test_lookup_gen_fallback_v6e_to_v5e():
    row = table_row({"zero_optimization.stage3_layer_prefetch": True},
                    gen="v5e")
    table = {"version": 1, "entries": [row]}
    got, prov = hw.lookup_knob_row(table, "v6e", "dp8", "unknown")
    assert got is row
    assert prov == "table:v5e/dp8/unknown"


def test_lookup_miss_and_cpu_never_borrows_tpu_rows():
    row = table_row({"serving.paged": True}, gen="v5e")
    table = {"version": 1, "entries": [row]}
    assert hw.lookup_knob_row(table, "v4", "other-topo", "unknown") == \
        (None, "miss")
    # cpu has an empty fallback chain: plumbing evidence only
    assert hw.lookup_knob_row(table, "cpu", "dp8", "unknown") == \
        (None, "miss")


def test_load_knob_table_corrupt_is_empty(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    assert hw.load_knob_table(str(p)) == {"version": 1, "entries": []}
    assert hw.load_knob_table(str(tmp_path / "absent.json")) == \
        {"version": 1, "entries": []}


def test_topology_key_orders_axes():
    class Topo:
        sizes = {"tp": 2, "dp": 4, "ep": 1}
        world_size = 8

    assert hw.topology_key(Topo()) == "dp4xtp2"
    assert hw.topology_key(None).startswith("dp")


# ----------------------------------------------------------- resolution
def test_resolve_hit_flips_knob_on():
    row = table_row({"tensor_parallel.overlap_comm": True})
    table = {"version": 1, "entries": [row]}
    cfg = DeepSpeedConfig(base_cfg_dict(
        tensor_parallel={"tp_size": 2, "overlap_comm": AUTO}))
    assert cfg.tensor_parallel.overlap_comm.enabled == AUTO
    report = resolve_auto_knobs(cfg, table=table)
    assert cfg.tensor_parallel.overlap_comm.enabled is True
    assert report["tensor_parallel.overlap_comm"] == {
        "value": True, "source": "table:cpu/dp8/unknown"}


def test_resolve_miss_is_conservative_off():
    cfg = DeepSpeedConfig(base_cfg_dict(
        tensor_parallel={"tp_size": 2, "overlap_comm": AUTO}))
    report = resolve_auto_knobs(cfg, table={"version": 1, "entries": []})
    assert cfg.tensor_parallel.overlap_comm.enabled is False
    assert report["tensor_parallel.overlap_comm"]["source"] == \
        "off-default:miss"


def test_resolve_inapplicable_never_consults_table():
    # tp=1: the knob cannot apply no matter what the table says
    row = table_row({"tensor_parallel.overlap_comm": True})
    cfg = DeepSpeedConfig(base_cfg_dict(
        tensor_parallel={"tp_size": 1, "overlap_comm": AUTO}))
    report = resolve_auto_knobs(cfg, table={"version": 1, "entries": [row]})
    assert cfg.tensor_parallel.overlap_comm.enabled is False
    assert report["tensor_parallel.overlap_comm"]["source"] == "inapplicable"


def test_resolve_stale_jax_invalidates():
    row = table_row({"zero_optimization.stage3_layer_prefetch": True},
                    jax_mm="0.1")
    cfg = DeepSpeedConfig(base_cfg_dict(
        zero_optimization={"stage": 3, "stage3_layer_prefetch": AUTO}))
    report = resolve_auto_knobs(cfg, table={"version": 1, "entries": [row]})
    assert cfg.zero_config.stage3_layer_prefetch is False
    assert report["zero_optimization.stage3_layer_prefetch"]["source"] == \
        "off-default:stale-jax:table:cpu/dp8/unknown"


def test_resolve_stale_band_invalidates():
    # evidence ratio 1/100 is outside even the forgiving cpu band —
    # the row is invalidated, the conservative off default resolves
    path = "zero_optimization.stage3_layer_prefetch"
    row = table_row({path: True}, evidence={
        path: {"predicted_step_s": 1.0, "measured_step_s": 100.0}})
    cfg = DeepSpeedConfig(base_cfg_dict(
        zero_optimization={"stage": 3, "stage3_layer_prefetch": AUTO}))
    report = resolve_auto_knobs(cfg, table={"version": 1, "entries": [row]})
    assert cfg.zero_config.stage3_layer_prefetch is False
    assert report[path]["source"] == \
        "off-default:stale-band:table:cpu/dp8/unknown"


def test_resolve_explicit_values_untouched():
    row = table_row({"tensor_parallel.overlap_comm": True,
                     "serving.paged": True})
    cfg = DeepSpeedConfig(base_cfg_dict(
        tensor_parallel={"tp_size": 2, "overlap_comm": False}))
    report = resolve_auto_knobs(cfg, table={"version": 1, "entries": [row]})
    assert cfg.tensor_parallel.overlap_comm.enabled is False
    assert "tensor_parallel.overlap_comm" not in report  # explicit wins
    assert cfg.serving.paged is False


def test_resolve_wire_codec_from_table():
    row = table_row({"zero_optimization.param_wire": "int8"})
    cfg = DeepSpeedConfig(base_cfg_dict(
        zero_optimization={"stage": 3, "param_wire": AUTO}))
    resolve_auto_knobs(cfg, table={"version": 1, "entries": [row]})
    assert cfg.zero_config.param_wire == "int8"


def test_resolve_wire_codec_miss_keeps_legacy_auto():
    cfg = DeepSpeedConfig(base_cfg_dict(
        zero_optimization={"stage": 3, "param_wire": AUTO}))
    report = resolve_auto_knobs(cfg, table={"version": 1, "entries": []})
    assert cfg.zero_config.param_wire == AUTO  # downstream resolution owns it
    assert report["zero_optimization.param_wire"]["source"] == "legacy-auto"


# --------------------------------------- "auto" through candidate patches
def test_auto_survives_planner_candidate_patches():
    """A base config spelling knobs "auto" must round-trip through every
    planner candidate patch: the candidate's own axes overwrite their
    knobs, every OTHER "auto" survives, and the patched dict still
    validates as a DeepSpeedConfig."""
    from deepspeed_tpu.autotuning import PlannerSearch

    model = tiny_llama()
    base = base_cfg_dict(
        tensor_parallel={"tp_size": 2, "overlap_comm": AUTO},
        zero_optimization={"stage": 3, "offload_double_buffer": AUTO,
                           "stage3_layer_prefetch": AUTO},
        autotuning={"max_train_micro_batch_size_per_gpu": 1},
    )
    search = PlannerSearch(model, base, remat_policies=("none",))
    cands = search.candidates()
    assert len(cands) >= 3
    patched = 0
    for cand in cands:
        cfg_dict = search._candidate_config(cand)
        ds = DeepSpeedConfig(cfg_dict)  # "auto" spellings still validate
        # offload_double_buffer is on no candidate axis: always survives
        assert ds.zero_config.offload_double_buffer == AUTO
        if cand.tp_overlap is not None:
            assert ds.tensor_parallel.overlap_comm.enabled is bool(
                cand.tp_overlap)
            patched += 1
    assert patched > 0


# --------------------------------------------------- drift tag separation
def _pair(ratio, tag=None, source="x"):
    e = {"source": source, "gen": "cpu", "predicted_step_s": ratio,
         "measured_step_s": 1.0, "ratio": ratio, "bound": "flops"}
    if tag:
        e["tag"] = tag
    return e


def test_entry_tag_and_by_tag():
    entries = [_pair(1.0), _pair(1.1, tag="campaign"), _pair(0.9)]
    assert drift.entry_tag(entries[0]) == "adhoc"
    assert drift.entry_tag(entries[1]) == "campaign"
    groups = drift.by_tag(entries)
    assert [len(groups["adhoc"]), len(groups["campaign"])] == [2, 1]


def test_check_spread_judged_per_tag():
    # ad-hoc pairs tight, campaign pairs deliberately heterogeneous
    # (>3x apart but inside the cpu band): only the campaign group may
    # flag spread, and it must say which group drifted
    entries = [_pair(1.0), _pair(1.1),
               _pair(1.0, tag="campaign"), _pair(10.0, tag="campaign")]
    ok, problems = drift.check(entries)
    assert not ok
    assert any("[campaign]" in p for p in problems)
    assert not any("[adhoc]" in p for p in problems)
    # pooled the other way: tight campaign pairs never pay for ad-hoc
    ok2, problems2 = drift.check([_pair(1.0), _pair(10.0),
                                  _pair(1.0, tag="campaign"),
                                  _pair(1.1, tag="campaign")])
    assert any("[adhoc]" in p for p in problems2)
    assert not any("[campaign]" in p for p in problems2)


def test_ledger_load_tag_filter(tmp_path):
    ledger = drift.DriftLedger(str(tmp_path / "d.jsonl"))
    ledger.append(_pair(1.0, source="a"))
    ledger.append(_pair(1.0, tag="campaign", source="b"))
    ledger.append(_pair(1.0, tag="campaign", source="c"))
    assert len(ledger.load()) == 3
    tagged = ledger.load(tag="campaign")
    assert [e["source"] for e in tagged] == ["b", "c"]
    assert [e["source"] for e in ledger.load(tag="adhoc")] == ["a"]


# ------------------------------------------------ bitwise closing oracle
def _one_loss(model, cfg_dict, data):
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg_dict)
    try:
        return float(engine.train_batch(batch=data))
    finally:
        engine.destroy()


def test_resolved_on_knob_bitwise_equals_explicit(tmp_path, monkeypatch):
    """A knob flipped on by table resolution trains bitwise-identically
    to the same knob spelled explicitly on — resolution changes where
    the decision comes from, never what program runs."""
    model = tiny_llama()
    path = "zero_optimization.stage3_layer_prefetch"
    row = table_row({path: True}, topo="dp8",
                    mclass=hw.model_class(model.config))
    tpath = tmp_path / "knob_defaults.json"
    tpath.write_text(json.dumps({"version": 1, "entries": [row]}))
    monkeypatch.setenv(hw.KNOB_TABLE_ENV, str(tpath))
    monkeypatch.setenv("DSTPU_TPU_GEN", "cpu")

    data = {"input_ids": np.random.RandomState(0).randint(
        0, 128, size=(8, 32))}

    def cfg(prefetch):
        return base_cfg_dict(zero_optimization={
            "stage": 3, "stage3_layer_prefetch": prefetch})

    loss_auto = _one_loss(model, cfg(AUTO), data)
    loss_explicit = _one_loss(model, cfg(True), data)
    loss_off = _one_loss(model, cfg(False), data)
    assert loss_auto == loss_explicit  # bitwise: the same program ran
    assert loss_off == pytest.approx(loss_auto)  # prefetch is layout-only


def test_engine_resolution_report_names_the_table(tmp_path, monkeypatch):
    model = tiny_llama()
    path = "zero_optimization.stage3_layer_prefetch"
    row = table_row({path: True}, topo="dp8",
                    mclass=hw.model_class(model.config))
    tpath = tmp_path / "knob_defaults.json"
    tpath.write_text(json.dumps({"version": 1, "entries": [row]}))
    monkeypatch.setenv(hw.KNOB_TABLE_ENV, str(tpath))
    monkeypatch.setenv("DSTPU_TPU_GEN", "cpu")
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_cfg_dict(zero_optimization={
            "stage": 3, "stage3_layer_prefetch": AUTO}))
    try:
        rep = engine.config.auto_resolution
        assert rep[path]["value"] is True
        assert rep[path]["source"].startswith("table:cpu/")
    finally:
        engine.destroy()


# ----------------------------------------------------- e2e CPU campaign
@pytest.mark.slow
def test_campaign_end_to_end_cpu(tmp_path, monkeypatch):
    """The whole chain in-process on the tiny model: enumerate ≥ 3 knob
    axes, compile ≤ top-k, bank campaign-tagged pairs, emit a row, and
    re-resolve a fresh all-"auto" config onto the winner."""
    from deepspeed_tpu.autotuning import (
        emit_table, run_campaign, verify_roundtrip,
    )
    from deepspeed_tpu.autotuning.campaign import candidate_knobs

    monkeypatch.setenv("DSTPU_TPU_GEN", "cpu")
    model = tiny_llama()
    rng = np.random.RandomState(0)

    def sample_batch(global_batch):
        return {"input_ids": rng.randint(0, 128, size=(global_batch, 32))}

    base = base_cfg_dict(
        zero_optimization={"stage": 3},
        autotuning={"max_train_micro_batch_size_per_gpu": 1, "top_k": 2,
                    "trials": 1, "start_profile_step": 1,
                    "end_profile_step": 2},
    )
    ledger_path = str(tmp_path / "drift.jsonl")
    out = run_campaign(model, base, sample_batch_fn=sample_batch,
                       top_k=2, drift_ledger_path=ledger_path)
    result = out["search"]
    axes = set()
    for pc in result.planned:
        axes.update(candidate_knobs(pc.cand))
    assert len(axes) >= 3, axes
    assert out["banked"] >= 1
    tagged = drift.DriftLedger(ledger_path).load(tag="campaign")
    assert len(tagged) == out["banked"]
    assert all(e["source"].startswith("campaign:") for e in tagged)

    row = out["row"]
    assert row is not None and row["gen"] == "cpu"
    tpath = str(tmp_path / "table.json")
    emit_table([row], tpath)
    rt = verify_roundtrip(base, tpath, model=model)
    for path, want in row["knobs"].items():
        if isinstance(want, bool):
            assert rt["resolved"][path] is want, (path, rt["resolved"])
