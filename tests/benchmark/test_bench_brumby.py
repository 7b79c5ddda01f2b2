"""The cell ``brumby14b-reason16`` on the CPU: its configuration file holds
the catalog row's numbers, the program builds the model the file describes,
its manifest entries are found BY NAME and lie after the accepted ones (never
"the last": the next PR's append must not redden this file), its rehearsal
runs end to end with no failed request, no request of its mix can be evicted
or cut, its kernel's cost grows with the work, and its readers say nothing on
a trace without their calls."""

import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import flops, loadgen, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG, MIX = "brumby14b-reason16", "brumby-14b", "reason-16-brumby"
READERS = ("retention_ms_per_step", "retention_roofline_pct")
# the cells and the configurations the benchmark had before this one
ACCEPTED_CELLS = (
    "bloom560m-pretrain-2k", "mixtral8x7b-chat", "bloom1b7-zero3-dp4",
    "mixtral8x7b-longdoc", "mellum2-12b-mixedlen", "deepseekv32-longctx",
    "glm47flash-pretrain-4k", "minicpm-sala-longctx128k",
    "ling3flash-reason16")
ACCEPTED_CONFIGS = (
    "bloom-560m", "mixtral-8x7b", "bloom-1b7", "mellum2-12b-a2.5b",
    "deepseek-v3.2", "glm-4.7-flash", "minicpm-sala", "ling-3.0-flash")
# the catalog row's ``config`` (model-configs guide, Brumby-14B-Base)
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("benchmarks", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def mix():
    return load("benchmarks", "traffic", MIX + ".json")


@pytest.fixture(scope="module")
def fam(cfg):
    return reference.family(cfg["family"])


def test_the_configuration_file_holds_the_catalog_rows_numbers(cfg, fam):
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():  # every key but the depth
        if key == "num_hidden_layers":
            assert (cfg["published"][key], cfg[key]) == (value, 8)
        else:
            assert cfg[key] == value, key
    assert cfg["layer_ids"] == list(range(8))  # period 1: the floor is 4
    assert cfg["source"] == ("https://huggingface.co/manifestai/"
                             "Brumby-14B-Base/blob/main/config.json")
    assert set(cfg["assumed"]) >= {
        "degree", "gate", "normaliser", "normaliser_eps", "scale", "qk_norm",
        "output", "state_dtype", "initializer_range", "state_alone", "draws"}
    assert (cfg["assumed"]["degree"], cfg["assumed"]["normaliser_eps"]) == (
        2, 1e-6)
    for part in ("One pipeline stage of five", "layers whole",
                 "the vocabulary whole", "NO layer keeps a page",
                 "16 slots = 4.40 GB", "4,198,652,928"):
        assert part in cfg["deployment"], part
    eng = cfg["engine"]
    assert eng["entry"] == "init_serving"
    assert eng["model"] == dict(factory="deepspeed_tpu.models:brumby",
                                size="brumby-14b",
                                overrides=dict(layer_ids=list(range(8))))
    assert eng["init_inference"] == dict(
        dtype="bfloat16", replace_with_kernel_inject=True)
    s = fam.shape_of(cfg)
    assert isinstance(s, flops.Shape)
    assert (s.d, s.layers, s.heads, s.kv_heads, s.hd, s.ffn, s.vocab,
            s.experts, s.top_k, s.gated, s.tied, s.expanded) == (
                5120, 8, 40, 8, 128, 17408, 151936, 0, 0, True, False, 8256)
    # 8 x (62.9 M of q, k, v, o + 0.04 M gate + 267.4 M MLP) + 2 x 777.9 M
    assert round(flops.stored_params(s) / 1e9, 2) == 4.20


def test_the_program_builds_the_model_the_file_describes(cfg, fam):
    """The factory with the file's overrides gives the sizes ``run.py``'s
    ``check_shape`` compares, the model's own count is the file's, and what
    a sequence keeps is the file's bytes."""
    from deepspeed_tpu.models import brumby
    from deepspeed_tpu.models.mixers import slot_leaves

    eng = cfg["engine"]["model"]
    c = brumby(eng["size"], **eng["overrides"]).config
    s = fam.shape_of(cfg)
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd, c.ffn,
            c.vocab_size, c.num_experts, bool(c.tie_embeddings)) == (
                s.d, s.layers, s.heads, s.kv_heads, s.hd, s.ffn, s.vocab,
                s.experts, s.tied)
    assert c.mixer_types == ("retention",) * 8 and c.paged_layers == 0
    assert (c.rope_theta, c.norm_eps, c.retention_eps) == (
        s.rope_theta, s.eps, s.ret_eps)
    assert c.num_params() == 4_198_652_928
    slots = cfg["engine"]["serving"]["max_slots"]
    leaves = slot_leaves(c, slots, None)
    assert set(leaves) == {"state", "norm"}
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in leaves.values())
    # 8 layers x 8 kv heads x (128 x 8,320 + 8,320) x 4 B a sequence; the
    # packed square keeps at least the symmetric square's numbers
    assert held == slots * 8 * 8 * 129 * 8320 * 4
    assert round(held / 1e9, 2) == 4.40
    assert 8320 >= s.expanded and 8320 < 128 * 128


def test_the_manifest_entries_are_found_by_name_after_the_accepted(cfg):
    manifest = load("BENCHMARK.json")
    names = [c["name"] for c in manifest["configs"]]
    assert tuple(names[:len(ACCEPTED_CONFIGS)]) == ACCEPTED_CONFIGS
    assert names.index(CONFIG) >= len(ACCEPTED_CONFIGS)
    entry = manifest["configs"][names.index(CONFIG)]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert os.path.isfile(os.path.join(ROOT, entry["file"]))
    assert len(entry["why"]) <= 200
    cells = [w["name"] for w in manifest["workloads"]]
    assert tuple(cells[:len(ACCEPTED_CELLS)]) == ACCEPTED_CELLS
    assert cells.index(CELL) >= len(ACCEPTED_CELLS)
    cell = manifest["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    for part in ("traffic/" + MIX + ".json",
                 "families/" + cfg["family"] + ".py"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", part))
    metric_names = [m["name"] for m in manifest["per_layer"]]
    metrics = dict(zip(metric_names, manifest["per_layer"]))
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
        assert metrics[name]["source"] == "device_trace"
        assert metrics[name]["layer"] == metrics[READERS[0]]["layer"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
        # after the metric the last accepted cell brought
        assert metric_names.index(name) > metric_names.index(
            "experts_touched_pct")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def listed_after_lings(workloads):
        return workloads.index(CELL) > workloads.index("ling3flash-reason16")

    assert listed_after_lings(e2e["serve_tokens_per_s"]["workloads"])
    tput = [m for m in manifest["per_layer"] if m["name"].endswith(".tput")]
    assert len(tput) == 7
    for m in tput:
        assert listed_after_lings(m["workloads"]), m["name"]
    # a dense model: no expert metric lists the cell
    assert CELL not in metrics["expert_ms_per_step"]["workloads"]
    assert len(json.dumps(manifest)) < 64 * 1024


def test_rehearsal_passes_with_no_failed_request():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "4800000011", "--seconds", "3",
         "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["workload"] == CELL
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["metric_names"] == ["serve_tokens_per_s", "setup_s"]
    assert "compilations inside the window: 0" in p.stdout
    assert "share rule: 16 within 0.0005" in p.stdout
    assert "exact rule: at least 100.0% = 32" in p.stdout


def test_no_request_of_the_mix_can_be_evicted_or_cut(cfg, mix):
    srv = cfg["engine"]["serving"]
    # the LENGTHS of reason-16: the same two state architectures' traffic
    lings = load("benchmarks", "traffic", "reason-16.json")
    for key in ("kind", "clients", "replay_requests", "schedule_seed",
                "prompt", "answer"):
        assert mix[key] == lings[key], key
    assert (mix["kind"], mix["clients"], mix["replay_requests"],
            mix["schedule_seed"]) == ("closed_loop", 16, 256, 4301)
    assert (srv["max_slots"], srv["token_budget"]) == (16, 128)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest <= srv["max_tokens"] == 18432
    assert srv["max_tokens"] % srv["token_budget"] == 0
    assert mix["clients"] == srv["max_slots"]  # callers = slots: no queue
    # no page to run dry: admission is by slot, and the arena asks for none
    assert srv["num_pages"] == 0 and "page_size" not in srv
    assert srv["prefix_cache"] is False
    cc = mix["correctness"]
    assert (cc["prompts"], cc["new_tokens"]) == ([97, 3203], 24)
    for n in (*cc["prompts"], *cc["precision"]["prompts"]):
        assert n % 16 and n % 64 and n % 128
    assert len(cc["precision"]["prompts"]) == srv["max_slots"]
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 256 and pairs.sum(1).max() <= srv["max_tokens"]
    # the worst drain: at the window's close the 16 callers hold at most the
    # 16 longest requests of the set, whole. By tokens at the slowest rate a
    # sound run has shown, and by steps (a decoding slot takes one row a
    # step, so 2,048 answer tokens are 2,048 steps whatever the rate) at the
    # slowest step: both inside the grace
    worst = np.sort(pairs.sum(1))[-16:].sum()
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    steps = (np.sort(pairs[:, 0])[-16:].sum() / srv["token_budget"]
             + mix["answer"]["max"])
    assert steps * mix["drain_step_ms"] / 1e3 < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]
    # the traffic file names what the chip's sample refuses and misses
    for fault in fam_faults():
        assert fault in cc["why"] + cc["precision"]["why"], fault


def fam_faults():
    return reference.family("brumby").FAULTS


def test_the_kernels_cost_grows_with_the_work(cfg, fam):
    s = fam.shape_of(cfg)
    D = 128 * 129 // 2
    # one decode row in each of 2 live slots: 40 read-outs and 8 updates
    # over D x 129, the row's own pair a head; 2 x 8 states and normalisers
    # read and written, float32; 2 rows of q, o (40 heads), k, v (8), bf16,
    # and 8 log-gates
    f, b = fam.retention_cost(s, rows=2, state_slots=2)
    assert f == 2 * (2 * 48 * D * 129 + 4 * 40 * 128)
    assert b == 2 * 2 * 8 * D * 129 * 4 + 2 * ((80 + 16) * 128 * 2 + 8 * 4)
    f2, b2 = fam.retention_cost(s, rows=128, state_slots=2)
    f3, b3 = fam.retention_cost(s, rows=128, state_slots=16)
    assert f2 > f and b2 > b and f3 == f2 and b3 > b2
    # 16 decoding slots are memory-bound, a 100-row chunk beside them is
    # near the compute ridge (the issue's arithmetic)
    peak = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
    assert flops.roofline_seconds(*fam.retention_cost(s, 16, 16), peak)[1] == (
        "memory")
    t_c = fam.retention_cost(s, 116, 16)[0] / 197e12
    t_m = fam.retention_cost(s, 116, 16)[1] / 819e9
    assert 0.02 < t_c / t_m < 1.0
    # the whole model's count is the recurrence's, whatever the context
    assert s.attention_flops_per_token(4096) == 8 * 2 * 48 * D * 129
    assert s.attention_flops_per_token(64) == s.attention_flops_per_token(4096)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_says_nothing_on_a_trace_without_its_calls(name, cfg, fam,
                                                            monkeypatch):
    """On the parent's program (no such call, no such counter) a new reader
    returns None, not 0, and does not raise."""
    from benchmarks import kinds_trace

    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    reduced = SimpleNamespace(op_seconds=lambda rx: 0.0,
                              spans={"bench/engine.step": [1, 2, 3]})
    ctx = SimpleNamespace(reduced=reduced, family=fam,
                          shape=fam.shape_of(cfg), flops=flops,
                          peak=dict(bf16_flops_per_s=197e12,
                                    hbm_bytes_per_s=819e9))
    # a trace whose steps carry another family's counts, and one with none
    for counts in ({"steps": 3.0, "kda_rows": 40.0, "kda_state_slots": 9.0},
                   None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    # another family (no ``retention_cost``) with these counts: nothing
    counts = {"steps": 3.0, "retention_rows": 3 * 30.0,
              "retention_state_slots": 3 * 16.0}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
    if name.endswith("_pct"):
        other = SimpleNamespace(**{**vars(ctx), "family": SimpleNamespace()})
        assert mod.read(other) is None
    # with its counts and its calls' time it reads a share in (0, 100]:
    # 3 steps x 8 layers of 1.6 ms are over the 1.33 ms the states' stream
    # needs at the HBM peak
    reduced.op_seconds = lambda rx: 3 * 8 * 1.6e-3
    value = mod.read(ctx)
    assert value is not None and value > 0
    if name.endswith("_pct"):
        assert 80.0 < value <= 100.0
