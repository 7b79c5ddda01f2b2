"""The cell ``commandaplus-rag8`` on the CPU: its configuration file holds the
catalog row's numbers, the program builds the model the file describes, its
manifest entries are found BY NAME and lie after the accepted ones (never
"the last": the next PR's append must not redden this file), no request of
its mix can be evicted or cut, its cost functions grow with the work, its
readers read a small recorded trace and say nothing on a trace without their
calls, and its rehearsal runs end to end with no failed request."""

import importlib.util
import json
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import flops, kinds_trace, loadgen, reference, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL, CONFIG, MIX = "commandaplus-rag8", "command-a-plus-05-2026", "rag-8"
READERS = ("shared_expert_ms_per_step", "shared_expert_roofline_pct",
           "gating_ms_per_step", "full_attention_roofline_pct")
# the accepted metrics the cell joins, each of whose readers reads it right
JOINED = ("step_ms", "tokens_per_step", "chunk_steps_pct",
          "computed_rows_real_pct", "context_tokens_per_slot",
          "first_traced_step", "expert_ms_per_step", "experts_touched_pct",
          "window_attention_ms_per_step", "full_attention_ms_per_step",
          "window_attention_roofline_pct", "window_keys_skipped_pct")
# the cells and the configurations the benchmark had before this one
ACCEPTED_CELLS = (
    "bloom560m-pretrain-2k", "mixtral8x7b-chat", "bloom1b7-zero3-dp4",
    "mixtral8x7b-longdoc", "mellum2-12b-mixedlen", "deepseekv32-longctx",
    "glm47flash-pretrain-4k", "minicpm-sala-longctx128k",
    "ling3flash-reason16", "brumby14b-reason16", "glm53flash-longreason8")
ACCEPTED_CONFIGS = (
    "bloom-560m", "mixtral-8x7b", "bloom-1b7", "mellum2-12b-a2.5b",
    "deepseek-v3.2", "glm-4.7-flash", "minicpm-sala", "ling-3.0-flash",
    "brumby-14b", "glm-5.3-flash")
REDUCED = {"num_hidden_layers": (32, 4), "num_experts": (128, 16),
           "vocab_size": (262144, 32768)}
# the catalog row's ``config`` (model-configs guide, command-a-plus-05-2026)
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CATALOG = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144,
}
PEAK = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)


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


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_configuration_file_holds_the_catalog_rows_numbers(cfg, fam):
    assert cfg["source"] == ("https://huggingface.co/CohereLabs/"
                             "command-a-plus-05-2026/blob/main/config.json")
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in CATALOG.items():
        if key in REDUCED:
            published, held = REDUCED[key]
            assert value == published == cfg["published"][key], key
            assert cfg[key] == held, key
        else:
            assert cfg[key] == value, key
    # no width is cut: hidden, heads and their size, the expert width, the
    # router's width (published), experts a token, shared, window, theta
    s = fam.shape_of(cfg)
    assert (s.d, s.heads, s.kv_heads, s.hd, s.ffn, s.routed, s.top_k,
            s.shared, s.window, s.rope_theta) == (
        4096, 128, 8, 128, 4096, 128, 8, 4, 4096, 50000.0)
    assert (s.layers, s.experts, s.vocab, s.first_expert) == (4, 16, 32768, 0)
    assert s.pattern == tuple(PERIOD) and s.tied and s.eps == 1e-5
    assert s.kind_layers("sliding_attention") == 3
    assert s.kind_layers("full_attention") == 1
    for key in ("shared_average", "router", "expert_width", "rotary_pairs",
                "vision_tower", "depth", "experts_held", "vocab_size"):
        assert len(cfg["assumed"][key]) > 40, key
    assert "NOT taken" in cfg["assumed"]["shared_average"]
    share = cfg["deployment_share"]
    assert (share["members"], share["member"], share["first_expert"],
            share["experts_held"], share["layers_here"]) == (
        8, 0, 0, 16, [0, 1, 2, 3])
    assert "eight chips share each layer" in cfg["deployment"]
    # the floors of a model_config cut: a whole period of at least four
    # layers, at least 8 routed experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]


def test_the_program_builds_the_model_the_file_describes(cfg, fam):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks.run import build_model, check_shape, merged

    for config in (cfg, merged(cfg, cfg["rehearse"])):
        model = build_model(config["engine"])
        check_shape(model, fam.shape_of(config))
        c, s = model.config, fam.shape_of(config)
        assert (c.routed_experts, c.moe_first_expert, c.moe_shared_width,
                c.attn_window) == (s.routed, s.first_expert,
                                   s.shared * s.ffn, s.window)
        assert c.parallel_block and c.moe_gate == "sigmoid"
        assert c.layer_pattern == ("window",) * 3 + ("full",)
    model = build_model(cfg["engine"])
    # the issue's arithmetic: 4.733 B parameters = 9.47 GB in bf16
    assert model.num_params() == 4_733_292_544
    assert flops.stored_params(fam.shape_of(cfg)) == (
        model.num_params() - 5 * 4096)  # the arithmetic leaves the norms out


def test_the_manifest_entries_are_found_by_name_after_the_accepted(cfg, mix,
                                                                  fam):
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
    for part in ("rows/expert", "deployed", "host x8", "misses"):
        assert part in cell["why"], part
    # the faults neither sample sees on the chip are named in the why, and
    # the traffic file says of every fault what the chip's samples made of it
    cc = mix["correctness"]
    unseen = [w for w in cell["why"].split("misses")[1].split()
              if w in fam.FAULTS]
    assert unseen == ["window_off_by_one", "window_off"]
    for fault in fam.FAULTS:
        assert fault in cc["why"] + cc["precision"]["why"], fault
    for fault in unseen:
        assert fault in cc["precision"]["why"].split("NOT seen")[1]
    # the control is refused, and each limit lies between the readings the
    # why gives: sound's largest and the int8-rounded reference's smallest
    pc = cc["precision"]
    assert "REFUSED AT 11 SEEDS OF 11" in pc["why"]
    tokens = len(pc["prompts"]) * pc["new_tokens"]
    assert 238 < tokens - math.ceil(pc["min_argmax_share"] * tokens) < 684
    assert 37 < tokens - math.ceil(pc["min_near_share"] * tokens) < 407
    for part in ("traffic/" + MIX + ".json",
                 "families/" + cfg["family"] + ".py"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", part))
    metric_names = [m["name"] for m in manifest["per_layer"]]
    metrics = dict(zip(metric_names, manifest["per_layer"]))
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
        assert metrics[name]["source"] == "device_trace"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
        # after the metrics the last accepted PR brought
        assert metric_names.index(name) > metric_names.index(
            "first_traced_step")
        assert not name.endswith(".tput")
    for name in READERS[:3]:
        assert metrics[name]["layer"] == metrics["expert_ms_per_step"]["layer"]
    assert metrics[READERS[3]]["layer"] == (
        metrics["full_attention_ms_per_step"]["layer"])
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def listed_after_the_accepted(workloads):
        return CELL in workloads and all(
            workloads.index(CELL) > workloads.index(w)
            for w in workloads if w in ACCEPTED_CELLS)

    assert listed_after_the_accepted(e2e["serve_tokens_per_s"]["workloads"])
    assert "workloads" not in e2e["setup_s"]
    tput = [m for m in manifest["per_layer"] if m["name"].endswith(".tput")]
    assert len(tput) == 7
    for m in tput:
        assert listed_after_the_accepted(m["workloads"]), m["name"]
    for name in JOINED:
        assert listed_after_the_accepted(metrics[name]["workloads"]), name
    # the latency cell's metrics, and every other family's kernels'
    for m in manifest["per_layer"]:
        if m["name"] not in (*READERS, *JOINED) and not m["name"].endswith(
                ".tput"):
            assert CELL not in m["workloads"], m["name"]
    assert len(json.dumps(manifest)) < 64 * 1024


def test_no_request_of_the_mix_can_be_evicted_or_cut(cfg, mix, fam):
    srv = cfg["engine"]["serving"]
    assert (mix["kind"], mix["clients"], mix["replay_requests"],
            mix["schedule_seed"]) == ("closed_loop", 8, 64, 5601)
    assert mix["prompt"] == dict(median=16384, sigma=0.6, min=4096, max=65536)
    assert mix["answer"] == dict(median=256, sigma=0.5, min=64, max=1024)
    W = srv["token_budget"]
    assert srv["max_slots"] == 8 and W in (128, 256, 512)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest <= srv["max_tokens"] == 66560
    assert srv["max_tokens"] % W == 0
    assert mix["clients"] == srv["max_slots"]  # callers = slots: no queue
    # the full layer's pool holds every slot at full length with the chunk
    # in flight; the window layers' is sized by the engine from the model's
    # window (4,096 keys + the chunk + a page a slot)
    per_slot = -(-(srv["max_tokens"] + W) // srv["page_size"])
    assert srv["num_pages"] == srv["max_slots"] * per_slot
    window_pages = -(-(cfg["sliding_window"] + W) // srv["page_size"]) + 1
    assert f"{8 * window_pages:,}".replace(",", "") in cfg[
        "deployment"].replace(",", "")
    assert srv["prefix_cache"] is False
    # the arena: 4,096 B a token a layer
    token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    full = (srv["num_pages"] + 1) * srv["page_size"] * token
    window = 3 * (8 * window_pages + 1) * srv["page_size"] * token
    weights = 2 * 4_733_292_544
    assert 11.5e9 < weights + full + window < 13e9
    cc = mix["correctness"]
    assert cc["new_tokens"] == 24 and len(cc["prompts"]) == 2
    # one sample inside the window, one several windows past it
    assert cc["prompts"][0] < cfg["sliding_window"] < cc["prompts"][1] / 4
    # the precision sample: the eight slots just past the window first, then
    # many SHORT answers to short prompts (a long greedy answer of drawn
    # weights runs into a cycle and repeats its misses: PERF.md section 6),
    # in whole waves of the slots and within the queue
    pp = cc["precision"]["prompts"]
    assert all(cfg["sliding_window"] < n < cfg["sliding_window"] + 512
               for n in pp[:srv["max_slots"]])
    assert all(n < cfg["sliding_window"] / 8 for n in pp[srv["max_slots"]:])
    assert len(pp) % srv["max_slots"] == 0 and len(pp) <= srv["queue_limit"]
    assert cc["precision"]["new_tokens"] <= mix["answer"]["min"]
    for n in (*cc["prompts"], *cc["precision"]["prompts"]):
        assert n % 16 and n % W
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 64 and pairs.sum(1).max() <= srv["max_tokens"]
    # every context is at least a window long: every window layer skips keys
    assert pairs[:, 0].min() >= cfg["sliding_window"]
    # the worst drain: at the window's close the 8 callers hold at most the
    # 8 longest requests of the set, whole. By tokens at the slowest rate a
    # sound run has shown, and by steps (a decoding slot takes one row a
    # step) at the slowest step: both inside the grace
    worst = np.sort(pairs.sum(1))[-8:].sum()
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    steps = np.sort(pairs[:, 0])[-8:].sum() / W + mix["answer"]["max"]
    assert steps * mix["drain_step_ms"] / 1e3 < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]


def test_the_cost_functions_grow_with_the_work(cfg, fam):
    s = fam.shape_of(cfg)
    # attention: a (query, key) pair costs 2 x 2 x 128 heads x 128; a key
    # 2 x 8 KV heads x 128 x 2 B, ONCE for its slot's rows and 16 heads
    f, b = fam.full_attention_cost(s, 1000, 100, 8)
    assert f == 65536 * 1000
    assert b == 4096 * 100 + 2 * 16384 * 2 * 8
    assert fam.full_attention_cost(s, 2000, 100, 8)[0] == 2 * f
    assert fam.window_attention_cost is fam.full_attention_cost
    # a 505-row chunk at a 20 k context is compute-bound; one row is not
    chunk = fam.full_attention_cost(s, 505 * 20000, 20500, 505)
    assert flops.roofline_seconds(*chunk, PEAK)[1] == "compute"
    one = fam.full_attention_cost(s, 20000, 20000, 1)
    assert flops.roofline_seconds(*one, PEAK)[1] == "memory"
    # the shared bank: 4 experts x 3 x 4096 x 4096 a layer, once a step
    f, b = fam.shared_expert_cost(s, real_rows=512, steps=1)
    one_bank = 4 * 3 * 4096 * 4096
    assert f == 2 * one_bank * 512 * 4
    assert b == (one_bank + 2 * 4096 * 512) * 2 * 4
    assert flops.roofline_seconds(f, b, PEAK)[1] == "compute"
    f8, b8 = fam.shared_expert_cost(s, real_rows=8, steps=1)
    assert flops.roofline_seconds(f8, b8, PEAK)[1] == "memory"
    # the whole model's count: the window bounds three layers in four
    assert s.attention_flops_per_token(70000) - s.attention_flops_per_token(
        60000) == 65536 * 10000
    assert s.attention_flops_per_token(3000) == 4 * 65536 * 3000
    import re
    gate = re.compile(fam.gating_shapes(s, 256))
    assert gate.search("%sort.8 = (f32[256,128]{0,1}, s32[256,128]{0,1}) "
                       "sort(f32[256,128]{0,1} %copy.395)")
    assert gate.search("%fusion.297 = f32[256,128]{1,0} fusion(bf16[256,4096]"
                       "{1,0} %c, bf16[4,4096,128]{2,1,0} %router)")
    assert gate.search("%eq.52 = s32[256,8,16]{0,2,1} broadcast(s32[256,8])")
    # the combine reads the weights too, and is the expert layer's
    assert not gate.search("%fusion.162 = bf16[256,4096]{1,0} fusion(bf16"
                           "[2048,4096]{1,0} %f, bf16[256,8]{1,0} %w)")
    assert not gate.search("%fusion.217 = bf16[256,128,128]{2,0,1} fusion()")


def recorded_ctx(cfg, fam):
    """A context over ``cohere_trace.textproto``: two traced steps of a
    [8, 512] engine, with the operations the readers look for named as the
    chip's trace names them."""
    with open(os.path.join(HERE, "cohere_trace.textproto")) as f:
        trace = trace_reduce.load_text_proto(f.read())
    return SimpleNamespace(
        reduced=trace_reduce.reduce_trace(trace), full_trace=trace,
        family=fam, shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
        counters=dict(token_budget=512), root=ROOT)


def test_the_readers_read_a_small_recorded_trace(cfg, fam):
    ctx = recorded_ctx(cfg, fam)
    counts = kinds_trace.step_counts(ctx)
    assert counts["steps"] == 2 and counts["rows"] == 2 * 512
    assert counts["held_assignments"] == 1000 and (
        counts["experts_touched"], counts["experts_held"]) == (120, 128)
    assert kinds_trace.traced_steps(ctx) == 2
    # the shared bank's two fusions, 3 + 1 ms a step; not the routed bank's,
    # nor the q projection's (the same shape under another name)
    assert reader("shared_expert_ms_per_step").read(ctx) == pytest.approx(4.0)
    assert reader("expert_ms_per_step").read(ctx) == pytest.approx(10.0)
    need = flops.roofline_seconds(
        *fam.shared_expert_cost(ctx.shape, 1024, 2), PEAK)[0]
    assert reader("shared_expert_roofline_pct").read(ctx) == pytest.approx(
        100 * need / 8e-3)
    # the gate: the router's product and the top-k, 0.2 + 0.3 ms a step
    assert reader("gating_ms_per_step").read(ctx) == pytest.approx(0.5)
    # the NoPE call: 5 ms a step for 2 x 5e6 attended pairs
    need = flops.roofline_seconds(
        *fam.full_attention_cost(ctx.shape, 1e7, 41000, 1024), PEAK)[0]
    value = reader("full_attention_roofline_pct").read(ctx)
    assert value == pytest.approx(100 * need / 10e-3) and 0 < value < 100
    assert reader("full_attention_ms_per_step").read(ctx) == pytest.approx(5.0)
    assert reader("window_attention_ms_per_step").read(ctx) == pytest.approx(
        6.0)
    assert 0 < reader("window_attention_roofline_pct").read(ctx) < 100
    assert reader("experts_touched_pct").read(ctx) == pytest.approx(93.75)
    assert reader("window_keys_skipped_pct").read(ctx) == pytest.approx(60.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_says_nothing_on_a_trace_without_its_calls(name, cfg, fam,
                                                            monkeypatch):
    """On the parent's program (no such call, no such counter) a new reader
    returns None, not 0, and does not raise."""
    mod = reader(name)
    reduced = SimpleNamespace(op_seconds=lambda rx: 0.0,
                              spans={"bench/engine.step": [1, 2, 3]})
    ctx = SimpleNamespace(reduced=reduced, family=fam,
                          shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
                          counters=dict(token_budget=512))
    monkeypatch.setattr(kinds_trace, "operand_seconds", lambda c, rx: None)
    # a trace whose steps carry another family's counts (a model without
    # layer kinds), and one with none
    for counts in ({"steps": 3.0, "rows": 300.0, "context_keys": 9e5}, None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    counts = {"steps": 3.0, "rows": 3 * 512.0, "attended_full": 3e7,
              "fetched_full": 4e5, "attended_window": 1e7,
              "fetched_window": 1e5}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
    # with its counts but no time of its calls: still nothing
    assert mod.read(ctx) is None
    # another family (no cost function, no shapes, no shared experts)
    other = SimpleNamespace(**{**vars(ctx), "family": SimpleNamespace(),
                               "shape": reference.family("mellum").shape_of(
                                   load("benchmarks", "configs",
                                        "mellum2-12b-a2.5b.json"))})
    reduced.op_seconds = lambda rx: 3 * 5e-3
    monkeypatch.setattr(kinds_trace, "operand_seconds",
                        lambda c, rx: 3 * 5e-3)
    assert mod.read(other) is None
    # with its counts and its calls' time it reads a positive number, a
    # share under 100: 3 steps of 5 ms
    value = mod.read(ctx)
    assert value is not None and value > 0
    if name.endswith("_pct"):
        assert value < 100.0
    else:
        assert value == pytest.approx(5.0)


def test_rehearsal_passes_with_no_failed_request():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "5600000011", "--seconds", "3",
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
