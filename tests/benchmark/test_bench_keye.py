"""The cell ``keyevl2-longmm4`` on the CPU: its configuration file holds the
catalog row's numbers, the program builds the model the file describes, its
manifest entries are found BY NAME and lie after the accepted ones (never
"the last": the next PR's append must not redden this file), no request of
its mix can be evicted or cut, its cost functions grow with the work, its
two readers read a small recorded trace and say nothing on a trace without
their calls, and its rehearsal runs end to end with no failed request."""

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
CELL, CONFIG, MIX = "keyevl2-longmm4", "keye-vl-2.0-30b-a3b", "longmm-4"
READERS = ("sparse_paged_attention_ms_per_step",
           "sparse_paged_attention_roofline_pct")
# the accepted metrics the cell joins, each of whose readers reads it right
JOINED = ("step_ms", "tokens_per_step", "chunk_steps_pct",
          "computed_rows_real_pct", "context_tokens_per_slot",
          "first_traced_step", "indexer_ms_per_step", "indexer_roofline_pct",
          "selected_keys_skipped_pct", "expert_ms_per_step",
          "experts_touched_pct")
# the cells and the configurations the benchmark had before this one
ACCEPTED_CELLS = (
    "bloom560m-pretrain-2k", "mixtral8x7b-chat", "bloom1b7-zero3-dp4",
    "mixtral8x7b-longdoc", "mellum2-12b-mixedlen", "deepseekv32-longctx",
    "glm47flash-pretrain-4k", "minicpm-sala-longctx128k",
    "ling3flash-reason16", "brumby14b-reason16", "glm53flash-longreason8",
    "commandaplus-rag8")
ACCEPTED_CONFIGS = (
    "bloom-560m", "mixtral-8x7b", "bloom-1b7", "mellum2-12b-a2.5b",
    "deepseek-v3.2", "glm-4.7-flash", "minicpm-sala", "ling-3.0-flash",
    "brumby-14b", "glm-5.3-flash", "command-a-plus-05-2026")
REDUCED = {"num_hidden_layers": (48, 12), "num_experts": (128, 32),
           "vocab_size": (151936, 37984)}
# the catalog row's ``config`` (model-configs guide, Keye-VL-2.0-30B-A3B)
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
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
    assert cfg["source"] == ("https://huggingface.co/Kwai-Keye/"
                             "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in CATALOG.items():
        if key in REDUCED:
            published, held = REDUCED[key]
            assert value == published == cfg["published"][key], key
            assert cfg[key] == held, key
        else:
            assert cfg[key] == value, key
    # no width is cut: hidden, heads and their size, the expert width, the
    # router's width, experts a token, the indexer, theta and the sections
    s = fam.shape_of(cfg)
    assert (s.d, s.heads, s.kv_heads, s.hd, s.ffn, s.routed, s.top_k,
            s.index_heads, s.index_dim, s.index_topk, s.rope_theta,
            s.sections) == (2048, 32, 4, 128, 768, 128, 8, 16, 64, 2048, 1e7,
                            (16, 24, 24))
    assert (s.layers, s.experts, s.vocab, s.dense_layers) == (12, 32, 37984, 0)
    assert not s.tied and s.eps == 1e-6
    for key in ("tower", "qk_norm", "indexer_query", "indexer_norm",
                "indexer_rope", "chunks", "precision", "mrope", "dense_width",
                "router", "depth", "experts", "vocabulary", "weights"):
        assert len(cfg["assumed"][key]) > 40, key
    assert "four pipeline stages of 12 layers" in cfg["deployment"]
    assert "member 0 of the first stage" in cfg["deployment"]
    # the floors of a model_config cut: at least four layers, at least 8
    # routed experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    # a configuration the family cannot compute is refused where it is read
    with pytest.raises(ValueError, match="ONE key a token"):
        fam.shape_of({**cfg, "sa_config": {**cfg["sa_config"],
                                           "indexer_num_kv_heads": 2}})
    with pytest.raises(ValueError, match="mrope_section"):
        fam.shape_of({**cfg, "rope_scaling": {"mrope_section": [16, 24]}})


def test_the_program_builds_the_model_the_file_describes(cfg, fam):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks.run import build_model, check_shape, merged

    for config in (cfg, merged(cfg, cfg["rehearse"])):
        model = build_model(config["engine"])
        check_shape(model, fam.shape_of(config))
        c, s = model.config, fam.shape_of(config)
        assert (c.routed_experts, c.moe_first_expert, c.index_heads,
                c.index_dim, c.index_rope_dim, c.index_topk) == (
            s.routed, 0, s.index_heads, s.index_dim, s.index_dim,
            s.index_topk)
        assert (c.moe_gate, c.moe_dropless, c.qk_norm, c.moe_shared_width,
                c.layer_pattern) == ("softmax", True, True, 0, ())
    model = build_model(cfg["engine"])
    # the issue's arithmetic: 2.22 B parameters = 4.45 GB in bf16
    assert model.num_params() == 2_224_347_648
    s = fam.shape_of(cfg)
    # the arithmetic leaves the norms out: two a layer and the last one,
    # a q and a k vector a layer, the index key's scale and bias
    assert flops.stored_params(s) == model.num_params() - (
        12 * (2 * 2048 + 2 * 128 + 2 * 64) + 2048)
    # a token a layer in the arena: K, V and the index key's 128-lane row
    from deepspeed_tpu.serving.engine import cache_token_bytes

    assert cache_token_bytes(model.config, 2, False) == 2304


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
    for part in ("rows/expert", "deployed", "host x4", "misses"):
        assert part in cell["why"], part
    # the faults neither sample sees on the chip are named in the why, and
    # the traffic file says of every fault what the chip's samples made of it
    cc = mix["correctness"]
    unseen = [w.strip(",.;") for w in cell["why"].split("misses")[1].split()
              if w.strip(",.;") in fam.FAULTS]
    for fault in fam.FAULTS:
        assert fault in cc["why"] + cc["precision"]["why"], fault
    for fault in unseen:
        assert fault in (cc["why"] + cc["precision"]["why"]).split(
            "NOT seen")[1]
    assert unseen == ["index_norm_off"]
    # the control is refused, and each limit lies between the readings the
    # why gives: sound's largest and the int8-rounded reference's smallest
    pc = cc["precision"]
    assert "REFUSED AT 13 SEEDS OF 13" in pc["why"]
    tokens = len(pc["prompts"]) * pc["new_tokens"]
    assert 103 < tokens - math.ceil(pc["min_argmax_share"] * tokens) < 230
    assert 3 < tokens - math.ceil(pc["min_near_share"] * tokens) < 44
    assert 0 < 48 - math.ceil(cc["min_near_share"] * 48) < 24
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
            "full_attention_roofline_pct")
    assert metrics[READERS[0]]["layer"] == metrics[READERS[1]]["layer"]
    assert (metrics[READERS[0]]["unit"], metrics[READERS[1]]["unit"]) == (
        "ms", "%")
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
            mix["schedule_seed"]) == ("closed_loop", 4, 32, 5801)
    assert mix["prompt"] == dict(median=24576, sigma=0.6, min=8192, max=65536)
    assert mix["answer"] == dict(median=256, sigma=0.6, min=64, max=1024)
    assert (mix["grace_s"], mix["trace_seconds"]) == (90.0, 4.0)
    # the keys longctx.json has
    assert set(mix) == set(load("benchmarks", "traffic", "longctx.json"))
    W = srv["token_budget"]
    assert (srv["max_slots"], W) == (4, 128)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest == srv["max_tokens"] == 66560 and longest % W == 0
    assert mix["clients"] == srv["max_slots"]  # callers = slots: no queue
    # the pools hold every slot at full length at once
    assert srv["num_pages"] * srv["page_size"] == 4 * srv["max_tokens"]
    assert srv["prefix_cache"] is False
    # the arena: 2,304 B a token a layer (K, V, a 128-lane index key) over
    # 12 layers, beside 4.45 GB of weights: the issue's 11.8 GB
    token = (2 * cfg["num_key_value_heads"] * cfg["head_dim"] + 128) * 2
    arena = (srv["num_pages"] + 1) * srv["page_size"] * token * 12
    assert 7.35e9 < arena < 7.37e9
    assert 11.7e9 < arena + 2 * 2_224_347_648 < 11.9e9
    cc = mix["correctness"]
    topk = cfg["sa_config"]["topk"]
    assert cc["new_tokens"] == 24 and len(cc["prompts"]) == 2
    # one sample inside the selection, one about ten times past it
    assert cc["prompts"][0] < topk < cc["prompts"][1] / 9
    # the precision sample: four waves of the slots just past topk first
    # (the selection bites), then many SHORT answers to short prompts (a
    # long greedy answer of drawn weights runs into a cycle and repeats its
    # misses: PERF.md section 6, PR 56), in whole waves of the slots
    pp = cc["precision"]["prompts"]
    assert all(topk < n < topk + 512 for n in pp[:16])
    assert all(n < topk / 4 for n in pp[16:]) and len(pp) == 68
    assert len(pp) % srv["max_slots"] == 0 and len(pp) <= srv["queue_limit"]
    assert cc["precision"]["new_tokens"] <= mix["answer"]["min"]
    for n in (*cc["prompts"], *pp):
        assert n % 16 and n % W and n % 512
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 32 and pairs.sum(1).max() <= srv["max_tokens"]
    # every context is at least four times topk: every query's selection
    # leaves most of its context out
    assert pairs[:, 0].min() >= 4 * topk
    assert (pairs[:, 0].min(), pairs[:, 0].max(), pairs[:, 1].min(),
            pairs[:, 1].max()) == (8643, 57872, 64, 752)
    # the worst drain: at the window's close the 4 callers hold at most the
    # 4 longest requests of the set, whole, at the slowest rate a sound run
    # has shown: inside the grace
    worst = np.sort(pairs.sum(1))[-4:].sum()
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]


def test_the_cost_functions_grow_with_the_work(cfg, fam):
    s = fam.shape_of(cfg)
    # the walk: a (query, key) pair costs 2 x 2 x 32 heads x 128; a chosen
    # token K and V of 4 KV heads x 128 x 2 B, ONCE for its slot's rows
    f, b = fam.sparse_attention_cost(s, 1000, 100, 8)
    assert f == 16384 * 1000
    assert b == 2048 * 100 + 2 * 32 * 128 * 2 * 8
    assert fam.sparse_attention_cost(s, 2000, 100, 8)[0] == 2 * f
    assert fam.sparse_attention_cost(s, 1000, 200, 8)[1] == b + 2048 * 100
    # a 125-row chunk past topk attends 2,048 keys a row and is
    # compute-bound; a decoding row reads its 2,048 chosen tokens and is not
    chunk = fam.sparse_attention_cost(s, 125 * 2048, 2048, 125)
    assert flops.roofline_seconds(*chunk, PEAK)[1] == "compute"
    one = fam.sparse_attention_cost(s, 2048, 2048, 1)
    assert flops.roofline_seconds(*one, PEAK)[1] == "memory"
    # the indexer: 2 x 16 heads x 64 a (query, cached token) pair; an index
    # key 64 x 2 B a token of the slot's pages
    f, b = fam.indexer_cost(s, 1e6, 3e4, 8)
    assert f == 2048 * 1e6 and b == 128 * 3e4 + 16 * (128 + 4) * 8
    assert fam.indexer_cost(s, 2e6, 3e4, 8)[0] == 2 * f
    # the whole model's count: past topk a token's attention stops growing
    # and its scoring does not
    grow = s.attention_flops_per_token(70000) - s.attention_flops_per_token(
        60000)
    assert grow == 12 * 2048 * 10000
    assert s.attention_flops_per_token(1000) == 12 * (2048 + 16384) * 1000
    # a token touches a quarter of its 8 experts here
    assert s.layer_matmul_params() == s.layer_matmul_params(False) - (
        30 * 3 * 2048 * 768)


def recorded_ctx(cfg, fam):
    """A context over ``keye_trace.textproto``: two traced steps of the
    [4, 128] engine, with the operations the readers look for named as the
    chip's trace names them."""
    with open(os.path.join(HERE, "keye_trace.textproto")) as f:
        trace = trace_reduce.load_text_proto(f.read())
    return SimpleNamespace(
        reduced=trace_reduce.reduce_trace(trace), full_trace=trace,
        family=fam, shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
        counters=dict(token_budget=128), root=ROOT)


def test_the_readers_read_a_small_recorded_trace(cfg, fam):
    ctx = recorded_ctx(cfg, fam)
    counts = kinds_trace.step_counts(ctx)
    assert counts["steps"] == 2 and counts["rows"] == 2 * 128
    assert (counts["context_keys"], counts["attended_sparse"]) == (
        6_000_000, 520_000)
    assert kinds_trace.traced_steps(ctx) == 2
    # the walk: 12 layers x 0.5 ms a step
    assert reader(READERS[0]).read(ctx) == pytest.approx(6.0)
    need = flops.roofline_seconds(*fam.sparse_attention_cost(
        ctx.shape, 520_000, 16_000, 256), PEAK)[0]
    value = reader(READERS[1]).read(ctx)
    assert value == pytest.approx(100 * need / (12e-3 / 12))
    assert 0 < value < 100
    # the joined readers on the same trace: the indexer's two calls, 0.25 +
    # 0.15 ms a layer; the routed bank's fusion; the counters
    assert reader("indexer_ms_per_step").read(ctx) == pytest.approx(4.8)
    need = flops.roofline_seconds(*fam.indexer_cost(
        ctx.shape, 6_000_000, 98_000, 256), PEAK)[0]
    assert reader("indexer_roofline_pct").read(ctx) == pytest.approx(
        100 * need / (9.6e-3 / 12))
    assert reader("selected_keys_skipped_pct").read(ctx) == pytest.approx(
        100 * (1 - 520_000 / 6_000_000))
    assert reader("expert_ms_per_step").read(ctx) == pytest.approx(3.0)
    assert reader("experts_touched_pct").read(ctx) == pytest.approx(75.0)
    # no latent walk and no plain paged call in this model's step
    assert reader("sparse_attention_ms_per_step").read(ctx) is None
    assert reader("full_attention_ms_per_step").read(ctx) is None


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
                          counters=dict(token_budget=128))
    # a trace whose steps carry another family's counts (a model without a
    # selection), and one with none
    for counts in ({"steps": 3.0, "rows": 300.0, "attended_full": 9e5}, None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    counts = {"steps": 3.0, "rows": 3 * 128.0, "context_keys": 9e6,
              "attended_sparse": 7e5, "index_keys": 1.5e5,
              "chosen_min": 2.4e4}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
    # with its counts but no time of its calls: still nothing
    assert mod.read(ctx) is None
    reduced.op_seconds = lambda rx: 3 * 6e-3 if "sparse_paged" in rx else 0.0
    if name.endswith("_pct"):
        # another family (no cost function): nothing
        other = SimpleNamespace(**{**vars(ctx), "family": SimpleNamespace()})
        assert mod.read(other) is None
    # with its counts and its calls' time it reads a positive number, a
    # share under 100: 3 steps of 6 ms
    value = mod.read(ctx)
    assert value is not None and value > 0
    if name.endswith("_pct"):
        assert value < 100.0
    else:
        assert value == pytest.approx(6.0)


def test_rehearsal_passes_with_no_failed_request():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "5800000011", "--seconds", "3",
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
