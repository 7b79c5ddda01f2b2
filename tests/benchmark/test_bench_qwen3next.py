"""The cell ``qwen3next-longctx256k`` on the CPU: its configuration file holds
the catalog row's numbers, the program builds the model the file describes,
its manifest entries are found BY NAME and lie after the accepted ones (never
"the last": the next PR's append must not redden this file), no request of
its mix can be evicted or cut, its cost functions grow with the work, its two
readers and the accepted readers it joins read a small recorded trace and say
nothing on a trace without their calls, and its rehearsal runs end to end
with no failed request."""

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
CELL, CONFIG, MIX = ("qwen3next-longctx256k", "qwen3-next-80b-a3b",
                     "longctx-256k")
READERS = ("gdn_ms_per_step", "gdn_roofline_pct")
# the accepted share of this call's roofline has its list of cells pinned by
# an accepted test, so the cell reads the same arithmetic under a new name
GATED = "gated_attention_roofline_pct"
# the accepted metrics the cell joins, each of whose readers reads it right
JOINED = ("step_ms", "tokens_per_step", "chunk_steps_pct",
          "computed_rows_real_pct", "context_tokens_per_slot",
          "first_traced_step", "full_attention_ms_per_step",
          "expert_ms_per_step", "experts_touched_pct")
# the cells and the configurations the benchmark had before this one
ACCEPTED_CELLS = (
    "bloom560m-pretrain-2k", "mixtral8x7b-chat", "bloom1b7-zero3-dp4",
    "mixtral8x7b-longdoc", "mellum2-12b-mixedlen", "deepseekv32-longctx",
    "glm47flash-pretrain-4k", "minicpm-sala-longctx128k",
    "ling3flash-reason16", "brumby14b-reason16", "glm53flash-longreason8",
    "commandaplus-rag8", "keyevl2-longmm4")
ACCEPTED_CONFIGS = (
    "bloom-560m", "mixtral-8x7b", "bloom-1b7", "mellum2-12b-a2.5b",
    "deepseek-v3.2", "glm-4.7-flash", "minicpm-sala", "ling-3.0-flash",
    "brumby-14b", "glm-5.3-flash", "command-a-plus-05-2026",
    "keye-vl-2.0-30b-a3b")
REDUCED = {"num_hidden_layers": (48, 12), "num_experts": (512, 64),
           "vocab_size": (151936, 18992)}
# the catalog row's ``config`` (model-configs guide,
# Qwen3-Next-80B-A3B-Instruct)
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
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
    assert cfg["source"] == ("https://huggingface.co/Qwen/"
                             "Qwen3-Next-80B-A3B-Instruct/blob/main/"
                             "config.json")
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in CATALOG.items():
        if key in REDUCED:
            published, held = REDUCED[key]
            assert value == published == cfg["published"][key], key
            assert cfg[key] == held, key
        else:
            assert cfg[key] == value, key
    # no width is cut: hidden, both kinds' heads and their sizes, the rotated
    # part, the taps, the expert and shared widths, the router's width,
    # experts a token, theta
    s = fam.shape_of(cfg)
    assert (s.d, s.heads, s.kv_heads, s.hd, s.rotary, s.key_heads,
            s.value_heads, s.gdn_dim, s.conv, s.ffn, s.shared, s.routed,
            s.top_k, s.rope_theta, s.interval) == (
        2048, 16, 2, 256, 64, 16, 32, 128, 4, 512, 512, 512, 10, 1e7, 4)
    assert (s.layers, s.experts, s.vocab, s.dense_layers, s.first_expert) == (
        12, 64, 18992, 0, 0)
    assert s.layer_ids == tuple(range(12))  # three whole periods G G G A
    assert (s.count("gdn"), s.kind_layers("full_attention")) == (9, 3)
    assert [s.kind(i) for i in range(4)] == ["gdn"] * 3 + ["full_attention"]
    assert not s.tied and s.eps == 1e-6
    for key in ("layout", "rotary", "norms", "decay", "convolution",
                "gdn_norm", "attention_gate", "router", "dense_width", "mtp",
                "depth", "experts", "vocabulary", "weights"):
        assert len(cfg["assumed"][key]) > 40, key
    assert "four pipeline stages of 12 layers" in cfg["deployment"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert "member 0 of the first stage" in cfg["deployment"]
    # the floors of a model_config cut: at least four layers, at least 8
    # routed experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    # a configuration the family cannot compute is refused where it is read
    with pytest.raises(ValueError, match="every layer is routed"):
        fam.shape_of({**cfg, "mlp_only_layers": [0]})
    with pytest.raises(ValueError, match="square"):
        fam.shape_of({**cfg, "linear_value_head_dim": 256})
    with pytest.raises(ValueError, match="layer_ids"):
        fam.shape_of({**cfg, "layer_ids": [0, 1]})


def test_the_program_builds_the_model_the_file_describes(cfg, fam):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks.run import build_model, check_shape, merged

    for config in (cfg, merged(cfg, cfg["rehearse"])):
        model = build_model(config["engine"])
        check_shape(model, fam.shape_of(config))
        c, s = model.config, fam.shape_of(config)
        assert (c.routed_experts, c.moe_first_expert, c.gdn_key_heads,
                c.gdn_value_heads, c.gdn_head_dim, c.rotary_dim,
                c.conv_kernel, c.moe_shared_width) == (
            s.routed, 0, s.key_heads, s.value_heads, s.gdn_dim, s.rotary,
            s.conv, s.shared)
        assert (c.moe_gate, c.moe_dropless, c.qk_norm, c.attn_out_gate,
                c.layer_pattern) == ("softmax", True, True, True, ())
        assert c.mixer_types == tuple(
            {"gdn": "gdn", "full_attention": "full"}[s.kind(i)]
            for i in s.layer_ids)
    model = build_model(cfg["engine"])
    # the issue's arithmetic: 33.72 M and 27.26 M a mixer, 2.93 B parameters
    # = 5.86 GB in bf16
    from deepspeed_tpu.models.qwen3_next import mixer_params

    assert mixer_params(model.config, "gdn") == 33_718_464
    assert mixer_params(model.config, "full") == 27_263_488
    assert model.num_params() == 2_929_374_400
    s = fam.shape_of(cfg)
    # the arithmetic leaves out what is no matrix: two norms a layer and the
    # last one, a q and a k vector an attention layer; a Gated DeltaNet
    # layer's taps, A_log, dt_bias and head norm
    assert flops.stored_params(s) == model.num_params() - (
        12 * 2 * 2048 + 2048 + 3 * 2 * 256 + 9 * (4 * 8192 + 2 * 32 + 128))
    # a token a paged layer in the arena: K and V of 2 KV heads of 256; a
    # slot's leaves: 9 x (2 MiB of state + 48 KiB of convolution rows)
    from deepspeed_tpu.serving.engine import cache_token_bytes, state_bytes

    assert cache_token_bytes(model.config, 2, False) == 2048
    assert state_bytes(model.config, 1, 2) == 9 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2) == 19_316_736


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
    # fourteen cells, one of them on four chips
    assert len(manifest["workloads"]) >= 14
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    for part in ("8k-254k", "rows/expert", "deployed", "misses"):
        assert part in cell["why"], part
    # the faults neither sample sees on the chip are named in the why, and
    # the traffic file says of every fault what the chip's samples made of it
    cc = mix["correctness"]
    said = cc["why"] + cc["precision"]["why"]
    unseen = [w.strip(",.;") for w in cell["why"].split("misses")[1].split()
              if w.strip(",.;") in fam.FAULTS]
    for fault in fam.FAULTS:
        assert fault in said, fault
    for fault in unseen:
        assert fault in said.split("NOT seen")[1], fault
    assert unseen == ["gate_clamped", "state_bf16", "norm_centre_off"]
    # the control is refused, and each limit lies between the readings the
    # why gives: sound's largest and the int8-rounded reference's smallest
    pc = cc["precision"]
    assert "REFUSED AT 12 SEEDS OF 12" in pc["why"]
    tokens = len(pc["prompts"]) * pc["new_tokens"]
    assert 824 < tokens - math.ceil(pc["min_argmax_share"] * tokens) < 1383
    assert 611 < tokens - math.ceil(pc["min_near_share"] * tokens) < 1200
    assert 0 < 48 - math.ceil(cc["min_near_share"] * 48) < 8
    assert (cc["logit_tol"], pc["logit_tol"]) == (0.3, 0.03)
    for part in ("traffic/" + MIX + ".json",
                 "families/" + cfg["family"] + ".py"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", part))
    metric_names = [m["name"] for m in manifest["per_layer"]]
    metrics = dict(zip(metric_names, manifest["per_layer"]))
    assert metrics[GATED]["layer"] == metrics[
        "full_attention_ms_per_step"]["layer"]
    assert CELL not in metrics["full_attention_roofline_pct"]["workloads"]
    for name in (*READERS, GATED):
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
        assert metrics[name]["source"] == "device_trace"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
        # after the metrics the last accepted PR brought
        assert metric_names.index(name) > metric_names.index(
            "sparse_paged_attention_roofline_pct")
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
    # no new metric is named *.tput: the accepted tests pin their count
    tput = [m for m in manifest["per_layer"] if m["name"].endswith(".tput")]
    assert len(tput) == 7
    for m in tput:
        assert listed_after_the_accepted(m["workloads"]), m["name"]
    for name in JOINED:
        assert listed_after_the_accepted(metrics[name]["workloads"]), name
    # the latency cell's metrics, and every other family's kernels'
    for m in manifest["per_layer"]:
        if m["name"] not in (*READERS, GATED, *JOINED) and not m[
                "name"].endswith(
                ".tput"):
            assert CELL not in m["workloads"], m["name"]
    assert len(json.dumps(manifest)) < 64 * 1024


def test_no_request_of_the_mix_can_be_evicted_or_cut(cfg, mix, fam):
    srv = cfg["engine"]["serving"]
    assert (mix["kind"], mix["clients"], mix["replay_requests"],
            mix["schedule_seed"]) == ("closed_loop", 4, 32, 6208)
    assert mix["prompt"] == dict(median=32768, sigma=0.9, min=8192,
                                 max=253952)
    assert mix["answer"] == dict(median=384, sigma=0.6, min=64, max=2048)
    assert (mix["grace_s"], mix["trace_seconds"]) == (180.0, 4.0)
    # the keys longctx.json has
    assert set(mix) == set(load("benchmarks", "traffic", "longctx.json"))
    W = srv["token_budget"]
    assert (srv["max_slots"], W) == (4, 256)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest == 256000 <= srv["max_tokens"] == 262144
    assert srv["max_tokens"] == cfg["max_position_embeddings"]
    assert mix["clients"] == srv["max_slots"]  # callers = slots: no queue
    # the pool holds every slot at full length at once
    assert srv["num_pages"] * srv["page_size"] == 4 * srv["max_tokens"]
    assert srv["max_tokens"] % srv["page_size"] == 0
    assert srv["prefix_cache"] is False
    # the arena: 2,048 B a token a paged layer (K and V of 2 KV heads of
    # 256) over 3 layers, and 19.3 MB a slot of state and convolution rows,
    # beside 5.86 GB of weights: the issue's 12.4 GB
    token = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    arena = (srv["num_pages"] + 1) * srv["page_size"] * token * 3
    assert 6.44e9 < arena < 6.45e9
    resident = arena + 4 * 19_316_736 + 2 * 2_929_374_400
    assert 12.3e9 < resident < 12.5e9
    # the page table of 4 slots rides in SMEM under the kernel's limit
    from deepspeed_tpu.ops.pallas.paged_attention import SMEM_TABLE_BYTES

    assert 4 * (srv["max_tokens"] // srv["page_size"]) * 4 <= SMEM_TABLE_BYTES
    cc = mix["correctness"]
    assert cc["new_tokens"] == 24 and len(cc["prompts"]) == 2
    # both samples run several chunks of the budget; the second runs tens
    assert W * 5 < cc["prompts"][0] < W * 8 and cc["prompts"][1] > 32 * W
    # the precision sample: four waves of the slots over three chunks first
    # (the chunk form, the carried state and convolution rows), then many
    # SHORT answers to short prompts (a long greedy answer of drawn weights
    # runs into a cycle and repeats its misses: PERF.md section 6, PR 56), in
    # whole waves of the slots
    pp = cc["precision"]["prompts"]
    assert all(3 * W < n < 4 * W for n in pp[:16])
    assert all(n < W // 4 for n in pp[16:]) and len(pp) == 68
    assert len(pp) % srv["max_slots"] == 0 and len(pp) <= srv["queue_limit"]
    assert cc["precision"]["new_tokens"] <= mix["answer"]["min"]
    for n in (*cc["prompts"], *pp):
        assert n % srv["page_size"] and n % W
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 32 and pairs.sum(1).max() <= 256000
    # the set's longest prompt passes every other cell's longest context
    assert (pairs[:, 0].min(), pairs[:, 0].max(), int(pairs[:, 0].mean()),
            pairs[:, 1].min(), pairs[:, 1].max(), int(pairs[:, 1].mean())
            ) == (8192, 253952, 56655, 111, 1084, 477)
    assert pairs[:, 0].max() >= 131072 and pairs[3, 0] == 253952
    # the worst drain: at the window's close the 4 callers hold at most the
    # 4 longest requests of the set, whole, at the slowest rate a sound run
    # has shown: inside the grace
    worst = np.sort(pairs.sum(1))[-4:].sum()
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]


def test_the_cost_functions_grow_with_the_work(cfg, fam):
    s = fam.shape_of(cfg)
    # the delta rule: 7 x 32 value heads x 128 x 128 a real row; a live
    # state 2 MiB read and 2 MiB written; a row's q, k (16 key heads), v, o
    # (32 value heads) in bf16 and its 32 log-decays and step sizes
    f, b = fam.gdn_cost(s, 259, 4)
    assert f == 7 * 32 * 128 * 128 * 259
    assert b == 4 * 2 * 32 * 128 * 128 * 4 + 259 * (
        2 * (16 + 32) * 128 * 2 + 2 * 32 * 4)
    assert fam.gdn_cost(s, 518, 4)[0] == 2 * f
    assert fam.gdn_cost(s, 259, 8)[1] == b + 4 * 2 * 32 * 128 * 128 * 4
    # a step of one chunk and three decoding rows moves states: memory-bound
    assert flops.roofline_seconds(f, b, PEAK)[1] == "memory"
    # gated attention: a (query, key) pair costs 2 x 2 x 16 heads x 256; a
    # fetched key K and V of 2 KV heads x 256 x 2 B, ONCE for its slot's
    # rows and query heads
    f, b = fam.full_attention_cost(s, 1000, 128, 8)
    assert f == 16384 * 1000
    assert b == 2048 * 128 + 2 * 16 * 256 * 2 * 8
    assert fam.full_attention_cost(s, 2000, 128, 8)[0] == 2 * f
    assert fam.full_attention_cost(s, 1000, 256, 8)[1] == b + 2048 * 128
    # a 253-row chunk at a context of 100 k is compute-bound (8 query heads
    # a KV head share a key); a decoding row is not
    chunk = fam.full_attention_cost(s, 253 * 100_000, 100_032, 253)
    assert flops.roofline_seconds(*chunk, PEAK)[1] == "compute"
    one = fam.full_attention_cost(s, 100_000, 100_032, 1)
    assert flops.roofline_seconds(*one, PEAK)[1] == "memory"
    # the whole model's count: three layers' attention grows with the
    # context, nine layers' recurrence does not
    grow = s.attention_flops_per_token(70000) - s.attention_flops_per_token(
        60000)
    assert grow == 3 * 16384 * 10000
    assert s.attention_flops_per_token(0) == 9 * 8 * 32 * 128 * 128
    # a token touches an eighth of its 10 experts here
    assert s.layer_matmul_params(False) - s.layer_matmul_params() == int(
        (64 - 1.25) * 3 * 2048 * 512)


def recorded_ctx(cfg, fam):
    """A context over ``qwen3next_trace.textproto``: two traced steps of the
    [4, 256] engine, with the operations the readers look for named as the
    chip's trace names them."""
    with open(os.path.join(HERE, "qwen3next_trace.textproto")) as f:
        trace = trace_reduce.load_text_proto(f.read())
    return SimpleNamespace(
        reduced=trace_reduce.reduce_trace(trace), full_trace=trace,
        family=fam, shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
        counters=dict(token_budget=256), root=ROOT)


def test_the_readers_read_a_small_recorded_trace(cfg, fam):
    ctx = recorded_ctx(cfg, fam)
    counts = kinds_trace.step_counts(ctx)
    assert counts["steps"] == 2 and counts["rows"] == 2 * 256
    assert (counts["gdn_rows"], counts["gdn_state_slots"],
            counts["state_resets"]) == (512, 8, 1)
    assert kinds_trace.traced_steps(ctx) == 2
    # the delta rule: 9 layers x 0.3 ms a step
    assert reader(READERS[0]).read(ctx) == pytest.approx(2.7)
    need = flops.roofline_seconds(*fam.gdn_cost(ctx.shape, 512, 8), PEAK)[0]
    value = reader(READERS[1]).read(ctx)
    assert value == pytest.approx(100 * need / (5.4e-3 / 9))
    assert 0 < value < 100
    # the joined readers on the same trace: the three gated-attention
    # layers' paged calls, 1.5 ms a layer; the routed bank's fusion; the
    # counters
    assert reader("full_attention_ms_per_step").read(ctx) == pytest.approx(4.5)
    need = flops.roofline_seconds(*fam.full_attention_cost(
        ctx.shape, 18_000_000, 360_000, 512), PEAK)[0]
    value = reader(GATED).read(ctx)
    assert value == pytest.approx(100 * need / (9e-3 / 3))
    assert 0 < value < 100
    # (the accepted reader would read the same, were the cell on its list)
    assert reader("full_attention_roofline_pct").read(ctx) == value
    assert reader("expert_ms_per_step").read(ctx) == pytest.approx(6.0)
    assert reader("experts_touched_pct").read(ctx) == pytest.approx(
        100 * 600 / 768)
    # no other family's delta rule, no window layer and no selection here
    for other in ("kda_ms_per_step", "kda_roofline_pct",
                  "window_attention_ms_per_step",
                  "sparse_paged_attention_ms_per_step",
                  "lightning_roofline_pct"):
        assert reader(other).read(ctx) is None, other


def test_the_gated_share_says_nothing_without_both_kinds_counts(cfg, fam,
                                                               monkeypatch):
    """A model of window and full layers carries ``attended_full`` too: the
    new name reads only where the Gated DeltaNet counts are beside it, so
    the parent's traced runs of the accepted cells leave it out."""
    mod = reader(GATED)
    reduced = SimpleNamespace(op_seconds=lambda rx: 9e-3,
                              spans={"bench/engine.step": [1, 2, 3]})
    ctx = SimpleNamespace(reduced=reduced, family=fam,
                          shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
                          counters=dict(token_budget=256))
    theirs = {"steps": 3.0, "rows": 768.0, "attended_full": 9e6,
              "fetched_full": 2e5, "attended_window": 1e6}
    for counts in (theirs, None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    ours = {**theirs, "gdn_rows": 768.0, "gdn_state_slots": 12.0}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: ours)
    assert 0 < mod.read(ctx) < 100
    reduced.op_seconds = lambda rx: 0.0
    assert mod.read(ctx) is None


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
                          counters=dict(token_budget=256))
    # a trace whose steps carry another family's counts (KDA's), and one
    # with none
    for counts in ({"steps": 3.0, "rows": 300.0, "kda_rows": 300.0,
                    "kda_state_slots": 12.0}, None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    counts = {"steps": 3.0, "rows": 3 * 256.0, "gdn_rows": 3 * 256.0,
              "gdn_state_slots": 12.0, "state_resets": 0.0}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
    # with its counts but no time of its calls: still nothing
    assert mod.read(ctx) is None
    reduced.op_seconds = lambda rx: 3 * 2.7e-3 if "gated_delta" in rx else 0.0
    if name.endswith("_pct"):
        # another family (no cost function): nothing
        other = SimpleNamespace(**{**vars(ctx), "family": SimpleNamespace()})
        assert mod.read(other) is None
    # with its counts and its calls' time it reads a positive number, a
    # share under 100: 3 steps of 2.7 ms
    value = mod.read(ctx)
    assert value is not None and value > 0
    if name.endswith("_pct"):
        assert value < 100.0
    else:
        assert value == pytest.approx(2.7)


@pytest.mark.parametrize("fault", ["decay_off", "value_group_off",
                                   "attn_gate_off", "shared_gate_off",
                                   "norm_centre_off", "weights_int8"])
def test_a_fault_changes_the_reference_at_the_rehearsals_sizes(cfg, fam,
                                                              fault):
    """The reference the benchmark judges by, at the rehearsal's sizes with
    weights drawn as ``run.py`` draws their kinds (the decay's ``A_log`` and
    ``dt_bias`` N(0, 0.02)): a name of ``FAULTS`` moves its logits
    (tests/test_qwen3_next.py holds every one of them at the model's own
    draw, where ``gate_clamped`` bites too)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.run import build_model, merged

    config = merged(cfg, cfg["rehearse"])
    model, shape = build_model(config["engine"]), fam.shape_of(config)
    shapes = jax.eval_shape(lambda k: model.init(k, dtype=jnp.float32),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(treedef, [
        jnp.ones(a.shape) if getattr(path[-1], "key", "") == "scale"
        else 0.1 * jax.random.normal(jax.random.PRNGKey(i), a.shape)
        for i, (path, a) in enumerate(leaves)])
    ids = np.random.default_rng(0).integers(0, shape.vocab, 40)
    sound = np.asarray(fam.logits(params, ids, shape))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, **fam.faulted(params, fault, shape)))
    assert np.isfinite(sound).all()
    assert np.abs(broken - sound).max() > 1e-3, fault
    with pytest.raises(ValueError, match="no fault"):
        fam.faulted(params, "no_such_fault", shape)


def test_rehearsal_passes_with_no_failed_request():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "6200000011", "--seconds", "3",
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
