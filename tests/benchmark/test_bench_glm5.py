"""The cell ``glm53flash-longreason8`` on the CPU: its configuration file holds
the catalog row's numbers, the program builds the model the file describes,
its manifest entries are found BY NAME and lie after the accepted ones (never
"the last": the next PR's append must not redden this file), its rehearsal
runs end to end with no failed request, no request of its mix can be evicted
or cut, its cost functions grow with the work, and its readers say nothing on
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
CELL, CONFIG, MIX = "glm53flash-longreason8", "glm-5.3-flash", "longreason-8"
READERS = ("pooled_indexer_roofline_pct", "nope_sparse_attention_roofline_pct",
           "residual_mix_ms_per_step", "residual_mix_roofline_pct")
# the accepted metrics the cell joins, each of whose readers reads it right
JOINED = ("kda_ms_per_step", "kda_roofline_pct", "indexer_ms_per_step",
          "sparse_attention_ms_per_step", "selected_keys_skipped_pct",
          "expert_ms_per_step", "experts_touched_pct")
# the cells and the configurations the benchmark had before this one
ACCEPTED_CELLS = (
    "bloom560m-pretrain-2k", "mixtral8x7b-chat", "bloom1b7-zero3-dp4",
    "mixtral8x7b-longdoc", "mellum2-12b-mixedlen", "deepseekv32-longctx",
    "glm47flash-pretrain-4k", "minicpm-sala-longctx128k",
    "ling3flash-reason16", "brumby14b-reason16")
ACCEPTED_CONFIGS = (
    "bloom-560m", "mixtral-8x7b", "bloom-1b7", "mellum2-12b-a2.5b",
    "deepseek-v3.2", "glm-4.7-flash", "minicpm-sala", "ling-3.0-flash",
    "brumby-14b")
REDUCED = {"num_hidden_layers": (45, 5), "first_k_dense_replace": (3, 1),
           "n_routed_experts": (288, 36), "vocab_size": (154880, 19360),
           "num_nextn_predict_layers": (1, 0)}
# the catalog row's ``config`` (model-configs guide, GLM-5.3-Flash): the
# numbers and flags at its top level, and its one nested group
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hc_eps": 1e-06,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "head_dim": 0,
    "hidden_act": "silu", "hidden_size": 4096, "index_head_dim": 128,
    "index_kpool": 4, "index_kpool_always_select_tail": True,
    "index_kpool_compress": True, "index_n_heads": 32, "index_topk": 2048,
    "index_share_for_mtp_iteration": True, "indexer_rope_interleave": True,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576, "mhc": True, "mla_use_nope": True,
    "model_type": "glm5_next_text", "moe_intermediate_size": 2048,
    "n_group": 1, "n_routed_experts": 288, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 45,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 256, "qk_nope_head_dim": 256,
    "qk_rope_head_dim": 0, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "swiglu_limit": 10, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880,
}
LINEAR = {"num_heads": 64, "gate_lower_bound": -5, "head_dim": 128,
          "short_conv_kernel_size": 4}
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


def test_the_configuration_file_holds_the_catalog_rows_numbers(cfg, fam):
    assert cfg["reduced"] == list(REDUCED)
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert (cfg["published"][key], cfg[key]) == REDUCED[key], key
            assert value == REDUCED[key][0]
        else:
            assert cfg[key] == value, key
    lin = cfg["linear_attn_config"]
    assert {k: lin[k] for k in LINEAR} == LINEAR
    full = list(range(3, 45, 4))  # every fourth published layer
    assert lin["full_attn_layers"] == full
    assert lin["kda_layers"] == [i for i in range(45) if i not in full]
    assert cfg["layer_types"] == [
        "deepseek_sparse_attention" if i in full else "linear_attention"
        for i in range(45)]
    assert cfg["indexer_types"] == ["full"] * 45
    assert cfg["mlp_layer_types"] == ["dense"] * 3 + ["sparse"] * 42
    assert cfg["layer_ids"] == [0, 4, 5, 6, 7]  # dense K, then K K K D
    assert cfg["source"] == ("https://huggingface.co/zai-org/GLM-5.3-Flash/"
                             "blob/main/config.json")
    assert set(cfg["assumed"]) >= {
        "streams_begin_and_end", "hc_equations", "hc_draws", "kda_equations",
        "kda_gate_rank", "index_rope_dim", "index_rope_theta",
        "indexer_rotary", "index_kpool", "selection_ties", "indexer_types",
        "swiglu_limit", "router", "precision", "not_built"}
    assert (cfg["assumed"]["kda_gate_rank"], cfg["assumed"]["index_rope_dim"],
            cfg["assumed"]["index_rope_theta"]) == (128, 64, 1000000)
    for part in ("8 chips share each layer", "member 0", "experts 0-35",
                 "published layers 0 and 4-7", "4,718,150,030", "9.44 GB",
                 "10.2 GB"):
        assert part in cfg["deployment"], part
    eng = cfg["engine"]
    assert eng["entry"] == "init_serving"
    assert eng["model"] == dict(
        factory="deepspeed_tpu.models:glm5", size="glm-5.3-flash",
        overrides=dict(layer_ids=[0, 4, 5, 6, 7], num_experts=36,
                       moe_routed_experts=288, vocab_size=19360))
    assert eng["init_inference"] == dict(
        dtype="bfloat16", replace_with_kernel_inject=True)
    s = fam.shape_of(cfg)
    assert isinstance(s, flops.Shape)
    assert (s.d, s.layers, s.heads, s.kv_heads, s.hd, s.ffn, s.vocab,
            s.experts, s.top_k, s.gated, s.tied) == (
                4096, 4, 64, 64, 128, 2048, 19360, 36, 8, True, False)
    assert (s.count("kda"), s.count("mla"), s.dense_layers, s.routed,
            s.streams, s.kpool, s.index_topk, s.limit) == (
                4, 1, 1, 288, 4, 4, 2048, 10.0)
    assert round(flops.stored_params(s) / 1e9, 2) == 4.72


def test_the_program_builds_the_model_the_file_describes(cfg, fam):
    """The factory with the file's overrides gives the sizes ``run.py``'s
    ``check_shape`` compares, the model's own count is the file's, and what
    the arena keeps is the file's bytes."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import glm5
    from deepspeed_tpu.models.decoding import init_paged_cache

    eng = cfg["engine"]["model"]
    c = glm5(eng["size"], **eng["overrides"]).config
    s = fam.shape_of(cfg)
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd, c.ffn,
            c.vocab_size, c.num_experts, c.moe_top_k,
            bool(c.tie_embeddings)) == (
                s.d, s.layers, s.heads, s.kv_heads, s.hd, s.ffn, s.vocab,
                s.experts, s.top_k, s.tied)
    assert c.mixer_types == ("kda",) * 4 + ("mla",)
    assert (c.q_latent_dim, c.kv_latent_dim, c.qk_nope_dim, c.qk_rope_dim,
            c.v_head_dim, c.latent_width) == (1536, 512, 256, 0, 256, 512)
    assert (c.index_heads, c.index_dim, c.index_topk, c.index_kpool,
            c.index_rope_dim, c.rope_theta) == (
                s.index_heads, s.index_dim, s.index_topk, s.kpool,
                s.index_rope, s.rope_theta)
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.swiglu_limit,
            c.kda_gate_rank, c.norm_eps, c.moe_routed_scale) == (
                s.streams, s.sinkhorn, s.hc_eps, s.limit, s.gate_rank, s.eps,
                s.routed_scale)
    assert (c.routed_experts, c.lead_dense_ffn, c.moe_shared_width) == (
        s.routed, s.dense_ffn, s.shared)
    assert c.num_params() == 4_718_150_030
    srv = cfg["engine"]["serving"]
    pools = jax.eval_shape(lambda: init_paged_cache(
        c, srv["num_pages"], srv["page_size"], jnp.bfloat16,
        max_slots=srv["max_slots"]))
    size = {k: int(np.prod(a.shape)) * a.dtype.itemsize
            for k, a in pools.items()}
    assert set(size) == {"kv", "ki", "state", "conv", "ki_tail"}
    assert pools["ki"].shape[2] * 4 == pools["kv"].shape[2] == 16
    assert [round(size[k] / 1e9, 3) for k in ("kv", "ki", "state", "conv")
            ] == [0.555, 0.035, 0.134, 0.005]
    assert round((2 * c.num_params() + sum(size.values())) / 1e9, 1) == 10.2


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
    for part in ("3.6 rows/expert", "28 deployed", "host x9", "misses"):
        assert part in cell["why"], part
    # the faults neither sample sees on the chip (traffic file, "NOT seen")
    for fault in ("pool_off", "tail_dropped", "block_visible_early",
                  "selection_off", "clamp_off", "state_not_reset"):
        assert fault in cell["why"].split("misses")[1], fault
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
        # after the metrics the last accepted cell brought
        assert metric_names.index(name) > metric_names.index(
            "retention_roofline_pct")
    assert metrics[READERS[0]]["layer"] == metrics[READERS[1]]["layer"] == (
        metrics["indexer_ms_per_step"]["layer"])
    assert metrics[READERS[2]]["layer"] == metrics[READERS[3]]["layer"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def listed_after_brumbys(workloads):
        return CELL in workloads and (
            "brumby14b-reason16" not in workloads
            or workloads.index(CELL) > workloads.index("brumby14b-reason16"))

    assert listed_after_brumbys(e2e["serve_tokens_per_s"]["workloads"])
    assert "workloads" not in e2e["setup_s"]
    tput = [m for m in manifest["per_layer"] if m["name"].endswith(".tput")]
    assert len(tput) == 7
    for m in tput:
        assert listed_after_brumbys(m["workloads"]), m["name"]
    for name in JOINED:
        assert metrics[name]["workloads"][-1] == CELL or (
            CELL in metrics[name]["workloads"]), name
    # their shares of a roofline divide by ALL layers: wrong for one indexed
    # layer in five, so the cell brings its own and stays off these
    for name in ("indexer_roofline_pct", "sparse_attention_roofline_pct",
                 "latent_attention_roofline_pct"):
        assert CELL not in metrics[name]["workloads"]
    assert len(json.dumps(manifest)) < 64 * 1024


def test_rehearsal_passes_with_no_failed_request():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "5200000011", "--seconds", "3",
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


def test_no_request_of_the_mix_can_be_evicted_or_cut(cfg, mix, fam):
    srv = cfg["engine"]["serving"]
    longctx = load("benchmarks", "traffic", "longctx.json")
    reason = load("benchmarks", "traffic", "reason-16.json")
    # longctx's prompts under reason-16's answers
    assert mix["prompt"] == longctx["prompt"]
    assert mix["answer"] == reason["answer"]
    assert (mix["kind"], mix["clients"], mix["replay_requests"],
            mix["schedule_seed"]) == ("closed_loop", 8, 64, 5201)
    assert (srv["max_slots"], srv["token_budget"]) == (8, 128)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest <= srv["max_tokens"] == 67584
    assert srv["max_tokens"] % srv["token_budget"] == 0
    assert mix["clients"] == srv["max_slots"]  # callers = slots: no queue
    # the pools hold every slot at full length with the chunk in flight
    per_slot = -(-(srv["max_tokens"] + srv["token_budget"])
                 // srv["page_size"])
    assert per_slot == 4224 + 8
    assert srv["num_pages"] == srv["max_slots"] * per_slot == 33856
    assert srv["prefix_cache"] is False
    cc = mix["correctness"]
    assert cc["new_tokens"] == 24 and len(cc["prompts"]) == 2
    reach = 4 * 2048 + 3  # the selection's: 2,048 blocks and a tail
    assert cc["prompts"][0] < reach < cc["prompts"][1] / 2
    assert len(cc["precision"]["prompts"]) == srv["max_slots"]
    assert all(reach < n < reach + 512 for n in cc["precision"]["prompts"])
    for n in (*cc["prompts"], *cc["precision"]["prompts"]):
        assert n % 4 and n % 16 and n % 128
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 64 and pairs.sum(1).max() <= srv["max_tokens"]
    assert pairs[:, 0].min() >= 8192  # every context past the reach's edge
    # the worst drain: at the window's close the 8 callers hold at most the
    # 8 longest requests of the set, whole. By tokens at the slowest rate a
    # sound run has shown, and by steps (a decoding slot takes one row a
    # step) at the slowest step: both inside the grace
    worst = np.sort(pairs.sum(1))[-8:].sum()
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    steps = (np.sort(pairs[:, 0])[-8:].sum() / srv["token_budget"]
             + mix["answer"]["max"])
    assert steps * mix["drain_step_ms"] / 1e3 < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]
    # the traffic file names what the chip's samples refuse and miss
    for fault in fam.FAULTS:
        assert fault in cc["why"] + cc["precision"]["why"], fault


def test_the_cost_functions_grow_with_the_work(cfg, fam):
    s = fam.shape_of(cfg)
    # KDA: 8 decoding slots are memory-bound on their float32 states
    f, b = fam.kda_cost(s, rows=8, state_slots=8)
    assert f == 7 * 64 * 128 * 128 * 8
    assert b == 8 * 2 * 64 * 128 * 128 * 4 + 8 * (
        4 * 8192 * 2 + 8192 * 4 + 64 * 4)
    assert flops.roofline_seconds(f, b, PEAK)[1] == "memory"
    f2, b2 = fam.kda_cost(s, rows=128, state_slots=8)
    assert f2 > f and b2 > b
    # the indexer: a pair costs 32 heads x 128 x 2; a pooled key is 256 B
    f, b = fam.indexer_cost(s, 1000, 100, 8)
    assert f == 2 * 32 * 128 * 1000
    assert b == 128 * 2 * 100 + 32 * (128 * 2 + 4) * 8
    assert fam.indexer_cost(s, 2000, 100, 8)[0] == 2 * f
    assert fam.indexer_cost(s, 1000, 200, 8)[1] > b
    # the walk: a pair costs 64 heads x (512 + 512) x 2; a row is 1,024 B
    f, b = fam.sparse_attention_cost(s, 8195, 8195, 1)
    assert f == 2 * 64 * 1024 * 8195
    assert b == 1024 * 8195 + 64 * 1024 * 2
    assert fam.sparse_attention_cost(s, 2 * 8195, 8195, 1)[0] == 2 * f
    # the mixes: ten boundaries read and write the four streams
    f, b = fam.residual_mix_cost(s, 128)
    assert b == 10 * (2 * 4 + 2) * 4096 * 2 * 128
    assert f == 10 * 128 * 2 * (16384 * 24 + 16384 + 4 * 5 * 4096)
    assert flops.roofline_seconds(f, b, PEAK)[1] == "memory"
    assert fam.residual_mix_cost(s, 256) == (2 * f, 2 * b)
    # the whole model's count: the reach bounds attention, not the context
    assert s.attention_flops_per_token(70000) - s.attention_flops_per_token(
        60000) == 2 * 32 * 128 * 10000 / 4


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
                          shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
                          counters=dict(token_budget=128))
    monkeypatch.setattr(kinds_trace, "operand_seconds", lambda c, rx: None)
    # a trace whose steps carry another family's counts (DeepSeek's: an
    # indexer whose keys are not pooled), and one with none
    for counts in ({"steps": 3.0, "context_keys": 9e5, "index_keys": 6e4,
                    "attended_sparse": 2e4, "chosen_min": 6e3, "rows": 300.0},
                   None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    counts = {"steps": 3.0, "rows": 3 * 128.0, "context_keys": 3 * 2.5e6,
              "index_keys": 3 * 6.2e5, "index_rows": 3 * 5.0e4,
              "attended_sparse": 3 * 1.0e6, "tail_keys": 3 * 190.0,
              "chosen_min": 3 * 6.5e4, "residual_streams": 12.0}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
    # with its counts but no time of its calls: still nothing
    assert mod.read(ctx) is None
    if name.endswith("_pct"):  # another family (no cost function): nothing
        other = SimpleNamespace(**{**vars(ctx), "family": SimpleNamespace()})
        assert mod.read(other) is None
    # with its counts and its calls' time it reads a positive number, a
    # share under 100: 3 steps of 5 ms of either
    reduced.op_seconds = lambda rx: 3 * 5e-3
    seen = []
    monkeypatch.setattr(kinds_trace, "operand_seconds",
                        lambda c, rx: seen.append(rx) or 3 * 5e-3)
    value = mod.read(ctx)
    assert value is not None and value > 0
    if name.endswith("_pct"):
        assert value < 100.0
    if name.startswith("residual"):
        assert seen == [r"\[4,(1,)?128,4096\]"]
        assert value == pytest.approx(
            5.0 if name.endswith("ms_per_step")
            else 100 * (10 * 10 * 4096 * 2 * 128 / 819e9) / 5e-3)
