"""The cell ``ling3flash-reason16`` on the CPU: its configuration file holds
the catalog row's numbers, its manifest entries (looked up by NAME) name
files that exist and only append, its rehearsal runs end to end with no failed
request, no request of its mix can be evicted or cut, its kernels' costs grow
with the work, and its readers say nothing on a trace without their calls."""

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
CELL, CONFIG, MIX = "ling3flash-reason16", "ling-3.0-flash", "reason-16"
READERS = ("kda_ms_per_step", "kda_roofline_pct",
           "latent_attention_ms_per_step", "latent_attention_roofline_pct",
           "experts_touched_pct")
REDUCED = {"num_hidden_layers": (42, 13), "first_k_dense_replace": (2, 1),
           "num_experts": (512, 64), "vocab_size": (157184, 19648),
           "num_nextn_predict_layers": (1, 0)}
# the catalog row's ``config`` (model-configs guide, Ling-3.0-flash)
CATALOG = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid",
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
    assert cfg["reduced"] == list(REDUCED)
    for key, value in CATALOG.items():  # every key but those in ``reduced``
        if key in REDUCED:
            assert (cfg["published"][key], cfg[key]) == REDUCED[key], key
            assert value == REDUCED[key][0]
        else:
            assert cfg[key] == value, key
    # the cut: the leading dense layer once, then two whole periods of five
    # KDA layers and one latent layer, each under its own published index
    ids = cfg["layer_ids"]
    assert ids == [0, *range(6, 18)]
    assert [(i + 1) % 6 == 0 for i in ids].count(True) == 2
    # no layer kept clamps its SwiGLU (the program builds no clamp)
    assert all(cfg["expert_swiglu_limit_list"][i] == 0
               and cfg["share_expert_swiglu_limit_list"][i] == 0 for i in ids)
    assert cfg["source"].startswith("https://huggingface.co/inclusionAI/")
    assert set(cfg["assumed"]) >= {
        "kda_equations", "safe_gate", "qk_norm_kda", "qk_norm_mla", "rotary",
        "output_gate", "kda_draws", "router", "swiglu_limits", "unused",
        "depth", "precision"}
    for part in ("8 chips share each layer", "ONE routing group of 64",
                 "vocabulary in 8 slices", "published layers 0 and 6-17",
                 "Expected load", "against 16 in the deployment"):
        assert part in cfg["deployment"], part
    over = cfg["engine"]["model"]["overrides"]
    assert over == dict(layer_ids=ids, num_experts=64, moe_routed_experts=512,
                        vocab_size=19648)
    assert cfg["engine"]["model"]["factory"] == "deepspeed_tpu.models:ling"
    assert cfg["engine"]["init_inference"] == dict(
        dtype="bfloat16", replace_with_kernel_inject=True)
    s = fam.shape_of(cfg)
    assert isinstance(s, flops.Shape)
    assert (s.layers, s.heads, s.kv_heads, s.hd, s.ffn, s.vocab, s.experts,
            s.top_k, s.routed, s.groups, s.groups_kept, s.dense_ffn, s.shared,
            s.count("kda"), s.count("latent")) == (
                12, 32, 32, 128, 768, 19648, 64, 8, 512, 8, 4, 6144, 768,
                11, 2)
    # 11 x 52.6 M + 2 x 32.0 M of mixers, 37.7 M the dense MLP, 12 x (377.5
    # M of experts + 5.9 M shared + 1.3 M router), 100.6 M the vocabulary
    # slice twice (norm vectors, taps and biases left out): 5.41 B, 10.8 GB
    assert round(flops.stored_params(s) / 1e9, 2) == 5.41


def test_the_program_builds_the_model_the_file_describes(cfg, fam):
    """The factory with the file's overrides gives the sizes ``run.py``'s
    ``check_shape`` compares, and the model's own count is the file's."""
    from deepspeed_tpu.models import ling

    eng = cfg["engine"]["model"]
    c = ling(eng["size"], **eng["overrides"]).config
    s = fam.shape_of(cfg)
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd, c.ffn,
            c.vocab_size, c.num_experts, c.moe_top_k) == (
                s.d, s.layers, s.heads, s.kv_heads, s.hd, s.ffn, s.vocab,
                s.experts, s.top_k)
    assert c.mixer_types.count("kda") == 11 and c.lead_dense_layers == 1
    assert c.num_params() == 5_407_252_704
    assert "5,407,252,704" in cfg["deployment"]


def test_the_manifest_entries_are_found_by_name_and_only_append(cfg):
    manifest = load("BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert os.path.isfile(os.path.join(ROOT, entry["file"]))
    assert len(entry["why"]) <= 200
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    for part in ("traffic/" + MIX + ".json", "families/" + cfg["family"] + ".py"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", part))
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert metrics["experts_touched_pct"]["layer"] == (
        metrics["expert_ms_per_step"]["layer"])
    assert metrics["kda_ms_per_step"]["layer"] == (
        metrics["kda_roofline_pct"]["layer"])
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s"]["workloads"][-1] == CELL
    assert metrics["expert_ms_per_step"]["workloads"][-1] == CELL
    for name, m in metrics.items():
        if name.endswith(".tput"):
            assert m["workloads"][-1] == CELL, name
    # appended, not inserted: the new entries are the lists' last
    assert manifest["configs"][-1]["name"] == CONFIG
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"]][-len(READERS):] == list(
        READERS)
    assert len(json.dumps(manifest)) < 64 * 1024


def test_rehearsal_passes_with_no_failed_request():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000011", "--seconds", "3",
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
    # the traffic file's parameters, as the issue gives them
    assert (mix["kind"], mix["clients"], mix["replay_requests"],
            mix["schedule_seed"]) == ("closed_loop", 16, 256, 4301)
    assert mix["prompt"] == dict(median=2048, sigma=1.0, min=256, max=16384)
    assert mix["answer"] == dict(median=1024, sigma=0.5, min=256, max=2048)
    assert (srv["max_slots"], srv["token_budget"], srv["page_size"]) == (
        16, 128, 16)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest <= srv["max_tokens"] == 18432
    assert srv["max_tokens"] % srv["token_budget"] == 0
    assert mix["clients"] == srv["max_slots"]  # callers = slots: no queue
    # every slot at its full length at once, the chunk in flight included
    pages = -(-(longest + srv["token_budget"]) // srv["page_size"])
    assert srv["max_slots"] * pages <= srv["num_pages"]
    assert srv["prefix_cache"] is False
    cc = mix["correctness"]
    for n in (*cc["prompts"], *cc["precision"]["prompts"]):
        assert n % 16 and n % 64 and n % 128
    # one sample inside one chunk, one across many chunks and pages
    assert min(cc["prompts"]) < srv["token_budget"]
    assert max(cc["prompts"]) > 25 * srv["token_budget"]
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


def test_the_kernels_costs_grow_with_the_work(cfg, fam):
    s = fam.shape_of(cfg)
    # one decode row in each of 2 live slots: decay, erase, write and
    # read-out of 32 heads x 128 x 128 (7 flops an entry), 2 states of 2 MiB
    # read and written, 2 rows of q, k, v, o (bf16), g (float32) and beta
    f, b = fam.kda_cost(s, rows=2, state_slots=2)
    assert f == 2 * 7 * 32 * 128 * 128
    assert b == 2 * 2 * 32 * 128 * 128 * 4 + 2 * (
        4 * 4096 * 2 + 4096 * 4 + 32 * 4)
    f2, b2 = fam.kda_cost(s, rows=128, state_slots=2)
    f3, b3 = fam.kda_cost(s, rows=128, state_slots=16)
    assert f2 > f and b2 > b and f3 == f2 and b3 > b2
    # a decode row at position 9,999 scores 10,000 latents of 576 and sums
    # 10,000 of 512 for 32 heads; the latents are read once
    f, b = fam.latent_walk_cost(s, context_keys=10000, keys_walked=10000,
                                rows=1)
    assert f == 2 * 32 * (576 + 512) * 10000
    assert b == 10000 * 576 * 2 + 32 * (576 + 512) * 2
    f2, b2 = fam.latent_walk_cost(s, context_keys=20000, keys_walked=10000,
                                  rows=2)
    assert f2 == 2 * f and b2 > b
    # the whole model's count follows the layers as run
    assert s.attention_flops_per_token(4096) == (
        2 * 2 * 32 * (2 * 512 + 64) * 4096 + 11 * 8 * 32 * 128 * 128)


def test_the_engines_counters_are_the_hand_counts():
    """The plan's vectors -> the counts the readers take from the trace."""
    import deepspeed_tpu.serving.engine as eng

    me = SimpleNamespace(
        config=SimpleNamespace(block_sparse=None, num_experts=64,
                               num_layers=12),
        metrics=SimpleNamespace(context_keys=0, state_resets=0),
        _experts_touched=301)
    me._count_state_and_latent = (
        lambda plan: eng.ServingEngine._count_state_and_latent(me, plan))
    plan = SimpleNamespace(
        start_pos=np.array([0, 9999, 100, 0]),
        num_new=np.array([112, 1, 1, 0]))
    got = eng.ServingEngine._count_mixers(me, plan)
    assert (got["kda_rows"], got["kda_state_slots"], got["state_resets"]) == (
        114, 3, 1)
    assert got["latent_rows"] == 114
    assert got["latent_keys_walked"] == 112 + 10000 + 101
    assert got["context_keys"] == 112 * 113 // 2 + 10000 + 101
    assert (got["experts_touched"], got["experts_held"]) == (301, 64 * 12)
    assert me.metrics.state_resets == 1


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
    for counts in ({"steps": 3.0, "rows": 40.0, "state_slots": 9.0}, None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    # with its counts and its calls' time it reads a share in (0, 100]
    counts = {"steps": 3.0, "kda_rows": 48.0, "kda_state_slots": 48.0,
              "latent_rows": 48.0, "latent_keys_walked": 3 * 16 * 9000.0,
              "context_keys": 3 * 16 * 9000.0, "experts_touched": 1500.0,
              "experts_held": 3 * 768.0}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
    reduced.op_seconds = lambda rx: 0.03
    value = mod.read(ctx)
    assert value is not None and value > 0
    if name.endswith("_pct"):
        assert value <= 100.0
