"""The cell ``deepseekv32-longctx`` on the CPU: its rehearsal runs end to
end with no failed request, the serving comparison refuses the faults it can
see at the rehearsal's size (and names those it cannot), its configuration
file holds the catalog row's numbers, no request of its mix can be evicted or
cut, and its kernels' costs are the hand counts."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import flops, loadgen, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "deepseekv32-longctx"
# what 16 + 32 served tokens of the tiny float32 model show at every seed ...
SEEN = ("selection_off", "selection_recent", "indexer_rope_off",
        "shared_expert_off", "scaling_off", "page_dropped")
# ... and what they do not show at every seed: a served token is judged by
# the reference's argmax, and these move the tiny model's logits by less than
# the gap to its runner-up at some seeds (the 0.02 selection bias weighs 2 %;
# one position of rotary phase; rounding to 8 bits; a group that would have
# been kept anyway). tests/test_deepseek.py holds each by the logits
# themselves (test_every_fault_moves_the_reference).
UNSEEN = ("bias_in_weight", "group_limit_off", "latent_rope_off_by_one",
          "weights_int8")
# the catalog row's ``config`` (model-configs guide, DeepSeek-V3.2)
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}


def run(*args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("benchmarks", "configs", "deepseek-v3.2.json")


@pytest.fixture(scope="module")
def mix():
    return load("benchmarks", "traffic", "longctx.json")


def test_rehearsal_passes_with_no_failed_request():
    # (--trace 0: the rehearsals of the other cells' tests trace into the
    # same .bench_out/trace, and a runner may have them side by side; the
    # readers are driven from a hand-written trace below)
    p = run("--workload", CELL, "--seed", "3000000011", "--seconds", "3",
            "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["workload"] == CELL
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "metrics" not in last and "correct" not in last
    assert last["metric_names"] == ["serve_tokens_per_s", "setup_s"]
    assert "compilations inside the window: 0" in p.stdout
    assert "share rule: 16 within 0.0005" in p.stdout
    assert "exact rule: at least 100.0% = 32" in p.stdout


def test_the_check_refuses_the_faults_it_can_see_and_names_the_rest():
    fam = reference.family("deepseek")
    assert sorted(SEEN + UNSEEN) == sorted(fam.FAULTS)
    p = run("--workload", CELL, "--rehearse", "--check-seeds", "3000000041,11",
            "--inject", ",".join(SEEN))
    checks = json.loads(p.stdout.strip().splitlines()[-1])["checks"]
    assert [(c["seed"], c["inject"]) for c in checks] == [
        (seed, inject) for seed in (3000000041, 11)
        for inject in (None, *SEEN)]
    for c in checks:
        assert c["correct"] == (c["inject"] is None), c
        assert c["tokens"] == 16 and c["precision"]["tokens"] == 32
    assert p.returncode == 1  # something read incorrect


def test_the_configuration_file_holds_the_catalog_rows_numbers(cfg):
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert cfg["reduced"] == reduced
    for key, value in CATALOG.items():  # every key but those in ``reduced``
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 16, 16160, 0)
    # the floors: four routed layers after the dense one, 8 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and 8 * cfg["vocab_size"] >= 129280
    assert cfg["source"].startswith("https://huggingface.co/deepseek-ai/")
    assert set(cfg["assumed"]) >= {"precision", "hadamard", "rotary_layout",
                                   "mtp", "depth", "experts", "vocabulary",
                                   "selection_bias"}
    assert "16 chips share each layer" in cfg["deployment"]
    over = cfg["engine"]["model"]["overrides"]
    assert (over["lead_dense_layers"] + over["num_layers"],
            over["num_experts"], over["moe_routed_experts"],
            over["vocab_size"]) == (5, 16, 256, 16160)
    manifest = load("BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}["deepseek-v3.2"]
    assert entry["reduced"] == reduced and entry["source"] == cfg["source"]


def test_no_request_of_the_mix_can_be_evicted_or_cut(cfg, mix):
    srv = cfg["engine"]["serving"]
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest <= srv["max_tokens"]
    assert srv["max_tokens"] % srv["token_budget"] == 0
    assert mix["clients"] <= srv["max_slots"]  # nothing ever queues
    # every slot at its full length at once, the chunk in flight included
    pages = -(-(longest + srv["token_budget"]) // srv["page_size"])
    assert srv["max_slots"] * pages <= srv["num_pages"]
    assert srv["prefix_cache"] is False
    cc = mix["correctness"]
    for n in (*cc["prompts"], *cc["precision"]["prompts"]):
        assert n % 16 and n % 128
        assert n + cc["precision"]["new_tokens"] <= srv["max_tokens"]
    assert min(cc["prompts"]) < cfg["index_topk"]
    assert max(cc["prompts"]) > 9 * cfg["index_topk"]
    assert min(cc["precision"]["prompts"]) > cfg["index_topk"]
    # the replay set is one fixed schedule; every context is 4 to 32 times
    # index_topk
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 24 and pairs[:, 0].min() >= 4 * cfg["index_topk"]
    assert pairs[:, 0].max() <= 32 * cfg["index_topk"]
    assert 18000 < pairs[:, 0].mean() < 21500
    # the worst drain: at the window's close the four callers hold at most
    # the four longest requests of the set, whole; at the slowest rate a
    # sound run has shown (benchmarks/traffic/longctx.json says which) they
    # are served inside the grace
    worst = np.sort(pairs.sum(1))[-4:].sum()
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]


def test_shape_and_the_kernels_costs_against_hand_counts(cfg):
    fam = reference.family("deepseek")
    s = fam.shape_of(cfg)
    assert isinstance(s, flops.Shape)
    assert (s.layers, s.dense_layers, s.experts, s.routed, s.hd, s.kv_heads,
            s.ffn, s.dense_ffn, s.shared_ffn) == (
        4, 1, 16, 256, 192, 1, 2048, 18432, 2048)
    # a routed layer: attention 187.1 M, indexer 14.0 M, shared 44.0 M,
    # router 1.8 M, and 16 experts of 44.0 M
    outside = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
               + 512 * 128 * 256 + 16384 * 7168
               + 1536 * 64 * 128 + 7168 * 128 + 7168 * 64
               + 3 * 7168 * 2048 + 7168 * 256)
    assert round(outside / 1e6, 1) == 246.9
    assert s.layer_matmul_params(active=False) == outside + 16 * 3 * 7168 * 2048
    assert s.mscale == pytest.approx(0.1 * np.log(40) + 1)
    # indexer: 2 x 64 x 128 a (query, key at or before it) pair; the keys'
    # 128 bf16 values once, the queries' 64 x 128 bf16 and 64 float32 weights
    need, nbytes = fam.indexer_cost(s, 1000, 2048, 128)
    assert need == 2 * 64 * 128 * 1000
    assert nbytes == 128 * 2 * 2048 + 64 * (128 * 2 + 4) * 128
    # attention: per pair and head 576 to score and 512 to sum, x 2; each
    # chosen row's 576 bf16 values once; q (576) in and out (512) a head
    need, nbytes = fam.sparse_attention_cost(s, 1000, 2048, 128)
    assert need == 2 * 128 * (576 + 512) * 1000
    assert nbytes == 576 * 2 * 2048 + 128 * (576 + 512) * 2 * 128
    # one query over 65,536 cached tokens: the indexer's work is 32 times
    # the context it hands attention
    per_tok = s.attention_flops_per_token(65536) / 5
    assert per_tok == 2 * 64 * 128 * 65536 + 2 * 128 * (192 + 128) * 2048


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_the_references_selection_is_the_k_largest_ties_to_the_lower(ties):
    """The reference finds a row's k-th largest score by bisection on its
    bits: the set a stable sort chooses, signed zeros and ties included."""
    import jax
    import jax.numpy as jnp

    fam = reference.family("deepseek")
    rng = np.random.default_rng(5)
    score = rng.normal(size=(6, 200)).astype(np.float32)
    if ties:
        score = np.round(score * 2) / 2  # many equal values, -0.0 among them
    qpos = np.array([3, 11, 12, 50, 150, 199])
    seen = np.arange(200)[None, :] <= qpos[:, None]
    got = np.asarray(fam._best(jnp.asarray(score), jnp.asarray(seen), 12))
    canon = np.where(score == 0.0, 0.0, score)
    for r, row in enumerate(got):
        n = qpos[r] + 1
        order = np.argsort(-canon[r, :n], kind="stable")[:12]
        assert sorted(np.flatnonzero(row)) == sorted(order), r
    assert got.sum(1).tolist() == [4, 12, 12, 12, 12, 12]


def test_the_new_readers_read_the_programs_counts_and_named_calls(cfg):
    """A trace by hand: two annotated steps with the counts
    ``ServingEngine._count_selected`` gives, and device time under the three
    calls' names. Each reader returns its number; from a trace without the
    annotation's counts (the parent's) it returns nothing and does not
    raise."""
    import importlib.util
    from types import SimpleNamespace

    from benchmarks import trace_reduce as tr

    fam = reference.family("deepseek")
    peak = load("benchmarks", "peaks.json")["TPU v5 lite"]

    def event(name, start, dur, **stats):
        return tr.Event(name, start, dur, stats)

    ops = [event("%indexer_scores.3 = f32[4,136,128,512] custom-call(...), "
                 'custom_call_target="tpu_custom_call"', 0, 2e6),
           event("%selection_topk.3 = s32[4,128,128] custom-call(...), "
                 'custom_call_target="tpu_custom_call"', 2e6, 3e6),
           event("%sparse_latent_attention.3 = bf16[4,16384,512] "
                 'custom-call(...), custom_call_target="tpu_custom_call"',
                 5e6, 20e6),
           event("%fusion.1 = bf16[16,128,2048] fusion(...)", 25e6, 5e6)]
    counts = dict(rows=128, context_keys=128 * 20000, attended_sparse=128 * 2048,
                  index_keys=20480, chosen_min=2048)

    def ctx_of(step_stats):
        host = [event("serve/device_step", 0, 30e6, **step_stats),
                event("serve/device_step", 30e6, 30e6, **step_stats),
                event("bench/engine.step", 0, 30e6),
                event("bench/engine.step", 30e6, 30e6)]
        trace = {"/device:TPU:0": {tr.OPS_LINE: ops},
                 tr.HOST_PLANE: {"python": host}}
        return SimpleNamespace(
            reduced=tr.reduce_trace(trace), full_trace=trace, family=fam,
            shape=fam.shape_of(cfg), flops=flops, peak=peak, root=ROOT)

    def read(name, ctx):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "benchmarks", "layer_metrics",
                               name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)

    ctx = ctx_of(counts)
    assert read("indexer_ms_per_step", ctx) == pytest.approx(2.5)
    assert read("sparse_attention_ms_per_step", ctx) == pytest.approx(10.0)
    assert read("selected_keys_skipped_pct", ctx) == pytest.approx(
        100 * (1 - 2048 / 20000))
    # two steps' needs over the calls' time of one of five layers
    need = 2 * 2 * 64 * 128 * 128 * 20000 / peak["bf16_flops_per_s"]
    assert read("indexer_roofline_pct", ctx) == pytest.approx(
        100 * need / (5e-3 / 5))
    need = 2 * 2 * 128 * (576 + 512) * 128 * 2048 / peak["bf16_flops_per_s"]
    assert read("sparse_attention_roofline_pct", ctx) == pytest.approx(
        100 * need / (20e-3 / 5))
    parent = ctx_of(dict(rows=128))
    for name in ("indexer_roofline_pct", "sparse_attention_roofline_pct",
                 "selected_keys_skipped_pct"):
        assert read(name, parent) is None
