"""The cell ``mellum2-12b-mixedlen`` on the CPU: its rehearsal runs end to
end with no failed request, the serving comparison refuses every fault this
family adds at the rehearsal's size, its configuration file holds the
catalog's numbers, and its arithmetic counts a window layer's keys."""

import json
import os
import subprocess
import sys

from benchmarks import flops, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "mellum2-12b-mixedlen"
NEW_FAULTS = ("window_off", "window_off_by_one", "yarn_off", "kinds_shifted",
              "window_page_dropped")


def run(*args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_rehearsal_passes_with_no_failed_request():
    p = run("--workload", CELL, "--seed", "3000000011", "--seconds", "3",
            "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["workload"] == CELL
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "metrics" not in last and "correct" not in last
    # the program's counters reach the reader through the trace
    assert "window_keys_skipped_pct" in last["metric_names"]
    assert "compilations inside the window: 0" in p.stdout
    assert "share rule: 16 within 0.0005" in p.stdout
    assert "outlier rule: 16 clear of a near-tie" in p.stdout
    assert "exact rule: at least 100.0% = 32" in p.stdout
    assert "+window=16 (4/slot)" in p.stderr + p.stdout  # the second pool


def test_the_check_refuses_every_fault_the_family_adds():
    p = run("--workload", CELL, "--rehearse", "--check-seeds", "3000000041,11",
            "--inject", ",".join(NEW_FAULTS))
    checks = json.loads(p.stdout.strip().splitlines()[-1])["checks"]
    assert [(c["seed"], c["inject"]) for c in checks] == [
        (seed, inject) for seed in (3000000041, 11)
        for inject in (None, *NEW_FAULTS)]
    for c in checks:
        assert c["correct"] == (c["inject"] is None), c
        assert c["tokens"] == 16 and c["precision"]["tokens"] == 32
    assert p.returncode == 1  # something read incorrect


def test_the_configuration_file_holds_the_catalogs_numbers():
    cfg = load("benchmarks", "configs", "mellum2-12b-a2.5b.json")
    want = dict(
        hidden_size=2304, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, num_experts=64, num_experts_per_tok=8,
        moe_intermediate_size=896, intermediate_size=7168,
        vocab_size=98304, sliding_window=1024,
        max_position_embeddings=131072, rms_norm_eps=1e-06,
        max_window_layers=0)
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 28}
    assert cfg["num_hidden_layers"] in (8, 12)  # whole periods of four
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["beta_fast"],
            full["original_max_position_embeddings"]) == ("yarn", 16, 32, 8192)
    assert set(cfg["assumed"]) >= {"qk_norm", "mtp_head", "router", "depth",
                                   "host_share"}
    srv = cfg["engine"]["serving"]
    # every slot can run to max_tokens at once in the full layers' pool
    assert srv["max_tokens"] == 16384 + 256
    per_slot = -(-(srv["max_tokens"] + srv["token_budget"]) // srv["page_size"])
    assert srv["num_pages"] == srv["max_slots"] * per_slot
    assert "window_num_pages" not in srv  # the program derives that pool
    assert srv["prefix_cache"] is False


def test_no_request_of_the_mix_can_be_evicted_or_cut():
    mix = load("benchmarks", "traffic", "mixedlen.json")
    srv = load("benchmarks", "configs",
               "mellum2-12b-a2.5b.json")["engine"]["serving"]
    assert mix["prompt"]["max"] + mix["answer"]["max"] <= srv["max_tokens"]
    assert mix["clients"] <= srv["max_slots"]  # nothing ever queues
    cc = mix["correctness"]
    for n in (*cc["prompts"], *cc["precision"]["prompts"]):
        assert n % 16 and n % 128
        assert n + cc["precision"]["new_tokens"] <= srv["max_tokens"]
    assert min(cc["prompts"]) < 1024 < 8192 < max(cc["prompts"])
    # no routing margin excuses a token here: every one is held to the
    # outlier rule (the block says why in its own words)
    assert cc["min_margin"] == 0.0 and cc["outlier_tol"] > cc["logit_tol"]


def test_shape_counts_a_window_layers_keys_once():
    cfg = load("benchmarks", "configs", "mellum2-12b-a2.5b.json")
    fam = reference.family("mellum")
    s = fam.shape_of(cfg)
    assert isinstance(s, flops.Shape)
    assert (s.layers, s.kind_layers("sliding_attention"),
            s.kind_layers("full_attention")) == (12, 9, 3)
    per_key = 2 * 2 * 32 * 128
    assert s.attention_flops_per_token(512) == per_key * 12 * 512
    assert s.attention_flops_per_token(16000) == per_key * (
        3 * 16000 + 9 * 1024)
    # a layer: attention 21.2 M, router 0.15 M, 64 experts of 6.19 M
    assert s.layer_matmul_params(active=False) == (
        2304 * 4096 * 2 + 2304 * 512 * 2 + 2304 * 64 + 64 * 3 * 2304 * 896)
    assert flops.stored_params(s) == 12 * s.layer_matmul_params(
        active=False) + 2 * 98304 * 2304
    need, bytes_ = fam.window_attention_cost(s, 1000, 2048, 128)
    assert need == 4 * 32 * 128 * 1000
    assert bytes_ == 2 * 4 * 128 * 2 * 2048 + 2 * 32 * 128 * 2 * 128
    assert set(fam.FAULTS) >= {*NEW_FAULTS, "rope_off_by_one", "page_dropped",
                               "experts_swapped", "gqa_mispaired",
                               "weights_int8", "weights_int4"}
