"""The cell ``minicpm-sala-longctx128k`` on the CPU: its configuration file
holds the catalog row's numbers, its manifest entries (looked up by NAME)
name files that exist, its rehearsal runs end to end with no failed request,
no request of its mix can be evicted or cut, and its kernels' costs are the
hand counts."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import flops, loadgen, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG, MIX = "minicpm-sala-longctx128k", "minicpm-sala", "longctx-128k"
READERS = ("lightning_ms_per_step", "lightning_roofline_pct",
           "block_select_ms_per_step", "block_select_roofline_pct",
           "block_sparse_attention_ms_per_step",
           "block_sparse_attention_roofline_pct")
SPARSE_AT = (0, 9, 16, 17, 22, 29, 30, 31)
# the catalog row's ``config`` (model-configs guide, MiniCPM-SALA)
CATALOG = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": ["minicpm4" if i in SPARSE_AT else "lightning-attn"
                    for i in range(32)],
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
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


def test_the_configuration_file_holds_the_catalog_rows_numbers(cfg):
    reduced = ["num_hidden_layers", "mixer_types"]
    assert cfg["reduced"] == reduced
    for key, value in CATALOG.items():  # every key but those in ``reduced``
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # the cut: published layers 16-27 in published order, 3 sparse to 9
    # lightning (the published 1 : 3), each under its own published index
    assert cfg["layer_ids"] == list(range(16, 28))
    assert cfg["num_hidden_layers"] == 12
    assert cfg["mixer_types"] == [CATALOG["mixer_types"][i]
                                  for i in cfg["layer_ids"]]
    assert cfg["mixer_types"].count("minicpm4") == 3
    assert cfg["source"].startswith("https://huggingface.co/openbmb/")
    assert cfg["assumed"]["sparse_config"] == dict(
        kernel_size=32, kernel_stride=16, block_size=64, topk=64,
        init_blocks=1, window_size=2048, dense_len=8192)
    assert set(cfg["assumed"]) >= {
        "sparse_config_why", "dense_switch", "decay", "qk_norm",
        "lightning_activation", "output_norm_and_gate", "mup", "precision"}
    assert "layers are not divided" in cfg["deployment"]
    assert cfg["engine"]["model"]["overrides"]["layer_ids"] == cfg["layer_ids"]
    fam = reference.family(cfg["family"])
    s = fam.shape_of(cfg)
    assert isinstance(s, flops.Shape)
    assert (s.layers, s.heads, s.kv_heads, s.hd, s.ffn, s.vocab, s.depth,
            s.count("sparse"), s.count("lightning")) == (
                12, 32, 2, 128, 16384, 73448, 32, 3, 9)
    # 3 x 253.8 M + 9 x 285.2 M + 601.7 M (norm vectors left out)
    assert round(flops.stored_params(s) / 1e9, 2) == 3.93


def test_the_manifest_entries_are_found_by_name_and_name_files_that_exist(cfg):
    manifest = load("BENCHMARK.json")
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert os.path.isfile(os.path.join(ROOT, entry["file"]))
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
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL in metrics["selected_keys_skipped_pct"]["workloads"]
    for name, m in metrics.items():
        if name.endswith(".tput"):
            assert CELL in m["workloads"], name
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
    sc = cfg["assumed"]["sparse_config"]
    # the traffic file's parameters, as the issue gives them
    assert (mix["kind"], mix["clients"], mix["replay_requests"]) == (
        "closed_loop", 4, 16)
    assert mix["prompt"] == dict(median=32768, sigma=0.7, min=16384,
                                 max=131072)
    assert mix["answer"] == dict(median=256, sigma=0.6, min=64, max=1024)
    assert (mix["grace_s"], mix["trace_seconds"]) == (120.0, 4.0)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest <= srv["max_tokens"]
    assert srv["max_tokens"] % srv["token_budget"] == 0
    assert mix["clients"] <= srv["max_slots"]  # nothing ever queues
    assert srv["page_size"] == sc["kernel_stride"]
    # every slot at its full length at once, the chunk in flight included
    pages = -(-(longest + srv["token_budget"]) // srv["page_size"])
    assert srv["max_slots"] * pages <= srv["num_pages"]
    assert srv["prefix_cache"] is False
    cc = mix["correctness"]
    for n in (*cc["prompts"], *cc["precision"]["prompts"]):
        assert n % 16 and n % 64 and n % 128
    assert min(cc["prompts"]) < sc["dense_len"] < 2 * sc["dense_len"] < max(
        cc["prompts"])
    assert all(sc["dense_len"] < n < sc["dense_len"] + 512
               for n in cc["precision"]["prompts"])
    # the replay set is one fixed schedule; every context is 2 to 16 times
    # dense_len, so every sparse layer selects for every query past 8,192
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 16 and pairs[:, 0].min() >= 2 * sc["dense_len"]
    assert pairs[:, 0].max() <= 16 * sc["dense_len"]
    # the worst drain: at the window's close the four callers hold at most
    # the four longest requests of the set, whole; at the slowest rate a
    # sound run has shown (the traffic file says which) they are served
    # inside the grace
    worst = np.sort(pairs.sum(1))[-4:].sum()
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]


def test_the_kernels_costs_against_hand_counts(cfg):
    fam = reference.family(cfg["family"])
    s = fam.shape_of(cfg)
    # one decode row in each of 2 live slots: 2 x (update + read-out) of
    # 32 heads x 128 x 128, 2 states of 2 MiB read and written, 2 rows of
    # q, k, v, o
    f, b = fam.lightning_cost(s, rows=2, state_slots=2)
    assert f == 2 * 4 * 32 * 128 * 128
    assert b == 2 * 2 * 32 * 128 * 128 * 4 + 2 * 4 * 32 * 128 * 2
    # a query at position 16,383 sees (16384 - 32) / 16 + 1 = 1,023
    # compressed keys: 32 heads x 128 x 2 each; the keys of both kv heads in
    f, b = fam.block_select_cost(s, compressed_keys=1023,
                                 compressed_rows=1023, rows=1)
    assert f == 2 * 32 * 128 * 1023
    assert b == 1023 * 2 * 128 * 2 + 32 * 128 * 2
    # it attends 64 blocks of 64 = 4,096 keys: QK^T and PV of 32 heads; K
    # and V of both kv heads once; its query in and its output out
    f, b = fam.block_sparse_attention_cost(s, attended_keys=4096,
                                           chosen_rows=4096, rows=1)
    assert f == 2 * 2 * 32 * 128 * 4096
    assert b == 2 * 2 * 128 * 2 * 4096 + 2 * 32 * 128 * 2
    # the whole model's count follows the layers as run
    assert s.attention_flops_per_token(4096) == (
        3 * 2 * 32 * 128 * (4096 / 16 + 2 * 4096) + 9 * 4 * 32 * 128 * 128)


def test_the_engines_counters_are_the_hand_counts():
    """The plan's vectors -> the counts the readers take from the trace."""
    from types import SimpleNamespace

    import deepspeed_tpu.serving.engine as eng
    from deepspeed_tpu.ops.pallas.block_sparse_attention import BlockSparse

    geom = BlockSparse()
    booked = []
    me = SimpleNamespace(
        config=SimpleNamespace(block_sparse=geom),
        metrics=SimpleNamespace(
            on_keys=lambda *a: booked.append(a), context_keys=0,
            state_resets=0))
    plan = SimpleNamespace(
        start_pos=np.array([0, 16383, 100, 0]),
        num_new=np.array([128, 1, 1, 0]))
    got = eng.ServingEngine._count_mixers(me, plan)
    # slot 0: a first chunk of 128 (1 + ... + 128 keys, all attended; keys
    # complete at positions 31, 47, ... 127: 1 + 2 + ... over 97 rows);
    # slot 1: one row at 16,383 (16,384 keys, 4,096 attended, 1,023
    # compressed); slot 2: one row at 100 (101 keys, 5 compressed)
    assert got["context_keys"] == 128 * 129 // 2 + 16384 + 101
    assert got["attended_sparse"] == 128 * 129 // 2 + 4096 + 101
    assert got["compressed_keys"] == sum(
        max((t + 1 - 32) // 16 + 1, 0) for t in range(128)) + 1023 + 5
    assert got["compressed_rows"] == 7 + 1023 + 5
    assert got["chosen_min"] == 128 + 4096 + 101
    assert (got["state_slots"], got["state_resets"]) == (3, 1)
