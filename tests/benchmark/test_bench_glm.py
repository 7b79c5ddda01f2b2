"""The cell ``glm47flash-pretrain-4k`` on the CPU: its manifest entries against
its files, its configuration file against the catalog row, its ``Shape``'s
FLOP count and its kernels' costs against hand counts, its four readers on a
trace written by hand, and its rehearsal end to end. (The lowering of its train
step for the described v5e stands in ``tests/test_tpu_compile.py``, the one
file that may load the chip's compiler.)"""

import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import flops, reference
from benchmarks import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "glm47flash-pretrain-4k"
READERS = ("expert_train_ms_per_step", "expert_train_roofline_pct",
           "latent_flash_ms_per_step", "latent_flash_roofline_pct")
# the catalog row's ``config`` (model-configs guide, GLM-4.7-Flash)
CATALOG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load("benchmarks", "configs", "glm-4.7-flash.json")


@pytest.fixture(scope="module")
def fam():
    return reference.family("glm4_moe_lite")


def test_the_manifest_entries_name_the_files_and_only_append(cfg):
    m = load("BENCHMARK.json")
    entry = m["configs"][-1]
    assert entry["name"] == "glm-4.7-flash" == cfg["name"]
    assert entry["file"] == "benchmarks/configs/glm-4.7-flash.json"
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "glm-4.7-flash", "pretrain-4k", 1)
    assert len(cell["why"]) <= 200
    assert cfg["family"] == cfg["model_type"] == "glm4_moe_lite"
    metrics = {x["name"]: x for x in m["end_to_end"] + m["per_layer"]}
    for name in ("train_tokens_per_s_per_chip", "data_wait_ms_per_step",
                 "mfu_pct", "device_idle_pct.train"):
        assert metrics[name]["workloads"][-1] == CELL
    # the accepted flash readers count every Pallas call as flash and
    # ``shape.layers`` layers of ``kv_heads``: wrong here, so not listed
    for name in ("flash_ms_per_step", "flash_roofline_pct"):
        assert CELL not in metrics[name]["workloads"]
    assert [x["name"] for x in m["per_layer"][-4:]] == list(READERS)
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "train_tokens_per_s_per_chip"
        assert metrics[name]["source"] == "device_trace"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mix = load("benchmarks", "traffic", "pretrain-4k.json")
    assert mix["kind"] == "train_stream"
    assert mix["documents"] == load("benchmarks", "traffic",
                                    "pretrain-2k.json")["documents"]
    assert 0 < mix["correctness"]["loss_rtol"] <= 0.002


def test_the_configuration_file_holds_the_catalog_rows_numbers(cfg):
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["reduced"] == reduced
    for key, value in CATALOG.items():  # every key but those in ``reduced``
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) == (
        5, 8, 19360, 1)
    # the floors: four routed layers after the dense one, 8 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["n_routed_experts"] == 8 and 8 * cfg["vocab_size"] == 154880
    assert set(cfg["assumed"]) >= {
        "precision", "bias_update_rate", "mtp_loss_weight",
        "mtp_concat_order", "balance_loss", "rotary_pairing",
        "selection_bias", "sequence_length", "weights"}
    assert "8 chips share each layer" in cfg["deployment"]
    assert "706,518,848" in cfg["deployment"]
    eng = cfg["engine"]
    over = eng["model"]["overrides"]
    assert (eng["entry"], eng["micro_batch_per_chip"], eng["max_seq_len"],
            over["max_seq_len"]) == ("initialize", 2, 4096, 4096)
    assert (over["lead_dense_layers"] + over["num_layers"],
            over["num_experts"], over["moe_routed_experts"],
            over["moe_first_expert"], over["vocab_size"],
            over["mtp_layers"]) == (5, 8, 64, 0, 19360, 1)
    assert over["mtp_loss_weight"] == cfg["assumed"]["mtp_loss_weight"]["value"]
    assert over["moe_bias_update_rate"] == cfg["assumed"][
        "bias_update_rate"]["value"]
    # the BLOOM cells' ds_config (bf16, AdamW at 3e-4, ZeRO-0, full
    # recomputation) plus a warm-up towards that rate and the step tracer
    # whose spans carry the routed rows
    ds = dict(eng["ds_config"])
    sched, tracer = ds.pop("scheduler"), ds.pop("steptrace")
    assert ds == load("benchmarks", "configs",
                      "bloom-560m.json")["engine"]["ds_config"]
    assert sched["type"] == "WarmupLR" and tracer == {"enabled": True}
    assert sched["params"]["warmup_max_lr"] == ds["optimizer"]["params"]["lr"]
    assert {"lr_schedule", "step_tracer"} <= set(cfg["assumed"])
    assert 0 < cfg["eos_token_id"] < cfg["vocab_size"]


def test_the_shape_counts_what_one_token_touches_here(cfg, fam):
    s = fam.shape_of(cfg)
    assert isinstance(s, flops.Shape)
    assert (s.layers, s.dense_layers, s.mtp, s.experts, s.routed, s.top_k,
            s.hd, s.v_dim, s.kv_heads, s.ffn, s.dense_ffn, s.shared_ffn,
            s.vocab, s.mtp_weight) == (
        4, 1, 1, 8, 64, 4, 256, 256, 1, 1536, 10240, 1536, 19360, 0.3)
    attn = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
            + 20 * 256 * 2048)
    assert attn == 21_757_952 == s.attention_params
    expert = 3 * 2048 * 1536
    # a routed block: attention, the router's 64 outputs, the shared expert
    # and 4 x 8/64 = half an expert a token
    routed = attn + 2048 * 64 + expert + expert // 2
    dense = attn + 3 * 2048 * 10240
    head = 2048 * 19360
    # the dense layer, four routed layers, the MTP block and eh_proj, the
    # head twice: about 352 M matmul parameters a token
    touched = dense + 4 * routed + (routed + 2 * 2048 * 2048) + 2 * head
    assert touched == 352_583_680
    assert 4 * s.layer_matmul_params() + flops.head_params(s) == touched
    # attention over 6 blocks at qk 256 / v 256, 2,048 keys on average
    attend = 6 * 2 * 20 * (256 + 256) * 2048
    assert s.attention_flops_per_token(2048) == attend
    per_token = flops.train_flops_per_token(s, 4096)
    assert per_token == 3 * (2 * touched + attend)
    assert round(per_token / 1e9, 2) == 2.87
    # what is stored: 8 experts a routed block, the head once more as the
    # embedding (biases and norm vectors left out)
    stored = dense + 5 * (attn + 2048 * 64 + 9 * expert) + 2 * 2048 * 2048 \
        + 2 * head
    assert flops.stored_params(s) == stored
    # ... all but the norm vectors (two a block, two inside each attention,
    # the final one, the MTP module's three) and the selection bias
    assert 706_518_848 - stored == 6 * (2 * 2048 + 768 + 512) + 4 * 2048 \
        + 5 * 64


def test_the_kernels_costs_against_hand_counts(cfg, fam):
    s = fam.shape_of(cfg)
    assert fam.expected_rows(s, 2 * 4096) == 4096  # 4 x 8/64 a token
    need, nbytes = fam.expert_train_cost(s, 4096)
    # nine products of 2 x 4096 x 2048 x 1536 in each of five routed blocks
    assert need == 5 * 9 * 2 * 4096 * 2048 * 1536 == 1_159_641_169_920
    assert nbytes == 5 * 9 * 2 * 4096 * (2048 + 1536)
    assert fam.expert_train_cost(s, 0) == (0, 0)  # no row, no need
    need, nbytes = fam.latent_flash_train_cost(s, 2, 4096)
    # seven causal matmuls of 2 x (2 x 20 heads x 256) x 4096^2 / 2 in each
    # of six blocks; twelve [2, 4096, 20, 256] bf16 tensors moved a block
    assert need == 6 * 7 * 2 * 2 * 20 * 256 * 4096 * 4096 // 2
    assert nbytes == 6 * 12 * 2 * 4096 * 20 * 256 * 2
    peak = load("benchmarks", "peaks.json")["TPU v5 lite"]
    assert flops.roofline_seconds(need, nbytes, peak)[1] == "compute"
    assert flops.roofline_seconds(*fam.expert_train_cost(s, 4096), peak)[1] \
        == "compute"


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_the_four_readers_on_a_trace_by_hand(cfg, fam):
    """Two traced steps: device time under the names the chip gives the
    grouped products (``ragged-dot-...``) and the flash kernels (named after
    the scope they are traced under). From a trace without them (the
    parent's, or a family without the costs) every reader returns nothing
    and does not raise."""
    peak = load("benchmarks", "peaks.json")["TPU v5 lite"]

    def event(name, start, dur):
        return tr.Event(name, start, dur, {})

    call = ', custom_call_target="tpu_custom_call"'
    ops = [event("%ragged-dot-metadata.3 = (s32[9]{0}, s32[23]{0}) "
                 "custom-call(s32[8]{0} %g)" + call, 0, 1e6),
           event("%ragged-dot-none.3 = bf16[32768,1536]{1,0} custom-call("
                 "s32[1]{0} %a, bf16[32768,2048]{1,0} %x)" + call, 1e6, 39e6),
           event("%latent_attention.68 = (bf16[2,20,4096,256]{3,2,1,0}, "
                 "f32[2,20,4096,8]{3,2,1,0}) custom-call(bf16[2,20,4096,256]"
                 "{3,2,1,0} %q)" + call, 40e6, 160e6),
           event("%fusion.1 = bf16[8192,2048]{1,0} fusion(...)", 200e6, 50e6)]
    host = [event("bench/train_batch", 0, 125e6),
            event("bench/train_batch", 125e6, 125e6)]

    def ctx_of(ops, family=fam):
        trace = {"/device:TPU:0": {tr.OPS_LINE: ops},
                 tr.HOST_PLANE: {"python": host}}
        return SimpleNamespace(
            reduced=tr.reduce_trace(trace), family=family, flops=flops,
            shape=fam.shape_of(cfg), peak=peak,
            counters=dict(micro_batch=2, seq=4096))

    from deepspeed_tpu.profiling import steptrace

    steptrace.reset()  # no step tracer: the roofline is at the expected rows
    ctx = ctx_of(ops)
    assert read("expert_train_ms_per_step", ctx) == pytest.approx(20.0)
    assert read("latent_flash_ms_per_step", ctx) == pytest.approx(80.0)
    need = 1_159_641_169_920 / peak["bf16_flops_per_s"]
    assert read("expert_train_roofline_pct", ctx) == pytest.approx(
        100 * need / 20e-3)
    need = 6 * 7 * 2 * 2 * 20 * 256 * 4096 * 2048 / peak["bf16_flops_per_s"]
    assert read("latent_flash_roofline_pct", ctx) == pytest.approx(
        100 * need / 80e-3)
    assert 0 < read("expert_train_roofline_pct", ctx) < 100
    assert 0 < read("latent_flash_roofline_pct", ctx) < 100
    # with the program's step tracer on, the rows are the traced steps' own
    # (the last two ``train/device`` spans), not the balanced router's
    try:
        reg = steptrace.configure()
        for rows in (9000.0, 1024.0, 3072.0):
            span = reg.begin("train/device", "train")
            span.annotate(moe_rows_held=rows)
            span.end()
        need = fam.expert_train_cost(fam.shape_of(cfg), 2048)[0] \
            / peak["bf16_flops_per_s"]
        assert read("expert_train_roofline_pct", ctx) == pytest.approx(
            100 * need / 20e-3)
    finally:
        steptrace.reset()
    # BLOOM's step: flash kernels under another family, no grouped product
    bloom = ctx_of([ops[2], ops[3]], family=reference.family("bloom"))
    for name in READERS:
        assert read(name, bloom) is None
    nothing = ctx_of([ops[3]])
    for name in READERS:
        assert read(name, nothing) is None


def test_rehearsal_trains_with_a_falling_loss_and_nothing_compiled_inside():
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
    assert last["attempted"] >= 6 and last["failed"] == 0
    assert last["metric_names"] == ["setup_s", "train_tokens_per_s_per_chip"]
    assert "compilations inside the window: 0, retraces 0" in p.stdout
    assert "INCORRECT" not in p.stdout
