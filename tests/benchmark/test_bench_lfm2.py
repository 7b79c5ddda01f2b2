"""The cell ``lfm2-agents64`` on the CPU: its configuration file holds the
catalog row's numbers, the program builds the model the file describes, its
manifest entries are found BY NAME and lie after the accepted ones (never
"the last": the next PR's append must not redden this file), no request of
its mix can be evicted or cut, its cost function grows with the work, its two
readers and the accepted readers it joins read a small recorded trace and say
nothing on a trace without their counts, every fault changes the reference,
and its rehearsal runs end to end with no failed request."""

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
CELL, CONFIG, MIX = "lfm2-agents64", "lfm2-8b-a1b", "agents-64"
PAIRED, DECODE = "paired_head_attention_roofline_pct", "decode_slots_per_step"
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
    "commandaplus-rag8", "keyevl2-longmm4", "qwen3next-longctx256k")
ACCEPTED_CONFIGS = (
    "bloom-560m", "mixtral-8x7b", "bloom-1b7", "mellum2-12b-a2.5b",
    "deepseek-v3.2", "glm-4.7-flash", "minicpm-sala", "ling-3.0-flash",
    "brumby-14b", "glm-5.3-flash", "command-a-plus-05-2026",
    "keye-vl-2.0-30b-a3b", "qwen3-next-80b-a3b")
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv",
         "conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
# the catalog row's ``config`` (model-configs guide, LFM2-8B-A1B)
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
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
    assert cfg["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                             "blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key == "num_hidden_layers":
            assert value == cfg["published"][key] == 24 and cfg[key] == 14
        else:
            assert cfg[key] == value, key
    # no width is cut, every expert is held and the vocabulary is whole
    s = fam.shape_of(cfg)
    assert (s.d, s.heads, s.kv_heads, s.hd, s.conv, s.ffn, s.dense_ffn,
            s.routed, s.experts, s.top_k, s.rope_theta, s.vocab) == (
        2048, 32, 8, 64, 3, 1792, 7168, 32, 32, 4, 1e6, 65536)
    assert (s.layers, s.dense_layers, s.first_expert, s.routed_scale) == (
        12, 2, 0, 1.0)
    # the two leading dense layers and three whole periods A c c c
    assert s.layer_ids == tuple(range(14))
    assert (s.count("conv"), s.kind_layers("full_attention")) == (11, 3)
    assert [s.kind(i) for i in range(2, 6)] == ["full_attention"] + [
        "conv"] * 3
    assert s.tied and s.eps == 1e-5
    for key in ("head_dim", "conv_columns", "convolution", "rotary",
                "qk_norm", "final_norm", "tie_word_embeddings", "router",
                "bias_draw", "depth", "experts", "vocabulary", "weights"):
        assert len(cfg["assumed"][key]) > 40, key
    for part in ("2 v5e chips", "two pipeline stages", "WHOLE on its chip",
                 "ALSO applies the final norm and the tied head"):
        assert part in cfg["deployment"], part
    # the floors of a model_config cut: a whole period and at least four
    # layers after the leading dense ones, at least 8 routed experts, at
    # least an eighth of the vocabulary
    assert s.layers >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    # a configuration the family cannot compute is refused where it is read
    with pytest.raises(ValueError, match="no bias"):
        fam.shape_of({**cfg, "conv_bias": True})
    with pytest.raises(ValueError, match="layer_types"):
        fam.shape_of({**cfg, "layer_types": TYPES[:10]})
    with pytest.raises(ValueError, match="layer_ids"):
        fam.shape_of({**cfg, "layer_ids": [0, 1]})


def test_the_program_builds_the_model_the_file_describes(cfg, fam):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks.run import build_model, check_shape, merged

    for config in (cfg, merged(cfg, cfg["rehearse"])):
        model = build_model(config["engine"])
        check_shape(model, fam.shape_of(config))
        c, s = model.config, fam.shape_of(config)
        assert (c.routed_experts, c.moe_first_expert, c.conv_kernel,
                c.lead_dense_layers, c.lead_dense_ffn, c.moe_shared_width) == (
            s.routed, 0, s.conv, s.dense_layers, s.dense_ffn, 0)
        assert (c.moe_gate, c.moe_groups, c.moe_norm_eps, c.moe_dropless,
                c.qk_norm, c.layer_pattern) == (
            "sigmoid_groups", 1, 1e-6, True, True, ())
        assert c.mixer_types == tuple(
            {"conv": "conv", "full_attention": "full"}[s.kind(i)]
            for i in s.layer_ids)
    model = build_model(cfg["engine"])
    # the issue's arithmetic: 16.78 M and 10.49 M a mixer, 4,667 M
    # parameters = 9.33 GB in bf16
    from deepspeed_tpu.models.lfm2 import mixer_params

    assert mixer_params(model.config, "conv") == 16_783_360
    assert mixer_params(model.config, "full") == 10_485_888
    assert model.num_params() == 4_667_077_376
    s = fam.shape_of(cfg)
    # the arithmetic leaves out what is no matrix: two norms a layer and the
    # last one, a q and a k vector an attention layer, a convolution's taps
    # and a routed layer's selection bias
    assert flops.stored_params(s) == pytest.approx(
        model.num_params() - (14 * 2 * 2048 + 2048 + 3 * 2 * 64
                              + 11 * 3 * 2048 + 12 * 32), abs=12)
    # a token a paged layer in the arena: K and V of 8 KV heads of 64; a
    # slot's leaf: 11 x 2 carried rows of 2,048 in bf16
    from deepspeed_tpu.serving.engine import cache_token_bytes, state_bytes

    assert cache_token_bytes(model.config, 2, False) == 2048
    assert state_bytes(model.config, 1, 2) == 11 * 2 * 2048 * 2 == 90_112


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
    # fifteen cells, one of them on four chips
    assert len(manifest["workloads"]) >= 15
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    for part in ("64 callers", "rows/expert", "misses"):
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
    for part in ("traffic/" + MIX + ".json",
                 "families/" + cfg["family"] + ".py"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", part))
    metric_names = [m["name"] for m in manifest["per_layer"]]
    metrics = dict(zip(metric_names, manifest["per_layer"]))
    assert metrics[PAIRED]["layer"] == metrics[
        "full_attention_ms_per_step"]["layer"]
    assert metrics[DECODE]["layer"] == metrics["step_ms"]["layer"]
    assert CELL not in metrics["full_attention_roofline_pct"]["workloads"]
    for name, source, unit in ((PAIRED, "device_trace", "%"),
                               (DECODE, "program_counter", "slots")):
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
        assert (metrics[name]["source"], metrics[name]["unit"]) == (
            source, unit)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
        # after the metrics the last accepted PR brought
        assert metric_names.index(name) > metric_names.index(
            "gated_attention_roofline_pct")
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
        if m["name"] not in (PAIRED, DECODE, *JOINED) and not m[
                "name"].endswith(".tput"):
            assert CELL not in m["workloads"], m["name"]
    assert len(json.dumps(manifest)) < 64 * 1024


def test_no_request_of_the_mix_can_be_evicted_or_cut(cfg, mix, fam):
    srv = cfg["engine"]["serving"]
    assert (mix["kind"], mix["clients"], mix["replay_requests"],
            mix["schedule_seed"]) == ("closed_loop", 64, 1024, 6503)
    assert mix["prompt"] == dict(median=1024, sigma=1.0, min=64, max=6144)
    assert mix["answer"] == dict(median=320, sigma=0.7, min=32, max=1536)
    # the keys longctx.json has
    assert set(mix) == set(load("benchmarks", "traffic", "longctx.json"))
    W = srv["token_budget"]
    assert (srv["max_slots"], W) == (64, 256)
    longest = mix["prompt"]["max"] + mix["answer"]["max"]
    assert longest == 7680 <= srv["max_tokens"] == 8192
    assert mix["clients"] == srv["max_slots"]  # callers = slots: no queue
    # the pool holds every slot at full length at once
    assert srv["num_pages"] * srv["page_size"] == 64 * srv["max_tokens"]
    assert srv["max_tokens"] % srv["page_size"] == 0
    assert srv["prefix_cache"] is False
    # the arena: 2,048 B a token a paged layer (K and V of 8 KV heads of 64,
    # two a 128-lane row) over 3 layers, and 90 KB a slot of carried rows,
    # beside 9.33 GB of weights: the issue's 12.6 GB
    token = 2 * cfg["num_key_value_heads"] * 64 * 2
    arena = (srv["num_pages"] + 1) * srv["page_size"] * token * 3
    assert 3.22e9 < arena < 3.23e9
    resident = arena + 64 * 90_112 + 2 * 4_667_077_376
    assert 12.5e9 < resident < 12.6e9
    # the page table of 64 slots rides in SMEM under the kernel's limit
    from deepspeed_tpu.ops.pallas.paged_attention import SMEM_TABLE_BYTES

    table = 64 * (srv["max_tokens"] // srv["page_size"]) * 4
    assert table == 32 * 1024 <= SMEM_TABLE_BYTES
    cc = mix["correctness"]
    assert cc["new_tokens"] == 24 and len(cc["prompts"]) == 2
    # both samples run several chunks of the budget
    assert W * 5 < cc["prompts"][0] < W * 8 and cc["prompts"][1] > 15 * W
    # the precision sample: a wave of the slots over two chunks first (the
    # carried rows cross a chunk's edge and go on into decode), then a wave
    # of short prompts; many SHORT answers (a long greedy answer of drawn
    # weights runs into a cycle and repeats its misses: PERF.md section 6,
    # PR 56), in whole waves of the slots
    pp = cc["precision"]["prompts"]
    assert all(W < n < 2 * W for n in pp[:64])
    assert all(n < W // 4 for n in pp[64:]) and len(pp) == 128
    assert len(pp) % srv["max_slots"] == 0 and len(pp) <= srv["queue_limit"]
    assert cc["precision"]["new_tokens"] <= mix["answer"]["min"]
    for n in (*cc["prompts"], *pp):
        assert n % srv["page_size"] and n % W
    pairs = loadgen._length_pairs(mix, mix["replay_requests"])
    assert len(pairs) == 1024 and pairs.sum(1).max() <= 7680
    assert (pairs[:, 0].min(), pairs[:, 0].max(), int(pairs[:, 0].mean()),
            pairs[:, 1].min(), pairs[:, 1].max(), int(pairs[:, 1].mean())
            ) == (64, 6144, 1505, 32, 1536, 387)
    # the worst drain: at the window's close the 64 callers hold at most the
    # 64 longest requests of the set, whole, at the slowest rate a sound run
    # has shown: inside the grace
    worst = np.sort(pairs.sum(1))[-64:].sum()
    assert worst == 393134
    assert worst / mix["drain_tokens_per_s"] < mix["grace_s"]
    assert srv["request_timeout_s"] > 50 + mix["grace_s"]


def test_the_cost_function_grows_with_the_work(cfg, fam):
    s = fam.shape_of(cfg)
    # a (query, key) pair costs 2 x 2 x 32 heads x 64: the 64-WIDE products,
    # not the 128-lane ones the pairing spends; a fetched key K and V of 8
    # KV heads x 64 x 2 B, ONCE for its slot's rows and query heads
    f, b = fam.full_attention_cost(s, 1000, 128, 8)
    assert f == 8192 * 1000
    assert b == 2048 * 128 + 2 * 32 * 64 * 2 * 8
    assert fam.full_attention_cost(s, 2000, 128, 8)[0] == 2 * f
    assert fam.full_attention_cost(s, 1000, 256, 8)[1] == b + 2048 * 128
    # 54 decoding slots at 2 k of context beside a 200-row chunk at 1 k
    # move bytes: memory-bound
    step = fam.full_attention_cost(s, 54 * 2000 + 200 * 1100,
                                   54 * 2048 + 1216, 254)
    assert flops.roofline_seconds(*step, PEAK)[1] == "memory"
    # the whole model's count: three layers' attention grows with the
    # context, eleven convolutions do not
    grow = s.attention_flops_per_token(7000) - s.attention_flops_per_token(
        6000)
    assert grow == 3 * 8192 * 1000
    assert s.attention_flops_per_token(0) == 11 * 8 * 2048
    # a token touches 4 of its 32 experts
    assert s.layer_matmul_params(False) - s.layer_matmul_params() == (
        28 * 3 * 2048 * 1792)


def recorded_ctx(cfg, fam):
    """A context over ``lfm2_trace.textproto``: two traced steps of the [64,
    256] engine, with the operations the readers look for named as the
    chip's trace names them."""
    with open(os.path.join(HERE, "lfm2_trace.textproto")) as f:
        trace = trace_reduce.load_text_proto(f.read())
    return SimpleNamespace(
        reduced=trace_reduce.reduce_trace(trace), full_trace=trace,
        family=fam, shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
        counters=dict(token_budget=256), root=ROOT)


def test_the_readers_read_a_small_recorded_trace(cfg, fam):
    ctx = recorded_ctx(cfg, fam)
    counts = kinds_trace.step_counts(ctx)
    assert counts["steps"] == 2 and counts["rows"] == 2 * 256
    assert (counts["conv_rows"], counts["conv_state_slots"],
            counts["state_resets"], counts["decode_slots"]) == (
        512, 112, 1, 108)
    assert kinds_trace.traced_steps(ctx) == 2
    assert reader(DECODE).read(ctx) == pytest.approx(54.0)
    # the three attention layers' paged calls, 0.8 ms a layer
    assert reader("full_attention_ms_per_step").read(ctx) == pytest.approx(2.4)
    need = flops.roofline_seconds(*fam.full_attention_cost(
        ctx.shape, 700_000, 260_000, 512), PEAK)[0]
    value = reader(PAIRED).read(ctx)
    assert value == pytest.approx(100 * need / (4.8e-3 / 3))
    assert 0 < value < 100
    # (the accepted reader would read the same, were the cell on its list)
    assert reader("full_attention_roofline_pct").read(ctx) == value
    assert reader("expert_ms_per_step").read(ctx) == pytest.approx(10.0)
    assert reader("experts_touched_pct").read(ctx) == pytest.approx(100.0)
    # no other family's state layer, no window layer and no selection here
    for other in ("gdn_ms_per_step", "gdn_roofline_pct", "kda_ms_per_step",
                  "gated_attention_roofline_pct",
                  "window_attention_ms_per_step",
                  "sparse_paged_attention_ms_per_step"):
        assert reader(other).read(ctx) is None, other


@pytest.mark.parametrize("name", [PAIRED, DECODE])
def test_a_reader_says_nothing_without_the_convolutions_counts(name, cfg, fam,
                                                               monkeypatch):
    """On the parent's program (no such counter) and on another family's
    trace (``attended_full`` and ``decode_slots``-free counts of window and
    full layers, or Gated DeltaNet's) a new reader returns None, not 0, and
    does not raise."""
    mod = reader(name)
    reduced = SimpleNamespace(op_seconds=lambda rx: 4.8e-3,
                              spans={"bench/engine.step": [1, 2, 3]})
    ctx = SimpleNamespace(reduced=reduced, family=fam,
                          shape=fam.shape_of(cfg), flops=flops, peak=PEAK,
                          counters=dict(token_budget=256))
    theirs = {"steps": 3.0, "rows": 768.0, "attended_full": 9e5,
              "fetched_full": 2e5, "gdn_rows": 768.0, "gdn_state_slots": 12.0}
    for counts in (theirs, None):
        monkeypatch.setattr(kinds_trace, "step_counts", lambda c: counts)
        assert mod.read(ctx) is None
    ours = {**theirs, "conv_rows": 768.0, "conv_state_slots": 160.0,
            "decode_slots": 150.0}
    monkeypatch.setattr(kinds_trace, "step_counts", lambda c: ours)
    value = mod.read(ctx)
    if name == DECODE:
        assert value == pytest.approx(50.0)
        return
    assert 0 < value < 100
    reduced.op_seconds = lambda rx: 0.0
    assert mod.read(ctx) is None
    other = SimpleNamespace(**{**vars(ctx), "family": SimpleNamespace()})
    assert mod.read(other) is None


@pytest.mark.parametrize("fault", reference.family("lfm2_moe").FAULTS)
def test_a_fault_changes_the_reference_at_the_rehearsals_sizes(cfg, fam,
                                                              fault):
    """The reference the benchmark judges by, at the rehearsal's sizes with
    weights drawn as ``run.py`` draws their kinds (the selection bias and
    the taps normal like every leaf): every name of ``FAULTS`` moves its
    logits (tests/test_lfm2.py holds them at a bias in U(-0.5, 0.5))."""
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
    ids = np.random.default_rng(0).integers(0, shape.vocab, 300)
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
         "--workload", CELL, "--seed", "6500000011", "--seconds", "3",
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
