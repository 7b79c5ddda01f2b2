"""shardlint over real engine configs: the suite's own, the shipped
examples, and the bench legs (via the CLI — the tier-1 flow hook).

conftest records every (config, model, topology) the suite constructs an
engine from; here each unique one is rebuilt as an abstract engine
(ShapeDtypeStruct state — no compute) and linted. Configs abstract_init
refuses are skipped loudly, never passed silently.
"""

import json
import os
import subprocess
import sys

import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as comm
from deepspeed_tpu.analysis import lint_engine
from deepspeed_tpu.models import gpt2

import conftest

pytestmark = pytest.mark.shardlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# cap re-linted captured configs to keep the default suite fast; skipped
# ones are reported in the assertion message, not silently dropped
MAX_CAPTURED = 24

# configs the important subsystems run under — linted even when test
# selection (-k) means nothing was captured before this file executes
CURATED = [
    ("zero0-bf16", {
        "train_batch_size": 16, "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
    }),
    ("zero3-accum", {
        "train_batch_size": 32, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 3},
    }),
    ("zero3-offload-serial", {
        "train_batch_size": 16,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "offload_optimizer": {"device": "cpu"}},
    }),
    ("zero3-offload-double-buffer", {
        "train_batch_size": 16,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "offload_optimizer": {"device": "cpu"},
                              "offload_double_buffer": True},
    }),
    ("fp16-dynamic-scale", {
        "train_batch_size": 16,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "fp16": {"enabled": True},
        "zero_optimization": {"stage": 2},
    }),
]


def _lint_one(name, cfg, model, topology, failures, skipped):
    comm.destroy_process_group()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=cfg, topology=topology, abstract_init=True
        )
    except NotImplementedError as e:
        skipped.append((name, str(e).splitlines()[0]))
        return
    try:
        report = lint_engine(engine, source=name)
    finally:
        engine.destroy()
    if not report.ok:
        failures.extend(f.format() for f in report.errors)


def test_curated_suite_configs_lint_clean(devices8):
    failures, skipped = [], []
    for name, cfg in CURATED:
        model = gpt2("gpt2-tiny", vocab_size=128, max_seq_len=16)
        _lint_one(name, dict(cfg), model, None, failures, skipped)
    assert not failures, "\n".join(failures)
    assert not skipped, skipped  # curated configs must all trace on CPU


def test_dim0_sharded_stacked_leaves_lint_clean(devices8):
    """The PR-1 bug shape itself: L is the largest dp-divisible dim, so
    add_data_axes shards the stacked layer dim. With the resting re-put
    fix the bucketed scan must lint closed (R2) instead of being gated
    off."""
    model = gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16,
                 hidden_size=12, num_layers=8, num_heads=2,
                 intermediate_size=12)
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {
            "stage": 3,
            "stage3_param_persistence_threshold": 0,
            "offload_optimizer": {"device": "cpu"},
        },
    }
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=cfg, abstract_init=True
    )
    assert engine._bucketed_opt is not None  # the gate is gone
    report = lint_engine(engine, source="dim0-sharded-bucketed")
    assert report.ok and not report.findings, report.format()


def test_captured_suite_configs_lint_clean(devices8):
    """Lint every unique engine config the suite constructed before this
    file ran (conftest.SHARDLINT_CAPTURE). Alphabetical file order means
    roughly half the suite has executed by now — the curated list above
    covers the rest deterministically."""
    captured = list(conftest.SHARDLINT_CAPTURE)
    if not captured:
        pytest.skip("no engine configs captured (selective run)")
    failures, skipped = [], []
    linted = 0
    for cfg_raw, model, topology in captured[:MAX_CAPTURED]:
        name = f"captured[{linted}]"
        _lint_one(name, dict(cfg_raw), model, topology, failures, skipped)
        linted += 1
    over = len(captured) - MAX_CAPTURED
    assert not failures, (
        "\n".join(failures)
        + (f"\n(+{over} configs beyond the lint cap)" if over > 0 else "")
    )
    # only what abstract_init refuses may skip
    for name, why in skipped:
        assert "abstract_init" in why, (name, why)


def test_cli_all_examples_clean_and_fast(devices8, tmp_path):
    """The tier-1 flow hook: tools/shardlint.py --all-examples must exit 0
    with zero findings on every shipped examples/ config and the
    analysis/targets.py 410M/1.5B targets, each analyzed in < 30 s (ISSUE 2 acceptance)."""
    out = tmp_path / "shardlint.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "shardlint.py"),
         "--all-examples", "--json", str(out)],
        capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(out.read_text())
    assert payload["ok"]
    assert payload["findings"] == []
    names = [s["source"] for s in payload["sources"]]
    assert "examples/ds_config_zero3.json" in names
    assert "bench-410m" in names
    assert "bench-1b-offload" in names and "bench-1b-offload-db" in names
    for s in payload["sources"]:
        assert s.get("skipped") is None, s
        assert s["seconds"] < 30.0, s


def test_lint_config_rejects_modelless_call():
    from deepspeed_tpu.analysis import lint_config

    with pytest.raises(ValueError, match="model"):
        lint_config({"train_batch_size": 8})
