"""The static analysers' standing targets.

``tools/shardlint.py --all-examples`` (and ``tools/shardplan.py`` through
it), ``tools/autoplan.py --leg`` and the lint/plan tests all take their
(name, model, ds_config) triples from here. Models are config shells
only: the analysers trace them abstractly, nothing is materialized, so
the 1.4B target lints in seconds on CPU.

Analysers may import models and engines, never the reverse.
"""

from ..models import llama, mixtral

SEQ = 2048
BATCH = 16384 // SEQ  # the global batch holds 16384 tokens a step


def target_model(tag: str):
    """The Llama-shaped shell for ``tag``: "410m" or "1b".

    head_dim=128 matches the MXU lane width."""
    if tag == "1b":
        # ~1.4B params: bf16 weights+grads ~5.6 GB fit the 16 GB v5e, the
        # fp32 adam m/v + master (~17 GB) do NOT — precisely the shape
        # ZeRO-3 + pinned_host optimizer offload exists for
        return llama(
            "llama3-1b",
            vocab_size=32768,
            max_seq_len=SEQ,
            hidden_size=2048,
            num_layers=22,
            num_heads=16,
            num_kv_heads=8,
            head_dim=128,
            intermediate_size=8192,
        )
    if tag == "410m":
        return llama(
            "llama-tiny",
            vocab_size=32768,
            max_seq_len=SEQ,
            hidden_size=1024,
            num_layers=24,
            num_heads=8,
            num_kv_heads=4,
            head_dim=128,
            intermediate_size=4096,
        )
    raise ValueError(f"unknown target model {tag!r} (expected '410m' or '1b')")


def make_ds_config(B, zero, pol, micro, tk, tp=None):
    """ONE config builder for every target — separate inline dicts would
    silently drift apart as keys are added. ``tp`` optionally adds a
    tensor_parallel section."""
    cfg = {
        "train_batch_size": B,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": zero,
        "gradient_clipping": 1.0,
        "steps_per_print": 1000,
        "activation_checkpointing": {"policy": pol},
        "tpu_kernels": tk,
    }
    if tp:
        cfg["tensor_parallel"] = tp
    return cfg


def overlap_tp_section():
    """The tensor_parallel section of the tp-overlap target (decomposed
    collective matmul; parallel/tensor_overlap.py)."""
    return {
        "tp_size": 2,
        "overlap_comm": {
            "enabled": True,
            "chunks": 2,
            "bidirectional": True,
            "quantized_hops": False,
        },
    }


def moe_overlap_section():
    """The moe section of the a2a-overlap target (decomposed MoE
    all-to-all; parallel/a2a_overlap.py)."""
    return {
        "enabled": True,
        "ep_size": 2,
        "num_experts": 4,
        "overlap_a2a": {
            "enabled": True,
            "chunks": 2,
            "bidirectional": True,
        },
    }


def _batch_for(dp: int):
    """(global batch rounded up to a multiple of dp, per-device micro)."""
    B = -(-BATCH // dp) * dp
    return B, max(B // dp, 1)


def lint_targets(dp: int):
    """(name, model, ds_config) for the engine configurations shardlint
    gates: the 410M target and the 1.5B ZeRO-3 + pinned-host-offload
    target, serial and double-buffered, plus the overlap targets
    (decomposed MoE a2a on an ep mesh; stage-3 one-layer prefetch) whose
    declared streams rule R8 must statically confirm fit the compute
    window."""
    model_410m = target_model("410m")
    model_1b = target_model("1b")
    B, micro = _batch_for(dp)
    tiles = {"flash_block_q": 512, "flash_block_k": 1024}
    offload = {"stage": 3, "offload_optimizer": {"device": "cpu"},
               "offload_param": {"device": "cpu"}}
    moe_model = mixtral(
        "mixtral-tiny", vocab_size=2048, max_seq_len=256, num_layers=4,
        num_experts=4,
    )
    # the moe target shapes its own batch: the lint mesh splits the 8
    # devices dp=4 × ep=2, so 16 = micro 2 × dp 4 × accum 2
    moe_cfg = make_ds_config(16, {"stage": 1}, "none", 2, {})
    moe_cfg["moe"] = moe_overlap_section()
    z3_cfg = make_ds_config(
        B,
        {"stage": 3, "stage3_param_persistence_threshold": 10**5,
         "stage3_layer_prefetch": True},
        "none", micro, {},
    )
    return [
        ("bench-410m", model_410m,
         make_ds_config(B, {"stage": 0}, "none", micro, {})),
        ("bench-410m-tp-overlap", model_410m,
         make_ds_config(B, {"stage": 0}, "none", micro, {},
                        tp=overlap_tp_section())),
        ("bench-moe-a2a", moe_model, moe_cfg),
        ("bench-410m-z3-prefetch", model_410m, z3_cfg),
        # the 1.5B pair stays LAST: the lint speed budget test times the
        # biggest target via lint_targets()[-1]
        ("bench-1b-offload", model_1b,
         make_ds_config(B, dict(offload), "dots_flash", 1, tiles)),
        ("bench-1b-offload-db", model_1b,
         make_ds_config(B, dict(offload, offload_double_buffer=True),
                        "dots_flash", 1, tiles)),
    ]


def autotune_rung_targets(dp: int):
    """(name, model, ds_config) for representative autotuner ladder
    rungs, appended to ``shardlint --all-examples``: the planner-driven
    search measures only statically-clean rungs, so the rungs themselves
    must stay lintable. Two rungs that differ from ``lint_targets``: a
    mid-ladder ZeRO-2 remat rung and the deepest ladder rung (stage 3 +
    cpu offload at max remat, the phase-0 escalation endpoint)."""
    model_410m = target_model("410m")
    B, micro = _batch_for(dp)
    return [
        ("autotune-rung-z2-dots_flash", model_410m,
         make_ds_config(B, {"stage": 2}, "dots_flash", micro, {})),
        ("autotune-rung-z3off-full", model_410m,
         make_ds_config(B, {"stage": 3,
                            "offload_optimizer": {"device": "cpu"}},
                        "full", 1, {})),
    ]
