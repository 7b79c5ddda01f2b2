"""MoE dispatch A/B benchmark: train tokens/sec for the two formulations.

``moe_dispatch`` picks how tokens reach experts (moe/sharded_moe.py):
"einsum" (one-hot dispatch dots — MXU work, zero gather/scatter) vs
"gather" (index tables — O(N·D·K) moved bytes, no one-hot FLOPs). Which
wins is a hardware question (MXU headroom vs HBM headroom), so it must be
measured on the chip, once per mode. Prints one JSON line:
  {"moe_tok_s": ..., "dispatch": "einsum"|"gather", ...}

Usage:  python tools/bench_moe.py [--dispatch einsum|gather] [--steps N]
CPU smoke: BENCH_SMOKE=1 (tiny model, interpret kernels).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dispatch", default="einsum",
                    choices=["einsum", "gather"])
    ap.add_argument("--steps", type=int, default=5,
                    help="steps per timed chain (one dispatch per chain)")
    args = ap.parse_args()

    from bench import enable_compile_cache, smoke_mode

    smoke = smoke_mode()  # before any backend init
    enable_compile_cache()

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import mixtral

    # ~8 active of ~500M total params on the full config: big enough that
    # dispatch costs show, small enough that weights + adam + master fp32
    # (~7 GB) leave activation room on the 16 GB chip
    model = mixtral(
        "mixtral-tiny",
        vocab_size=1024 if smoke else 32768,
        max_seq_len=128 if smoke else 2048,
        hidden_size=128 if smoke else 1024,
        num_layers=2 if smoke else 8,
        num_heads=8,
        num_kv_heads=4,
        head_dim=16 if smoke else 128,
        intermediate_size=256 if smoke else 2048,
        num_experts=4 if smoke else 8,
        moe_top_k=2,
        moe_dispatch=args.dispatch,
    )
    B, S = (4, 128) if smoke else (8, 2048)
    dp = max(len(jax.devices()), 1)
    micro = max(B // dp // 2, 1)
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": B,
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
        "activation_checkpointing": {"policy": "dots_flash"},
    })
    rng = np.random.RandomState(0)
    data = {"input_ids": rng.randint(0, model.config.vocab_size,
                                     size=(B, S))}
    staged = engine.prepare_batch(data)
    chain = max(2 if smoke else args.steps, 1)
    engine.train_batch_chain(batch=staged, steps=chain)  # compile
    # a host read of engine.state.step both settles the warmup tail
    # before t0 and fences the timed chain
    float(engine.state.step)
    t0 = time.perf_counter()
    engine.train_batch_chain(batch=staged, steps=chain)
    float(engine.state.step)
    dt = time.perf_counter() - t0
    step_s = dt / chain
    print(json.dumps({
        "moe_tok_s": round(B * S / step_s, 1),
        "step_s": round(step_s, 4),
        "dispatch": args.dispatch,
        "params_m": round(model.num_params() / 1e6, 1),
        "steps": chain,
        "smoke": smoke,
    }))


if __name__ == "__main__":
    main()
