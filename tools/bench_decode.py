"""Inference decode benchmark: tokens/sec on the real chip.

The training bench (bench.py) is the driver-facing metric; this companion
measures the latency-critical decode loop (reference headline:
DeepSpeed-Inference kernel injection serving). Prints one JSON line:
  {"decode_tok_s": ..., "prefill_s": ..., "kernel_inject": ...}

Usage:  python tools/bench_decode.py [--no-inject] [--dtype bf16|int8|int4]
CPU smoke: BENCH_SMOKE=1 (tiny model, interpret kernels).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cyclic_pool(vocab, smoke):
    """The workload's fixed pattern pool (seeded: train and serve agree).

    A small pool makes the task memorization, not induction — a fresh
    410M model learns 4 repeated token patterns in minutes on one chip,
    whereas in-context copying of NOVEL patterns (induction) needs orders
    of magnitude more tokens to emerge. Serving a memorized/templated
    continuation is exactly the boilerplate-generation case prompt-lookup
    speculation targets."""
    r = np.random.RandomState(123)
    periods = [4] if smoke else [8, 11, 13, 16]
    return [r.randint(0, vocab, size=p) for p in periods]


def _train_cyclic(model, smoke):
    """Train the bench model on the fixed cyclic pool (~3 min on one
    v5e). The resulting greedy decode continues a pool prompt, so
    prompt-lookup drafts get real acceptance — the measured speedup is
    honest speculative decoding on the workload the technique targets (an
    UNtrained model's continuation is unpredictable by construction,
    which is why the random-workload leg shows speculation's worst
    case)."""
    import jax

    import deepspeed_tpu

    vocab = model.config.vocab_size
    S = 64 if smoke else 512
    B = 4 if smoke else 16
    steps = 4 if smoke else 250
    cfg = {
        "train_batch_size": B,
        "train_micro_batch_size_per_gpu": max(B // 4, 1),
        "gradient_accumulation_steps": min(B, 4),
        "bf16": {"enabled": not smoke},
        "activation_checkpointing": {"policy": "none" if smoke
                                     else "dots_flash"},
        "optimizer": {"type": "adamw",
                      "params": {"lr": 3e-4, "weight_decay": 0.0}},
        # fresh 410M + no warmup at lr 1e-3 diverged (final loss 11.1 >
        # ln V): warm up linearly, hold at 3e-4
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_min_lr": 0.0,
                                 "warmup_max_lr": 3e-4,
                                 "warmup_num_steps": 60,
                                 "warmup_type": "linear"}},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
    }
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    pool = _cyclic_pool(vocab, smoke)
    r = np.random.RandomState(0)
    last = None
    for _ in range(steps):
        rows = []
        for _b in range(B):
            pat = pool[r.randint(len(pool))]
            # random rotation: the model must continue the cycle from any
            # phase, which is what decoding from an arbitrary prompt needs
            k = r.randint(len(pat))
            pat = np.concatenate([pat[k:], pat[:k]])
            rows.append(np.tile(pat, S // len(pat) + 1)[:S])
        last = float(engine.train_batch(batch={"input_ids": np.stack(rows)}))
    print(f"# cyclic pretrain: {steps} steps, final loss {last:.3f}",
          file=sys.stderr)
    params = jax.tree.map(np.asarray, engine.state.params)
    engine.destroy()
    return params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-inject", action="store_true")
    ap.add_argument("--dtype", default="bf16",
                    choices=["bf16", "int8", "int4"])
    ap.add_argument("--kv-cache", default="auto",
                    choices=["auto", "bf16", "int8"],
                    help="KV cache storage (int8: quantized, half HBM)")
    ap.add_argument("--new-tokens", type=int, default=128)
    ap.add_argument("--speculative", action="store_true",
                    help="greedy speculative decoding (token-exact output); "
                    "--draft picks the proposer")
    ap.add_argument("--draft", default="ngram", choices=["ngram", "model"],
                    help="ngram: zero-cost prompt-lookup self-draft "
                    "(default); model: a 2-layer draft of the same family")
    ap.add_argument("--draft-tokens", type=int, default=5,
                    help="proposals per verifier forward")
    ap.add_argument("--workload", default="random",
                    choices=["random", "cyclic"],
                    help="cyclic: first train the model in-process on "
                    "period-repeated token sequences, then decode a cyclic "
                    "prompt — greedy output continues the cycle, which is "
                    "the induction workload prompt-lookup speculation "
                    "targets (random prompts give ~0 acceptance by "
                    "construction: an untrained model's continuation is "
                    "unpredictable)")
    args = ap.parse_args()
    if args.new_tokens <= 4 and not os.environ.get("BENCH_SMOKE"):
        ap.error("--new-tokens must be > 4 (4 tokens are folded into the "
                 "prefill-timing run; the decode rate would be degenerate)")

    from bench import smoke_mode

    smoke = smoke_mode()  # before any backend init

    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    model = llama(
        "llama-tiny",
        vocab_size=1024 if smoke else 32768,
        max_seq_len=256 if smoke else 2048,
        hidden_size=128 if smoke else 1024,
        num_layers=2 if smoke else 24,
        num_heads=8,
        num_kv_heads=4,
        head_dim=16 if smoke else 128,
        intermediate_size=512 if smoke else 4096,
    )
    draft = None
    if args.speculative and args.draft == "ngram":
        draft = "ngram"
    elif args.speculative:
        # head_dim 128 keeps the DRAFT on the Pallas decode kernel too —
        # the draft loop is the latency-critical part of speculation, and
        # hd=64 silently fell back to the XLA path (r4 decode bench logs)
        draft = llama(
            "llama-tiny",
            vocab_size=1024 if smoke else 32768,
            max_seq_len=256 if smoke else 2048,
            hidden_size=128 if smoke else 512,
            num_layers=2,
            num_heads=8 if smoke else 4,
            num_kv_heads=4 if smoke else 2,
            head_dim=16 if smoke else 128,
            intermediate_size=512 if smoke else 2048,
        )
    params = _train_cyclic(model, smoke) if args.workload == "cyclic" else None
    engine = deepspeed_tpu.init_inference(
        model,
        tp_size=1,
        dtype={"bf16": jnp.bfloat16, "int8": "int8", "int4": "int4"}[args.dtype],
        replace_with_kernel_inject=not args.no_inject,
        kv_cache_dtype=args.kv_cache,
        max_tokens=256 if smoke else 2048,
        draft_model=draft,
        params=params,
    )
    B, prompt_len = 1, 16 if smoke else 128
    new = 16 if smoke else args.new_tokens
    if args.workload == "cyclic":
        # a pool prompt from the training distribution: greedy decode
        # continues the cycle, prompt-lookup proposes it from the buffer
        pat = _cyclic_pool(model.config.vocab_size, smoke)[0]
        prompt = np.tile(pat, prompt_len // len(pat) + 1)[None, :prompt_len]
    else:
        prompt = np.random.RandomState(0).randint(
            0, model.config.vocab_size, size=(B, prompt_len)
        )
    gen_kw = (
        {"num_draft_tokens": args.draft_tokens} if args.speculative else {}
    )
    engine.generate(prompt, max_new_tokens=4, **gen_kw)  # compile

    # median of 3: the host clock jitters per dispatch, and a single
    # noisy prefill sample lands 1:1 in the decode-rate
    # subtraction below (observed: the same build measuring 590 vs 744
    # tok/s bf16 purely from this term)
    pf = []
    for _ in range(3):
        t0 = time.perf_counter()
        engine.generate(prompt, max_new_tokens=4, **gen_kw)
        pf.append(time.perf_counter() - t0)
    prefill_s = float(np.median(pf))  # ~prefill + 4 steps

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = engine.generate(prompt, max_new_tokens=new, **gen_kw)
        np.asarray(out)
        times.append(time.perf_counter() - t0)
    dt = float(np.median(times))  # full generate time
    # decode-only rate: subtract the measured prefill(+4 steps) run. On a
    # noisy host dt can come in *below* the separately-timed prefill run;
    # report that honestly instead of clamping to an absurd rate.
    decode_s = dt - prefill_s
    decode_tok_s = round((new - 4) / decode_s, 1) if decode_s > 0 else None
    print(
        json.dumps(
            {
                "decode_tok_s": decode_tok_s,
                "decode_timing_valid": decode_s > 0,
                "generate_s": round(dt, 4),
                "prefill_s": round(prefill_s, 4),
                "new_tokens": new,
                "dtype": args.dtype,
                "kv_cache": args.kv_cache,
                "kernel_inject": not args.no_inject,
                "speculative": args.speculative,
                "draft": args.draft if args.speculative else None,
                "draft_tokens": (args.draft_tokens if args.speculative
                                 else None),
                "spec_rounds": getattr(engine, "last_spec_rounds", None),
                "smoke": smoke,
            }
        )
    )


if __name__ == "__main__":
    main()
