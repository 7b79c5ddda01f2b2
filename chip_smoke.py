#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that deepspeed_tpu still starts on the chip.

    python chip_smoke.py              # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4    # four chips: sharded training vs one device
    python chip_smoke.py --rehearse   # sandbox rehearsal: CPU, tiny presets

One process, the entry points a user calls (``deepspeed_tpu.initialize``,
``init_serving``, ``init_inference``), no retry ladder, no phase that may fail
while the run exits 0: any failed check raises and the exit code is non-zero.

Default run (one chip):

1. train — BLOOM-560m exactly as published (hidden 1024, 24 layers, 16 heads,
   vocab 250880, ALiBi, seq 2048), bf16, AdamW, ZeRO-0, ``tpu_kernels`` at
   their defaults. Four steps on one repeated batch: loss finite and falling,
   ONE trace of the step, the compiled step holds ``tpu_custom_call``; then a
   checkpoint is saved and loaded into a fresh engine whose next loss matches.
2. serve — Mixtral-8x7B widths (hidden 4096, 32/8 heads, hd 128, expert width
   14336, 8 experts, top-2, vocab 32000), depth cut to what the chip's memory
   holds, paged KV arena. 8 greedy requests (prompts 64..1024, 32 new tokens)
   run to completion with ONE trace of the slot step; two are checked against
   ``init_inference(...).generate`` on the same weights.

A kernel→XLA fallback logged on either path fails the run.

``--chips 4`` runs only the sharded comparison: the train phase's model and
global batch under ZeRO-3 on dp=4 and under dp=2 x tp=2, against the
one-device engine, three steps each, in this one process.

The last line of stdout is one JSON object. On a TPU it is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase and prints no
result; ``--rehearse`` names the platform it really ran on and never prints
``"ok"``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

SEED = 0

# ---- what the phases run ---------------------------------------------------
# Train: micro-batch 4 x seq 2048 with full activation remat. The chip's own
# compiler (described v5e, this sandbox) accounts 10.0 GiB for that step
# program — 6.7 GB of fp32 params + AdamW moments, the rest activations — so
# it fits 16 GB with room; without remat the same step needs 14.1 GiB at
# micro-batch 1. Four is also the smallest global batch dp=4 divides.
TRAIN = dict(size="bloom-560m", overrides={}, micro=4, remat="full",
             lr=3e-4, steps=4)
TRAIN_TINY = dict(size="bloom-tiny", overrides=dict(vocab_size=512,
                  max_seq_len=128), micro=4, remat="full", lr=3e-3, steps=4)
# Serve: token_budget 128 is a SplitFuse chunk a deployment would run; the
# 1024-token prompt prefills over 8 steps. moe_capacity_factor 4 is PR 14's
# no-drop condition (capacity_factor * top_k >= num_experts): routing is then
# independent of batch composition, which serving == generate needs. The two
# requests checked against generate have 128-aligned prompts (128 and 1024):
# generate prefills in one shot through the flash kernel, which has no tile
# for a 64-token sequence and would log its fallback to XLA.
SERVE = dict(size="mixtral-8x7b", overrides=dict(moe_capacity_factor=4.0),
             prompts=(64, 128, 192, 256, 384, 512, 768, 1024), new_tokens=32,
             slots=8, token_budget=128, page_size=16, checked=(1, 7))
SERVE_TINY = dict(size="mixtral-tiny", overrides=dict(
                  moe_capacity_factor=2.0, vocab_size=512, max_seq_len=256),
                  prompts=(8, 16, 24, 32, 48, 64, 96, 128), new_tokens=8,
                  slots=8, token_budget=16, page_size=16, checked=(1, 7))
# A serving token must be a near-argmax of the reference's logits for the
# same prefix. Logits here are ~N(0, 1.3) over 32000 tokens (max ~5): a
# wrong page, position or expert lands several units below the max, while
# the bf16 rounding that separates chunked-prefill XLA attention from the
# one-shot flash/decode kernels moves a logit by a few hundredths (0.007 at
# most on the chip in PR 22's runs).
LOGIT_TOL = 0.25
# One device vs four: same math, different reduction trees (bf16 operands,
# fp32 accumulation; tp splits every contraction in two, dp splits the batch
# mean in four), then AdamW steps on top. 1% of a loss near ln(250880)=12.4.
MULTICHIP_RTOL = 1e-2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class CacheCounter:
    """Persistent compile-cache hits and misses, as jax reports them."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self) -> str:
        return f"hits={self.hits} misses={self.misses}"


def hbm_line(dev) -> str:
    """``memory_stats()`` as the backend reports it: device buffers, not the
    temporaries a running program holds (XLA's accounting covers those)."""
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "peak HBM: not reported by this backend"
    return (f"peak HBM in buffers so far "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB, in use now "
            f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB")


def release(engine, label: str) -> None:
    """Tear an engine down and prove its device state is gone: the next
    engine needs the memory. The caller drops its own name for it next."""
    import jax

    import deepspeed_tpu.comm as comm

    state_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        (engine.state.params, engine.state.opt_state)))
    engine.destroy()
    engine.state = None  # the caller's name still holds the engine object
    comm.destroy_process_group()
    gc.collect()
    left = sum(a.nbytes for a in jax.live_arrays())
    say(f"{label}: engine released, {left / 2**20:.1f} MiB of device arrays "
        f"still live (its state was {state_bytes / 2**20:.0f} MiB)")
    check(left < max(state_bytes // 10, 1 << 20),
          f"{label}: {left / 2**20:.0f} MiB still live after the engine "
          "was destroyed")


def kernel_calls(compiled_text: str, what: str, on_chip: bool) -> int:
    n = compiled_text.count("tpu_custom_call")
    if on_chip:
        check(n > 0, f"{what}: no tpu_custom_call in the compiled step — "
                     "the Pallas kernels did not reach the chip")
    return n


# ---------------------------------------------------------------- phase 1
def train_batch_for(model, spec):
    import numpy as np

    cfg = model.config
    ids = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, size=(spec["micro"], cfg.max_seq_len))
    return {"input_ids": ids}


def make_train_engine(spec, topology, zero_stage: int):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import bloom

    model = bloom(spec["size"], **spec["overrides"])
    engine, *_ = deepspeed_tpu.initialize(
        model=model, topology=topology, rng=jax.random.PRNGKey(SEED),
        config={
            # the GLOBAL batch: one device takes it whole, dp=4 a row each
            "train_batch_size": spec["micro"],
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw", "params": {"lr": spec["lr"]}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": zero_stage},
            "activation_checkpointing": {"policy": spec["remat"]},
        },
    )
    return model, engine


def run_steps(engine, batch, n: int, label: str):
    import numpy as np

    losses, times = [], []
    for i in range(n):
        t0 = time.perf_counter()
        loss = float(engine.train_batch(batch=batch))  # float() fences
        times.append(time.perf_counter() - t0)
        check(np.isfinite(loss), f"{label}: step {i + 1} loss is {loss}")
        losses.append(loss)
    say(f"{label}: losses {['%.4f' % x for x in losses]}; first call "
        f"{times[0]:.1f} s (compile included), later steps "
        f"{['%.3f' % t for t in times[1:]]} s (smoke timings, not a "
        "benchmark)")
    return losses


def phase_train(spec, dev, on_chip: bool) -> None:
    from deepspeed_tpu.analysis.shardlint import lower_train_step
    from deepspeed_tpu.comm import MeshTopology, ParallelDims

    def one_device():
        return MeshTopology(dims=ParallelDims(), devices=[dev])

    model, engine = make_train_engine(spec, one_device(), zero_stage=0)
    c = model.config
    say(f"train: {c.name} hidden={c.hidden_size} layers={c.num_layers} "
        f"heads={c.num_heads} vocab={c.vocab_size} pos={c.pos_embedding} "
        f"norm={c.norm} seq={c.max_seq_len} params={model.num_params():,}; "
        f"bf16 AdamW ZeRO-0, micro_batch={spec['micro']}, "
        f"remat={spec['remat']}, tpu_kernels at defaults")
    batch = train_batch_for(model, spec)
    losses = run_steps(engine, batch, spec["steps"], "train")
    check(losses[-1] < losses[0],
          f"train: loss did not fall on the repeated batch: {losses}")
    check(engine.step_traces == 1,
          f"train: the step traced {engine.step_traces} times, expected 1")

    compiled = lower_train_step(engine).compile()
    n = kernel_calls(compiled.as_text(), "train", on_chip)
    ma = compiled.memory_analysis()
    say(f"train: step program holds {n} tpu_custom_call(s); XLA accounts "
        f"{(ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30:.2f}"
        f" GiB (arguments + temporaries); {hbm_line(dev)}")

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        t0 = time.perf_counter()
        engine.save_checkpoint(ckpt, tag="smoke")
        say(f"train: checkpoint saved in {time.perf_counter() - t0:.1f} s")
        want = float(engine.train_batch(batch=batch))
        del compiled
        release(engine, "train")
        del engine
        _, fresh = make_train_engine(spec, one_device(), zero_stage=0)
        t0 = time.perf_counter()
        fresh.load_checkpoint(ckpt, tag="smoke")
        say(f"train: checkpoint loaded into a fresh engine in "
            f"{time.perf_counter() - t0:.1f} s")
        got = float(fresh.train_batch(batch=batch))
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    # same program, same state, same batch: the loss is reproduced, not
    # approximated (a last-digit slack for a cache-reloaded executable)
    check(abs(got - want) <= 1e-6 * abs(want),
          f"train: loss after reload {got!r} != loss before {want!r}")
    say(f"train: next loss after reload {got:.6f} == {want:.6f} before")
    release(fresh, "train (fresh engine)")


# ---------------------------------------------------------------- phase 2
def serve_depth(cfg, hbm_bytes: float, kv_bytes_per_layer: int) -> int:
    """Layers of this MoE width one chip holds in bf16. Two bounds: the
    resident weights plus arena, and the draw — models/transformer.init
    makes each stacked expert leaf [L, E, d, f] in float32, scales it
    (a second float32 buffer) and only then casts, while the leaves drawn
    before it are already resident."""
    d, f, E = cfg.hidden_size, cfg.ffn, cfg.num_experts
    attn = d * cfg.num_heads * cfg.hd * 2 + d * cfg.kv_heads * cfg.hd * 2
    expert_leaf = E * d * f                      # elements per layer
    n_expert_leaves = 3 if cfg.activation == "swiglu" else 2
    embed = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    budget = 0.9 * hbm_bytes                     # allocator + step workspace
    depth = 0
    for L in range(1, cfg.num_layers + 1):
        resident = 2 * (embed + L * (attn + n_expert_leaves * expert_leaf))
        resident += L * kv_bytes_per_layer
        # worst draw: the last expert leaf, the others already cast
        draw = 2 * (embed + L * (attn + (n_expert_leaves - 1) * expert_leaf))
        draw += 2 * 4 * L * expert_leaf
        if max(resident + (1 << 30), draw) > budget:
            break
        depth = L
    return depth


def phase_serve(spec, dev, hbm_bytes: float, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.config import ServingConfig
    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.models.mixtral import mixtral_config
    from deepspeed_tpu.serving import Request

    new = spec["new_tokens"]
    max_tokens = max(spec["prompts"]) + new
    serving = {"max_slots": spec["slots"],
               "token_budget": spec["token_budget"],
               "max_tokens": max_tokens, "paged": True,
               "page_size": spec["page_size"]}
    full = mixtral_config(spec["size"], **spec["overrides"])
    pages = spec["slots"] * ServingConfig(**serving).pages_per_slot(max_tokens)
    # K and V, bf16, one layer of the arena
    kv_per_layer = pages * spec["page_size"] * full.kv_heads * full.hd * 2 * 2
    depth = serve_depth(full, hbm_bytes, kv_per_layer)
    check(depth >= min(2, full.num_layers),
          f"serve: only {depth} layer(s) of {spec['size']} fit "
          f"{hbm_bytes / 2**30:.0f} GiB")
    model = mixtral(spec["size"], num_layers=depth, **spec["overrides"])
    c = model.config
    check(c.moe_capacity_factor * c.moe_top_k >= c.num_experts,
          "serve: expert capacity below the no-drop condition")
    say(f"serve: {c.name} widths hidden={c.hidden_size} heads={c.num_heads}"
        f"/{c.kv_heads} hd={c.hd} ffn={c.ffn} experts={c.num_experts} "
        f"top{c.moe_top_k} vocab={c.vocab_size}; depth cut {full.num_layers}"
        f" -> {depth} layers ({model.num_params():,} params, bf16) for "
        f"{hbm_bytes / 2**30:.0f} GiB; capacity_factor="
        f"{c.moe_capacity_factor} (no drops)")

    t0 = time.perf_counter()
    srv = deepspeed_tpu.init_serving(
        model, serving=serving, dtype=jnp.bfloat16,
        rng=jax.random.PRNGKey(SEED), replace_with_kernel_inject=True,
    )
    jax.block_until_ready(srv.engine.params)
    say(f"serve: weights drawn in {time.perf_counter() - t0:.1f} s; paged "
        f"arena {srv.num_pages} pages x {srv.page_size} tokens; "
        f"{hbm_line(dev)}")

    rs = np.random.RandomState(SEED + 1)
    prompts = [rs.randint(0, c.vocab_size, size=(n,)) for n in spec["prompts"]]
    states = [
        srv.submit(Request(request_id=f"r{i}", prompt=p, max_new_tokens=new))
        for i, p in enumerate(prompts)
    ]
    t0 = time.perf_counter()
    srv.step()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.run_until_idle()
    drain = time.perf_counter() - t0
    steps = srv.metrics.steps
    for st, p in zip(states, prompts):
        check(len(st.tokens) == new,
              f"serve: {st.request.request_id} (prompt {len(p)}) produced "
              f"{len(st.tokens)} tokens, expected {new}; status {st.status}")
    check(srv.step_traces == 1,
          f"serve: the slot step traced {srv.step_traces} times, expected 1")
    say(f"serve: {len(states)} requests (prompts {list(spec['prompts'])}, "
        f"{new} new tokens each) finished in {steps} slot steps, one trace; "
        f"first step {first:.1f} s (compile included), the other "
        f"{steps - 1} took {drain:.2f} s (smoke timings, not a benchmark)")

    n = kernel_calls(srv.lower_step().compile().as_text(), "serve", on_chip)
    say(f"serve: slot-step program holds {n} tpu_custom_call(s) (the norm "
        f"kernels and the attention); attention path {srv.attention_path}"
        + (f", because: {'; '.join(srv.attention_fallback)}"
           if srv.attention_fallback else ""))

    # the reference: lockstep generate on the SAME weights. It runs the
    # flash prefill kernel and the dense decode kernel (KV8 hd128).
    ref = deepspeed_tpu.init_inference(
        model, params=srv.engine.params, dtype=jnp.bfloat16,
        max_tokens=max_tokens, replace_with_kernel_inject=True,
    )
    # one common width, rounded up to the flash kernel's 128 tile (causal
    # attention makes the right-padding harmless)
    width = -(-(max(len(prompts[i]) for i in spec["checked"]) + new)
              // 128) * 128
    rows = np.zeros((len(spec["checked"]), width), np.int64)
    exact = []
    for r, i in enumerate(spec["checked"]):
        got = np.asarray(states[i].output())
        want = np.asarray(ref.generate(
            prompts[i][None, :], max_new_tokens=new, temperature=0.0))[0]
        check(got.shape == want.shape and
              np.array_equal(got[:len(prompts[i])], prompts[i]),
              f"serve: r{i} output does not start with its prompt")
        same = int((got == want).sum()) - len(prompts[i])
        exact.append(same == new)
        say(f"serve: r{i} (prompt {len(prompts[i])}) vs generate: "
            + ("token for token" if same == new else
               f"first {_common(got, want) - len(prompts[i])} of {new} new "
               "tokens equal, then the greedy paths part (bf16 argmax)"))
        rows[r, :len(got)] = got
    # teacher-forced logits: one forward over [prompt + served tokens]
    logits = np.asarray(ref.forward(rows), np.float32)
    worst = 0.0
    for r, i in enumerate(spec["checked"]):
        P = len(prompts[i])
        for t in range(new):
            row = logits[r, P + t - 1]
            worst = max(worst, float(row.max() - row[rows[r, P + t]]))
    say(f"serve: served tokens sit within {worst:.4f} of the reference's "
        f"max logit (tolerance {LOGIT_TOL}; logits std "
        f"{float(logits.std()):.2f})")
    check(worst <= LOGIT_TOL,
          f"serve: a served token is {worst:.3f} below the reference's max "
          f"logit (tolerance {LOGIT_TOL})")
    say(f"serve: {hbm_line(dev)}")
    del srv, ref, states, logits
    gc.collect()


def _common(a, b) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


# ----------------------------------------------------------- four chips
def state_bytes_per_device(engine) -> dict:
    import jax

    per = {}
    for leaf in jax.tree_util.tree_leaves(
            (engine.state.params, engine.state.opt_state)):
        for s in leaf.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    return per


def phase_multichip(spec, devices, on_chip: bool) -> None:
    import numpy as np

    from deepspeed_tpu.analysis.shardlint import lower_train_step
    from deepspeed_tpu.comm import MeshTopology, ParallelDims

    steps = 3
    legs = [
        ("one device, ZeRO-0", ParallelDims(), devices[:1], 0),
        ("ZeRO-3 dp=4", ParallelDims(dp=4), devices, 3),
        ("dp=2 x tp=2, ZeRO-0", ParallelDims(dp=2, tp=2), devices, 0),
    ]
    ref_losses = ref_total = None
    for label, dims, devs, stage in legs:
        model, engine = make_train_engine(
            spec, MeshTopology(dims=dims, devices=list(devs)), stage)
        say(f"{label}: {engine.topology}, {model.config.name}, global batch "
            f"{spec['micro']} x seq {model.config.max_seq_len}")
        losses = run_steps(engine, train_batch_for(model, spec), steps, label)
        check(engine.step_traces == 1,
              f"{label}: the step traced {engine.step_traces} times")
        per = state_bytes_per_device(engine)
        total = sum(per.values())
        say(f"{label}: params + optimizer bytes per device "
            f"{ {d: round(b / 2**20) for d, b in sorted(per.items())} } MiB")
        text = lower_train_step(engine).compile().as_text()
        kernel_calls(text, label, on_chip)
        if ref_losses is None:
            ref_losses, ref_total = losses, total
        else:
            np.testing.assert_allclose(
                losses, ref_losses, rtol=MULTICHIP_RTOL,
                err_msg=f"{label} vs the one-device engine")
            say(f"{label}: losses within "
                f"{max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)):.2e}"
                f" of one device (tolerance {MULTICHIP_RTOL})")
            check(len(per) == len(devs),
                  f"{label}: state on {len(per)} of {len(devs)} devices")
        if stage == 3:
            # nothing whole on device 0: every device holds about a quarter
            # (small leaves under the persistence threshold stay replicated)
            # (the rehearsal's tiny model is mostly such leaves)
            hi = 0.3 if on_chip else 0.5
            for d, b in per.items():
                check(0.2 <= b / ref_total <= hi,
                      f"{label}: device {d} holds {b / ref_total:.2f} of "
                      "the state, expected about a quarter")
            # (XLA:CPU spells the collectives differently: chip only)
            for op in ("all-gather", "reduce-scatter") if on_chip else ():
                check(op in text, f"{label}: no {op} in the compiled step")
            say(f"{label}: each device holds about a quarter of the state"
                + ("; the compiled step has its all-gather and "
                   "reduce-scatter" if on_chip else ""))
        elif len(devs) > 1:
            check("all-reduce" in text,
                  f"{label}: no all-reduce in the compiled step")
        release(engine, label)
        del engine


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox rehearsal on the CPU at tiny presets; "
                         "never prints the chip's ok line")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    try:
        import jax
        import jaxlib

        from deepspeed_tpu.analysis.cost import (HardwareModel,
                                                 gen_from_device_kind)
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
        from deepspeed_tpu.utils.logging import fallback_log_seen
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: no TPU here (jax found {device}); nothing was "
              "run. --rehearse runs the tiny CPU rehearsal.", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found {device}",
              file=sys.stderr)
        return 3
    if on_chip and gen_from_device_kind(dev.device_kind) is None:
        print(f"chip_smoke: device_kind {dev.device_kind!r} is not in the "
              "peak table (analysis/cost/hardware.py); nothing was run.",
              file=sys.stderr)
        return 3
    hw = HardwareModel.detect()
    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu_version}; device {device}; peak-table row {hw.gen} "
        f"({hw.peak_flops / 1e12:.0f} TFLOP/s bf16, "
        f"{hw.hbm_bytes / 2**30:.0f} GiB HBM)")
    say(f"compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'default under the checkout'}), "
        f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start")
    if args.rehearse:
        say("REHEARSAL on the CPU at tiny presets, kernels in interpret "
            "mode: control flow only, nothing here is a chip result")
    train = TRAIN_TINY if args.rehearse else TRAIN
    serve = SERVE_TINY if args.rehearse else SERVE

    t_all = time.perf_counter()
    if args.chips == 4:
        phase_multichip(train, devices[:4], on_chip)
    else:
        t0 = time.perf_counter()
        phase_train(train, dev, on_chip)
        say(f"train phase done in {time.perf_counter() - t0:.1f} s; "
            f"compile cache {cache}")
        t0 = time.perf_counter()
        phase_serve(serve, dev, hw.hbm_bytes, on_chip)
        say(f"serve phase done in {time.perf_counter() - t0:.1f} s; "
            f"compile cache {cache}")
    say("note: the dense decode kernel at head_dim 64 with several KV heads "
        "(BLOOM, GPT-2) steps aside to XLA on a TPU and logs it "
        "(ops/pallas/decode_attention.py); no phase here has that shape")
    say(f"kernel->XLA fallbacks logged: {sorted(fallback_log_seen) or 'none'}")
    check(not fallback_log_seen,
          f"a kernel fell back to XLA on a main path: {sorted(fallback_log_seen)}")
    say(f"all phases passed in {time.perf_counter() - t_all:.1f} s; compile "
        f"cache {cache} ({'warm' if cache.hits and not cache.misses else 'cold or partly cold'})")
    if on_chip:
        print(json.dumps({"ok": True, "device": device}))
    else:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
