#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It reads ``BENCHMARK.json`` for the cell, finds the cell's
configuration (``benchmarks/configs/<config>.json``) and its family
(``benchmarks/families/<family>.py``), traffic mix
(``benchmarks/traffic/<traffic>.json``) and per-layer readers
(``benchmarks/layer_metrics/<metric>.py``) by name, builds the engine
through the program's own entry points, warms up exactly the cell's shapes,
checks correctness against ``reference.py`` outside the window (a serving
cell: a small sample in set-up, and after the window the mix's precision
sample), measures for ``--seconds`` and prints ONE JSON object as the last
line of stdout.
Everything else it says goes to earlier lines. A new cell is new data files
and a manifest entry; nothing in this file names a cell, a configuration, a
family, a mix or a metric.

Without a TPU, with fewer chips than the cell asks for, with a
``device_kind`` missing from ``peaks.json`` or without the program beside it,
it exits non-zero and prints no result.

    --rehearse        the same control flow on the CPU at the tiny sizes the
                      data files give under "rehearse"; the last line says
                      rehearsal and carries no metric
    --sweep 1,2,3     (open-loop cells) one engine, one window per rate, to
                      find the knee; prints a table, no metric line
    --check-seeds a,b (serving cells) set-up and both correctness checks
                      only, one engine, each seed with its own weights and
                      prompts; prints the checks' lines and one ``correct`` a
                      seed, no window and no metric line; exits 1 if any was
                      incorrect
    --inject x,y      (with --check-seeds only) judge each seed again with the
                      reference broken on purpose: a name from the family's
                      FAULTS, or token_clear / token_tied (one served token a
                      request replaced, at a position clear of / inside a
                      near-tie in the routing). Each has to read incorrect,
                      but token_tied, the rule's known limit
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")  # git-ignored; traces land here
EXIT_NO_DEVICE = 3
# --inject names that change one served token and not the reference
TOKEN_FAULTS = ("token_clear", "token_tied")


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def die(msg: str, code: int = 2):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileCounter:
    """Compilations as jax reports them: every request to compile a program
    is a persistent-cache hit or miss."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def total(self) -> int:
        return self.hits + self.misses

    def __str__(self):
        return f"hits={self.hits} misses={self.misses}"


class Tracer:
    """The profiler, started late in the window and stopped after it."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.active = self.done = False
        self.dir = os.path.join(OUT_DIR, "trace")

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only: the python tracer
        opts.host_tracer_level = 2    # would write an event per call
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def maybe_start(self, now: float, at: float):
        if self.enabled and not self.active and not self.done and now >= at:
            self.start()

    def stop(self):
        import jax

        if self.active:
            jax.profiler.stop_trace()
            self.active, self.done = False, True

    def reduced(self):
        if not self.done:
            return None
        from benchmarks import trace_reduce

        return trace_reduce.reduce_trace(
            trace_reduce.load(self.dir, keep_stats=False))


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def seed_key(seed: int):
    """A jax key for any whole-number seed (the driver's pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def build_model(engine_cfg: dict):
    mod, fn = engine_cfg["model"]["factory"].split(":")
    factory = getattr(importlib.import_module(mod), fn)
    return factory(engine_cfg["model"]["size"],
                   **engine_cfg["model"].get("overrides", {}))


def check_shape(model, shape) -> None:
    """The program's model has the sizes the configuration file publishes."""
    c = model.config
    got = (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd, c.ffn,
           c.vocab_size, c.num_experts, c.moe_top_k if c.num_experts else 0,
           bool(c.tie_embeddings))
    want = (shape.d, shape.layers, shape.heads, shape.kv_heads, shape.hd,
            shape.ffn, shape.vocab, shape.experts, shape.top_k, shape.tied)
    if got != want:
        die(f"the program's model {got} is not the configuration file's {want}")


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


# ------------------------------------------------------------------ training
def run_train(ctx) -> dict:
    import numpy as np

    import deepspeed_tpu
    from benchmarks import loadgen
    from deepspeed_tpu.comm import MeshTopology, ParallelDims

    eng, mix, shape = ctx.config["engine"], ctx.mix, ctx.shape
    model = build_model(eng)
    check_shape(model, shape)
    seq = model.config.max_seq_len
    batch = int(eng["micro_batch_per_chip"]) * ctx.chips
    dims = {k: (ctx.chips if v == "chips" else int(v))
            for k, v in eng.get("parallel", {}).items()}
    topology = MeshTopology(dims=ParallelDims(**dims),
                            devices=list(ctx.devices))
    ds_config = dict(eng["ds_config"], train_batch_size=batch)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, topology=topology, rng=seed_key(ctx.seed),
        config=ds_config)
    say(f"train: {model.config.name} {model.num_params():,} params, global "
        f"batch {batch} x {seq} on {engine.topology}; engine built at "
        f"{time.monotonic() - T_START:.1f} s")

    batches = loadgen.train_batches(mix, ctx.seed, batch, seq,
                                    model.config.vocab_size,
                                    int(ctx.config["eos_token_id"]))
    first = next(batches)
    # ---- correctness 1: the engine's loss against the plain reference on a
    # seeded sequence of the first batch. The batch handed to the engine is
    # that one sequence in every row, so its mean loss IS the sequence's.
    cc = mix["correctness"]
    row = int(loadgen.rng_for(ctx.seed, 9).integers(batch))
    t0 = time.monotonic()
    want = ctx.family.loss(engine.state.params, first[row], shape,
                           device=ctx.devices[0])
    t_ref = time.monotonic() - t0
    t0 = time.monotonic()
    got = float(engine.train_batch(
        batch={"input_ids": np.tile(first[row][None], (batch, 1))}))
    say(f"train: first call {time.monotonic() - t0:.1f} s (compile or cache "
        f"load included); reference loss {want:.5f} ({t_ref:.1f} s), engine "
        f"loss {got:.5f}, relative difference {abs(got - want) / want:.2e} "
        f"(tolerance {cc['loss_rtol']})")
    ok = bool(np.isfinite(got) and abs(got - want) <= cc["loss_rtol"] * want)
    if not ok:
        say("INCORRECT: engine loss disagrees with the reference")
    for _ in range(int(mix["warmup_steps"]) - 1):
        float(engine.train_batch(batch={"input_ids": first}))
    traces_before = engine.step_traces

    feeder = loadgen.Feeder(batches, depth=int(mix["feeder_depth"]))
    tracer = Tracer(ctx.trace)
    losses, waits, step_s, parts = [], [], [], []
    compiles_before = ctx.compiles.total
    t_open = time.monotonic()
    setup_s = t_open - T_START
    elapsed = 0.0
    try:
        while elapsed < ctx.seconds:
            est = statistics.median(step_s) if step_s else 0.0
            tracer.maybe_start(
                elapsed, ctx.seconds - int(mix["trace_steps"]) * est
                if len(step_s) >= 3 else float("inf"))
            a = time.monotonic()
            with annotate("bench/feed"):
                b, waited = feeder.next()
            t_fed = time.monotonic()
            with annotate("bench/train_batch"):
                loss = engine.train_batch(batch={"input_ids": b})
            t_sent = time.monotonic()
            with annotate("bench/loss_fence"):
                losses.append(float(loss))
            now = time.monotonic()
            waits.append(waited)
            step_s.append(now - a)
            parts.append((t_fed - a, t_sent - t_fed, now - t_sent))
            elapsed = now - t_open
    finally:
        tracer.stop()
        feeder.close()
    # the window closes at the fence of the step that was in flight when
    # --seconds ran out: rate = all the work over all the time
    window_s = elapsed
    tokens = len(losses) * batch * seq
    in_window = ctx.compiles.total - compiles_before
    finite = bool(np.all(np.isfinite(losses)))
    fell = bool(len(losses) >= 6 and np.mean(losses[-5:]) < losses[0])
    retraced = engine.step_traces - traces_before
    med = statistics.median(step_s)
    say(f"train: {len(losses)} steps in {window_s:.2f} s; loss "
        f"{losses[0]:.4f} -> mean of last five {np.mean(losses[-5:]):.4f}; "
        f"step median {med:.4f} s, longest {max(step_s):.3f} s, longest wait "
        f"for data {1e3 * max(waits):.1f} ms; compilations inside the "
        f"window: {in_window}, retraces {retraced}")
    # a stall names its place: the wait for data, the call that hands the
    # step to the device, or the fence on its loss
    slow = [(i, t, *parts[i]) for i, t in enumerate(step_s) if t > 1.5 * med]
    say(f"train: {len(slow)} steps over 1.5 x the median" + "".join(
        f"; step {i}: {t:.3f} s = feed {f:.3f} + train_batch {d:.3f} + "
        f"loss_fence {w:.3f}" for i, t, f, d, w in slow[:8]))
    for cond, what in ((finite, "a loss in the window is not finite"),
                       (fell, "the loss did not fall over the window"),
                       (in_window == 0 and retraced == 0,
                        "something compiled inside the window")):
        if not cond:
            say(f"INCORRECT: {what}")
            ok = False
    rate = tokens / window_s / ctx.chips
    ctx.counters.update(
        steps=len(losses), window_s=window_s, data_wait_s=waits,
        step_s=step_s, seq=seq, batch=batch, micro_batch=batch // ctx.chips,
        train_tokens_per_s_per_chip=rate)
    ctx.reduced = tracer.reduced()
    return dict(correct=ok, attempted=len(losses), failed=0, setup_s=setup_s,
                end_to_end={"train_tokens_per_s_per_chip": rate})


# ------------------------------------------------------------------- serving
def draw_params(model, seed: int, dtype, device):
    """The serving weights, on the device, in the type they are served in,
    in ONE jitted call: shapes from ``jax.eval_shape(model.init)``, each
    leaf from ``fold_in(seed, leaf index)`` with the standard deviation
    ``models/transformer.init`` gives it (``initializer_range``; residual
    output projections ``/ sqrt(2 L)``; norm scales one; biases zero).
    Stacked leaves are drawn one matrix at a time (a sequential map), so the
    float32 draw of a matrix is the only temporary."""
    import math

    import jax
    import jax.numpy as jnp

    cfg = model.config
    shapes = jax.eval_shape(lambda k: model.init(k, dtype=dtype),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    std = cfg.initializer_range
    out_std = std / math.sqrt(2 * cfg.num_layers)

    def one(key, path, sds):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return jnp.ones(sds.shape, dtype)
        if name.startswith("b"):  # bias, bq, bk, bv, bo, bi
            return jnp.zeros(sds.shape, dtype)
        scale = out_std if name in ("wo", "res_wo") else std
        mat = sds.shape[-2:]
        lead = sds.shape[:-2]

        def draw(k):
            return (jax.random.normal(k, mat, jnp.float32) * scale).astype(dtype)

        if not lead:
            return draw(key)
        n = math.prod(lead)
        return jax.lax.map(draw, jax.random.split(key, n)).reshape(sds.shape)

    def draw_all(key):
        return jax.tree_util.tree_unflatten(treedef, [
            one(jax.random.fold_in(key, i), path, sds)
            for i, (path, sds) in enumerate(leaves)])

    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(draw_all, out_shardings=sharding)(seed_key(seed))


def make_submit(srv, Request):
    def submit(spec):
        return srv.submit(Request(request_id=spec.rid, prompt=spec.prompt,
                                  max_new_tokens=spec.new_tokens,
                                  temperature=0.0, eos_token_id=-1))
    return submit


def serve_sample(ctx, srv, submit, model, cc: dict, stream: int = 8):
    """Serve the sample requests of ``cc``, a block of the mix's
    ``correctness`` (the first one also warms the one slot-step program):
    the same requests a cell, greedy, prompts from the seed."""
    import numpy as np

    from benchmarks import loadgen

    rng = loadgen.rng_for(ctx.seed, stream)
    specs = [loadgen.RequestSpec(
        f"check{stream}.{i}", 0.0, rng.integers(
            0, model.config.vocab_size, size=int(n), dtype=np.int32),
        int(cc["new_tokens"])) for i, n in enumerate(cc["prompts"])]
    t0 = time.monotonic()
    states = [submit(s) for s in specs]
    srv.step()
    say(f"serve: {len(specs)} sample requests, first slot step "
        f"{time.monotonic() - t0:.1f} s (compile or cache load included)")
    srv.run_until_idle()
    say(f"serve: sample served in {time.monotonic() - t0:.1f} s")
    return specs, states


def judge_sample(ctx, srv, model, specs, states, cc: dict, inject=None):
    """Teacher-force prompt + answer of served sample requests through the
    plain reference, one pass a request, and judge every served token by
    ``reference.judge_served`` against the limits ``cc`` gives. For the
    sample of set-up: the share of all tokens that are near-argmaxes of the
    reference's logits, and no token far from the argmax where the
    reference's routing is clear of a near-tie (the program routes from
    bf16 activations, the reference from float32; inside a small margin the
    two may pick another expert, and with random weights one other expert
    moves that position's logits by about their own spread). For the
    precision sample, after the window: the share that are exactly the
    argmax. ``inject`` (developer only, never in a measured run) hands the
    reference something other than what the program served. Returns
    (correct, the comparison's counts)."""
    import numpy as np

    from benchmarks import reference

    ok = True
    gaps, margins = [], []
    t0 = time.monotonic()
    handed = dict(params=srv.engine.params)
    if inject in ctx.family_faults:
        handed = ctx.family.faulted(srv.engine.params, inject, ctx.shape,
                                    ctx.devices[0])
    for spec, st in zip(specs, states):
        if len(st.tokens) != spec.new_tokens:
            say(f"INCORRECT: {spec.rid} produced {len(st.tokens)} of "
                f"{spec.new_tokens} tokens, status {st.status}")
            ok = False
            continue
        ids = np.concatenate([spec.prompt, np.asarray(st.tokens, np.int32)])
        n = spec.new_tokens
        # logits at positions P-1 .. P+n-2 predict the n served tokens
        logits, margin = ctx.family.logits(
            ids=ids[:-1], shape=ctx.shape, device=ctx.devices[0], last=n,
            with_margin=True, **handed)
        tokens = st.tokens
        if inject in TOKEN_FAULTS and "min_margin" in cc:
            tokens = reference.swap_one_token(
                tokens, margin, float(cc["min_margin"]),
                inject == "token_clear", model.config.vocab_size)
        gaps.append(reference.served_token_gaps(logits, tokens))
        margins.append(np.asarray(margin))
    if not gaps:
        return False, {}
    gaps, margins = np.concatenate(gaps), np.concatenate(margins)
    faults, n = reference.judge_served(gaps, margins, cc)
    parts = []
    if "min_argmax_share" in cc:
        parts.append(
            f"exact rule: at least {cc['min_argmax_share']:.1%} = "
            f"{math.ceil(cc['min_argmax_share'] * n['tokens'])}")
    if "min_near_share" in cc:
        parts.append(
            f"share rule: {n['near']} within {cc['logit_tol']} of its max "
            f"logit (at least {cc['min_near_share']:.0%} = "
            f"{math.ceil(cc['min_near_share'] * n['tokens'])})")
    if "outlier_tol" in cc:
        parts.append(
            f"outlier rule: {n['clear']} clear of a near-tie (routing margin "
            f"at least {cc['min_margin']}; at least {cc['min_judged']}), the "
            f"worst of them {n['worst_clear']:.4f} from the max logit, "
            f"{n['outliers']} over {cc['outlier_tol']} (at most 0)")
    say(f"serve: {n['tokens']} served tokens, {n['argmax']} the reference's "
        f"argmax; {'; '.join(parts)}; reference took "
        f"{time.monotonic() - t0:.1f} s")
    say("serve: (gap, margin) per served token: " + " ".join(
        f"({g:.3f},{m:.3f})" for g, m in zip(gaps, margins)))
    for fault in faults:
        say(f"INCORRECT: {fault}")
    return ok and not faults, n


def check_precision(ctx, srv, submit, model, injects=(None,)):
    """Correctness 3, after the window (or alone under ``--check-seeds``):
    the mix's precision sample, if it has one: many tokens from as many
    requests as the cell keeps in flight, served by the same slot-step
    program, so nothing compiles and set-up does not grow; judged by the
    exact rule. Returns [(correct, counts)] for each of ``injects``."""
    pc = ctx.mix["correctness"].get("precision")
    if not pc:
        return [(True, {})] * len(injects)
    srv.run_until_idle()  # what the window left in flight
    sample = serve_sample(ctx, srv, submit, model, pc, stream=9)
    return [judge_sample(ctx, srv, model, *sample, pc, inject)
            for inject in injects]


def check_seeds(ctx, srv, submit, model, dtype) -> dict:
    """``--check-seeds``: set-up and the checks only, one engine, for each
    seed its own weights and prompts as a run with that seed draws them; no
    window. With ``--inject`` every seed is judged once as served and once
    for each fault named."""
    cc = ctx.mix["correctness"]
    injects = [None, *ctx.inject]
    rows = []
    for i, seed in enumerate(ctx.check_seeds):
        ctx.seed = seed
        if i:  # the engine was built on the first seed's weights; the old
            # ones go before the new are drawn: the chip holds one set
            srv.engine.params = None
            srv.engine.params = draw_params(model, seed, dtype, ctx.devices[0])
            # the keys and values the prefix cache holds are the old
            # weights': a prompt that begins with a cached request's first
            # token would attend to them (chip run, PR 27: seed 3165000286)
            if srv.scheduler.prefix_cache is not None:
                srv.scheduler.prefix_cache.clear()
        sample = serve_sample(ctx, srv, submit, model, cc)
        first = [judge_sample(ctx, srv, model, *sample, cc, inject)
                 for inject in injects]
        after = check_precision(ctx, srv, submit, model, injects)
        for inject, (ok1, n1), (ok2, n2) in zip(injects, first, after):
            rows.append(dict(seed=seed, inject=inject, correct=ok1 and ok2,
                             **n1, precision=n2))
            say("check " + json.dumps(rows[-1]))
    return dict(checks=rows)


def serve_window(ctx, srv, submit, model, seconds: float, tracer,
                 rate=None) -> SimpleNamespace:
    """One measured window of the mix's loop; returns the loop's result and
    the engine's counters over exactly the window."""
    from benchmarks import loadgen

    mix = ctx.mix
    m = srv.metrics
    vocab = model.config.vocab_size

    def counted():
        return dict(steps=m.steps, sched=m.scheduled_tokens, out=m.tokens_out)

    snap = {}

    def on_tick(now):
        tracer.maybe_start(now, seconds - float(mix["trace_seconds"]))
        if now >= seconds and not snap:
            snap.update(counted(), t=now)
            tracer.stop()

    base = counted()
    kw = dict(seconds=seconds, grace_s=float(mix["grace_s"]),
              annotate=annotate, on_tick=on_tick)
    if mix["kind"] == "open_loop":
        sched = loadgen.open_loop_schedule(mix, ctx.seed, seconds, vocab, rate)
        res = loadgen.run_open_loop(submit, srv.step, sched, **kw)
    else:
        replay = loadgen.replay_set(mix, ctx.seed, vocab)
        res = loadgen.run_closed_loop(submit, srv.step, replay,
                                      int(mix["clients"]), vocab, **kw)
    if not snap:  # the loop ran dry before the window closed
        snap.update(counted(), t=seconds)
    tracer.stop()
    return SimpleNamespace(
        res=res, window_s=snap["t"], steps=snap["steps"] - base["steps"],
        scheduled=snap["sched"] - base["sched"],
        tokens_out=snap["out"] - base["out"])


def serve_numbers(ctx, w, srv) -> dict:
    """Every end-to-end candidate and counter a serving window yields."""
    from benchmarks import loadgen

    res, grace = w.res, float(ctx.mix["grace_s"])
    ttft = loadgen.ttft_values(res, grace)
    itl = loadgen.itl_values(res)
    qwait = [(t.handle.prefill_start_t - t.due_t - res.t0)
             if t.handle.prefill_start_t is not None and not t.failed
             else res.window_s + grace - t.due_t for t in res.tracks]
    done = sum(1 for t in res.tracks if t.done and not t.failed)
    e2e = {
        "ttft_p95_ms": 1e3 * loadgen.percentile(ttft, 95),
        "itl_p95_ms": 1e3 * loadgen.percentile(itl, 95),
        "serve_tokens_per_s": w.scheduled / w.window_s,
    }
    ctx.counters.update(
        steps=w.steps, window_s=w.window_s, scheduled_tokens=w.scheduled,
        tokens_out=w.tokens_out, requests=len(res.tracks), completed=done,
        itl_gaps=len(itl), ttft_p50_ms=1e3 * loadgen.percentile(ttft, 50),
        itl_p50_ms=1e3 * loadgen.percentile(itl, 50), queue_wait_s=qwait,
        late_p95_ms=1e3 * loadgen.percentile(res.late_s, 95) if res.late_s
        else 0.0, step_wall_s=res.step_wall_s,
        slots=srv.max_slots, token_budget=srv.token_budget,
        out_tokens_per_s=w.tokens_out / w.window_s)
    return e2e


def run_serve(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.serving import Request

    eng = ctx.config["engine"]
    model = build_model(eng)
    check_shape(model, ctx.shape)
    kw = dict(eng.get("init_inference", {}))
    dtype = getattr(jnp, kw.pop("dtype", "bfloat16"))
    t0 = time.monotonic()
    params = draw_params(model, ctx.seed, dtype, ctx.devices[0])
    srv = deepspeed_tpu.init_serving(
        model, serving=dict(eng["serving"]), params=params, dtype=dtype,
        clock=time.monotonic, **kw)
    jax.block_until_ready(srv.engine.params)
    del params
    say(f"serve: {model.config.name} depth {model.config.num_layers}, "
        f"{model.num_params():,} params in {dtype.__name__} drawn and engine "
        f"built in {time.monotonic() - t0:.1f} s; slots {srv.max_slots} x "
        f"budget {srv.token_budget}, {srv.num_pages} pages x {srv.page_size}")
    submit = make_submit(srv, Request)
    if ctx.check_seeds:
        return check_seeds(ctx, srv, submit, model, dtype)
    cc = ctx.mix["correctness"]
    ok, _ = judge_sample(ctx, srv, model,
                         *serve_sample(ctx, srv, submit, model, cc), cc)
    traces_before = srv.step_traces

    if ctx.sweep:
        return sweep(ctx, srv, submit, model)

    tracer = Tracer(ctx.trace)
    compiles_before = ctx.compiles.total
    setup_s = time.monotonic() - T_START
    w = serve_window(ctx, srv, submit, model, ctx.seconds, tracer)
    in_window = ctx.compiles.total - compiles_before
    retraced = srv.step_traces - traces_before
    e2e = serve_numbers(ctx, w, srv)
    c = ctx.counters
    say(f"serve: {c['requests']} requests attempted, {c['completed']} "
        f"completed, {w.res.failed()} failed; {w.steps} steps in "
        f"{w.window_s:.2f} s; ttft p50/p95 {c['ttft_p50_ms']:.0f}/"
        f"{e2e['ttft_p95_ms']:.0f} ms, itl p50/p95 {c['itl_p50_ms']:.1f}/"
        f"{e2e['itl_p95_ms']:.1f} ms over {c['itl_gaps']} gaps; "
        f"{e2e['serve_tokens_per_s']:.0f} tokens/s processed, "
        f"{c['out_tokens_per_s']:.0f} generated; generator late p95 "
        f"{c['late_p95_ms']:.1f} ms; compilations inside the window: "
        f"{in_window}, retraces {retraced}")
    walls = w.res.step_wall_s
    med = statistics.median(walls)
    say(f"serve: engine.step median {1e3 * med:.1f} ms, longest "
        f"{max(walls):.3f} s (step {walls.index(max(walls))} of {len(walls)}"
        f"), {sum(t > 3 * med for t in walls)} over 3 x the median; outside "
        f"engine.step {w.window_s - sum(walls):.3f} s of the window")
    if in_window or retraced:
        say("INCORRECT: something compiled inside the window")
        ok = False
    ok = check_precision(ctx, srv, submit, model)[0][0] and ok
    ctx.reduced = tracer.reduced()
    return dict(correct=ok, attempted=w.res.attempted(), failed=w.res.failed(),
                setup_s=setup_s, end_to_end=e2e)


def sweep(ctx, srv, submit, model) -> dict:
    rows = []
    for leg, rate in enumerate(ctx.sweep):
        ctx.counters.clear()
        ctx.seed += leg > 0  # other prompts in every leg: no prefix is cached
        w = serve_window(ctx, srv, submit, model, ctx.seconds, Tracer(False),
                         rate=rate)
        e2e = serve_numbers(ctx, w, srv)
        c = ctx.counters
        row = dict(rate_per_s=rate, attempted=c["requests"],
                   completed=c["completed"], failed=w.res.failed(),
                   out_tokens_per_s=c["out_tokens_per_s"],
                   processed_tokens_per_s=e2e["serve_tokens_per_s"],
                   ttft_p50_ms=c["ttft_p50_ms"], ttft_p95_ms=e2e["ttft_p95_ms"],
                   itl_p50_ms=c["itl_p50_ms"], itl_p95_ms=e2e["itl_p95_ms"],
                   steps=w.steps,
                   backlog_at_close=sum(
                       1 for t in w.res.tracks
                       if not t.token_t or t.token_t[-1] > w.window_s))
        say("sweep " + json.dumps(row))
        rows.append(row)
        srv.run_until_idle()
    return dict(sweep=rows)


KINDS = {"train_stream": run_train, "open_loop": run_serve,
         "closed_loop": run_serve}


# ---------------------------------------------------------------------- main
def reader_path(name: str) -> str:
    """``layer_metrics/<name>.py``, or for a split name such as
    ``device_idle_pct.train`` the reader of the quantity before the first
    dot: the suffix only says which end-to-end metric the reading moves."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "layer_metrics", stem + ".py")
        if os.path.isfile(path):
            return path
    die(f"no reader benchmarks/layer_metrics/{name}.py for that metric")


def read_layer_metrics(ctx, names) -> dict:
    out = {}
    for name in names:
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics." + name.replace(".", "_"),
            reader_path(name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        try:
            value = mod.read(ctx)
        except (KeyError, ZeroDivisionError, statistics.StatisticsError) as e:
            say(f"layer metric {name}: nothing to read ({e!r})")
            value = None
        if value is not None:
            out[name] = float(value)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--check-seeds", default="")
    ap.add_argument("--inject", default="")
    args = ap.parse_args(argv)
    if args.inject and not args.check_seeds:
        die("--inject is for --check-seeds only: a measured run is never an "
            "injected one")
    seeds = [int(x) for x in args.check_seeds.split(",") if x]

    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        die(f"no workload {args.workload!r} in BENCHMARK.json "
            f"(have {sorted(cells)})")
    cell = cells[args.workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(ROOT, cfg_entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    chips = int(cell["chips"])
    if args.rehearse:
        config = merged(config, config.get("rehearse", {}))
        mix = merged(mix, mix.get("rehearse", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    seconds = float(args.seconds if args.seconds is not None
                    else manifest["run_seconds"])

    sys.path.insert(0, ROOT)
    try:
        import jax

        import deepspeed_tpu  # noqa: F401
        from benchmarks import flops, reference
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        die(f"cannot import the program beside the benchmark: {e}")

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peaks = load_json(HERE, "peaks.json")
    if not args.rehearse:
        if device["platform"] != "tpu":
            die(f"no TPU here (jax found {device}); nothing was run. "
                "--rehearse runs the tiny CPU rehearsal.", EXIT_NO_DEVICE)
        if device["kind"] not in peaks:
            die(f"device_kind {device['kind']!r} is not in "
                "benchmarks/peaks.json; nothing was run.", EXIT_NO_DEVICE)
    if len(devices) < chips:
        die(f"the cell asks for {chips} chip(s), jax found {device}",
            EXIT_NO_DEVICE)
    devices = devices[:chips]
    device["count"] = chips

    try:
        family = reference.family(config["family"])
    except ValueError as e:
        die(str(e))
    cache_dir = enable_compile_cache()
    ctx = SimpleNamespace(
        root=ROOT, manifest=manifest, cell=cell, config=config, mix=mix,
        seed=seeds[0] if seeds else int(args.seed), seconds=seconds,
        trace=bool(args.trace),
        rehearse=args.rehearse, chips=chips, devices=devices, device=device,
        peak=peaks.get(device["kind"]), family=family,
        shape=family.shape_of(config), flops=flops, counters={}, reduced=None,
        compiles=CompileCounter(),
        sweep=[float(x) for x in args.sweep.split(",") if x],
        check_seeds=seeds,
        inject=[x for x in args.inject.split(",") if x],
        family_faults=getattr(family, "FAULTS", ()))
    if seeds and mix["kind"] == "train_stream":
        die("--check-seeds is for serving cells")
    for name in ctx.inject:
        if name not in (*ctx.family_faults, *TOKEN_FAULTS):
            die(f"no fault {name!r} to inject (have "
                f"{(*ctx.family_faults, *TOKEN_FAULTS)})")
    say(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']} ({mix['kind']}), {chips} chip(s), seed "
        f"{ctx.seed}, {seconds:g} s, trace {int(ctx.trace)}; device {device}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    if args.rehearse:
        say("REHEARSAL on the CPU at tiny sizes: control flow only, nothing "
            "here is a chip result")
    os.makedirs(OUT_DIR, exist_ok=True)

    result = KINDS[mix["kind"]](ctx)
    say(f"compile cache {ctx.compiles}")
    if "sweep" in result:
        print(json.dumps({"sweep": result["sweep"], "device": device}))
        return 0
    if "checks" in result:
        print(json.dumps({"checks": result["checks"], "device": device}))
        return int(not all(r["correct"] for r in result["checks"]))

    e2e = dict(result["end_to_end"], setup_s=result["setup_s"])
    units = {m["name"]: m["unit"] for m in
             manifest["end_to_end"] + manifest["per_layer"]}
    if ctx.trace:
        # a rehearsal has no device and no peak: only the readers of
        # counts and benchmark spans run there
        names = [m["name"] for m in manifest["per_layer"]
                 if applies(m, cell["name"]) and not (
                     args.rehearse and m["source"] in ("device_trace",
                                                       "host_clock"))]
        values = read_layer_metrics(ctx, names)
    else:
        values = {m["name"]: e2e[m["name"]] for m in manifest["end_to_end"]
                  if applies(m, cell["name"])}
    if args.rehearse:
        print(json.dumps({
            "rehearsal": "passed" if result["correct"] else "FAILED",
            "workload": cell["name"], "attempted": result["attempted"],
            "failed": result["failed"], "metric_names": sorted(values),
            "device": device}))
        return 0 if result["correct"] else 1

    device["memory_peak_bytes"] = memory_peak(devices)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": device}
    if ctx.trace:
        r = ctx.reduced
        if r is None or r.busy_s <= 0:
            die("the traced run saw no operation on the device")
        device["busy_s"], device["window_s"] = r.busy_s, r.window_s
        line["breakdown"] = {"device_ops": r.top_ops(10),
                             "idle_gaps": r.idle_gaps(5, 5)}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
