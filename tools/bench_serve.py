#!/usr/bin/env python
"""bench_serve: replay a synthetic Poisson arrival trace through the
continuous-batching serving engine on a CPU mesh.

    python tools/bench_serve.py --requests 16 --rate 8
    python tools/bench_serve.py --tp 2 --kv-cache-dtype int8
    python tools/bench_serve.py --check-recompiles   # CI gate: exit 1 if
                                                     # the slot step traced
                                                     # more than once
    python tools/bench_serve.py --paged --system-prompt 24  # block-paged
                                                     # arena + prefix-heavy
                                                     # trace (one shared
                                                     # system prompt)
    python tools/bench_serve.py --spec --repetitive-prompt 3  # speculative
                                                     # decoding over a
                                                     # repetitive-prompt
                                                     # trace (n-gram drafts
                                                     # land acceptances)
    python tools/bench_serve.py --replicas 2 --paged # FLEET replay: the
                                                     # same trace through a
                                                     # single replica, then
                                                     # through the router
                                                     # over N replicas —
                                                     # prints fleet tokens/s
                                                     # + p95 TTFT next to
                                                     # the single-replica
                                                     # number
    python tools/bench_serve.py --replicas 3 --prefill-replicas 1 --paged
                                                     # disaggregated fleet:
                                                     # dedicated prefill
                                                     # replica handing KV
                                                     # to decode replicas
                                                     # as page transfers
    python tools/bench_serve.py --model mixtral --ep 2 --check-moe-parity
                                                     # MoE serving: tiny
                                                     # mixtral (4 experts,
                                                     # hidden 256) with the
                                                     # experts ep-sharded
                                                     # across 2 devices;
                                                     # the inline oracle
                                                     # replays the same
                                                     # trace dense-
                                                     # replicated and
                                                     # requires token-for-
                                                     # token equality

Arrivals land on a VIRTUAL clock (exponential inter-arrival gaps at
``--rate`` requests/s); each engine step advances the clock by its
measured wall time, so TTFT/TPOT percentiles are real step seconds laid
over the synthetic arrival pattern. Prompt/output lengths are drawn per
request (seeded), exercising the ragged path the slot engine exists for.

Prints tokens/s, p50/p95 TTFT/TPOT, queue/occupancy gauges, the KV-arena
stream line (comm_logger intake), and the recompile counters — the
zero-recompiles-after-warmup criterion is ``step traces == 1``.

CPU numbers are NOT perf claims (a speed comes from a cell of
benchmarks/ on the chip); this tool is the correctness/latency-shape
replay harness.
"""

import argparse
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    )

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_DIR not in sys.path:
    sys.path.insert(0, REPO_DIR)


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def build_trace(args):
    import numpy as np

    r = np.random.RandomState(args.seed)
    gaps = r.exponential(1.0 / args.rate, size=args.requests)
    arrivals = np.cumsum(gaps)
    # prefix-heavy traffic: every request opens with the SAME system
    # prompt (the "millions of users hitting one assistant prompt" shape
    # the prefix cache exists for)
    system = (
        r.randint(0, args.vocab, size=(args.system_prompt,))
        if args.system_prompt > 0 else np.zeros((0,), np.int64)
    )
    trace = []
    for i in range(args.requests):
        plen = int(r.randint(args.min_prompt, args.max_prompt + 1))
        new = int(r.randint(args.min_new, args.max_new + 1))
        if args.repetitive_prompt > 0:
            # repetitive-prompt replay (--spec's natural traffic): each
            # prompt tiles a short per-request motif, so the n-gram /
            # prompt-lookup drafts find their context and an untrained
            # greedy model settles into a cycle the lookup then predicts
            motif = r.randint(0, args.vocab,
                              size=(args.repetitive_prompt,))
            user = np.tile(motif, -(-plen // args.repetitive_prompt))[:plen]
        else:
            user = r.randint(0, args.vocab, size=(plen,))
        prompt = np.concatenate([system, user])
        trace.append((float(arrivals[i]), f"req-{i}", prompt, new))
    return trace


def _serving_section(args) -> dict:
    return {
        "max_slots": args.slots,
        "token_budget": args.token_budget,
        "queue_limit": max(args.requests, 1),
        "request_timeout_s": 1e9,  # the replay never times out
        "max_tokens": 64,
        "paged": args.paged,
        "page_size": args.page_size,
        "num_pages": args.num_pages,
        "host_pages": args.kv_host_pages,
        "spill_codec": args.kv_spill_codec,
        "prefix_cache": not args.no_prefix_cache,
        "moe_a2a": args.moe_a2a,
        "spec": {
            "enabled": args.spec,
            "max_draft": args.max_draft,
            "ngram_n": args.ngram_n,
        },
    }


def _build_model(args):
    """The replay model: tiny llama (default) or the tiny mixtral MoE
    preset (4 experts, hidden 256 — the ISSUE 14 CI leg shape)."""
    if args.model == "mixtral":
        from deepspeed_tpu.models import mixtral

        return mixtral(
            "mixtral-tiny", vocab_size=args.vocab, max_seq_len=64,
            hidden_size=256, num_layers=2, num_heads=4, num_kv_heads=4,
            intermediate_size=512, num_experts=4, moe_top_k=2,
        )
    from deepspeed_tpu.models import llama

    return llama(
        "llama-tiny", vocab_size=args.vocab, max_seq_len=64, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=4, intermediate_size=128,
    )


def _moe_parity_replay(args, trace):
    """The inline ep == dense oracle (--check-moe-parity): replay the
    same trace through a DENSE-REPLICATED engine (no ep axis, same
    params rng) and return {request_id: tokens}. Expert-parallel serving
    must reproduce it token-for-token — sharding the experts is a layout
    decision, never a numerics one."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, ServingEngine, ServingMetrics

    model = _build_model(args)
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64,
        quantize_bits=args.quantize_bits,
        kv_cache_dtype=args.kv_cache_dtype,
        rng=jax.random.PRNGKey(args.seed),
    )
    clock = VirtualClock()
    srv = ServingEngine(engine=eng, clock=clock,
                        metrics=ServingMetrics(clock=clock),
                        serving=_serving_section(args))
    pending = list(trace)
    finished = []
    while pending or srv.scheduler.has_work:
        while pending and pending[0][0] <= clock():
            at, rid, prompt, new = pending.pop(0)
            srv.submit(Request(request_id=rid, prompt=prompt,
                               max_new_tokens=new,
                               temperature=args.temperature))
        if not srv.scheduler.has_work:
            clock.advance(max(pending[0][0] - clock(), 1e-6))
            continue
        finished.extend(srv.step())
        clock.advance(1e-3)  # virtual: parity cares about tokens only
    return {st.request.request_id: list(st.tokens) for st in finished}


def _replay_stats(finished, clock):
    """(tokens, tokens_per_s, ttft_p95_s) over the REPLAY's finished
    states only — warmup requests (compile time) are not in the list."""
    from deepspeed_tpu.serving.metrics import percentile

    tokens = sum(len(st.tokens) for st in finished)
    ttfts = [st.first_token_t - st.arrival_t for st in finished
             if st.first_token_t is not None]
    dur = max(clock(), 1e-9)
    return tokens, tokens / dur, percentile(ttfts, 95)


def _twin_replay(args, engine, trace, num_pages, host_pages=0):
    """Replay the same trace through a twin engine with an explicit page
    budget — the inline oracle legs of --check-tiered-parity. Returns
    ({request_id: tokens} over requests that actually finished, count of
    "page pool exhausted" forced evictions)."""
    from deepspeed_tpu.serving import Request, ServingEngine, ServingMetrics

    clock = VirtualClock()
    serving = _serving_section(args)
    serving["num_pages"] = int(num_pages)
    serving["host_pages"] = int(host_pages)
    srv = ServingEngine(engine=engine, clock=clock,
                        metrics=ServingMetrics(clock=clock),
                        serving=serving)
    pending = list(trace)
    finished = []
    while pending or srv.scheduler.has_work:
        while pending and pending[0][0] <= clock():
            at, rid, prompt, new = pending.pop(0)
            st = srv.submit(Request(request_id=rid, prompt=prompt,
                                    max_new_tokens=new,
                                    temperature=args.temperature))
            if st.finished:
                finished.append(st)
        if not srv.scheduler.has_work:
            clock.advance(max(pending[0][0] - clock(), 1e-6))
            continue
        finished.extend(srv.step())
        clock.advance(1e-3)  # virtual: the twin cares about tokens only
    toks = {st.request.request_id: list(st.tokens) for st in finished
            if not st.evict_reason}
    exhausted = int(
        srv.metrics.evict_reasons.get("page pool exhausted", 0)
    )
    return toks, exhausted


def _cold_resume(args, srv, clock, trace, baseline_tokens):
    """--cold-resume K: re-submit the first K prompts as FRESH sessions
    after the main replay has churned the pool — their prefix chains (if
    anywhere) now live in the host tier, so first-token latency includes
    the page-in the staging path is supposed to hide. Prints measured
    page-in TTFT next to the analytic host-link budget. Returns (pages
    promoted during the resume, greedy-token mismatches vs the original
    sessions)."""
    import time as _time

    from deepspeed_tpu.analysis.cost.hardware import HardwareModel
    from deepspeed_tpu.serving import Request
    from deepspeed_tpu.serving.metrics import percentile

    m = srv.metrics
    promoted0, stall0 = m.pages_promoted, m.page_in_stall_s
    hits0, bytes0 = m.host_prefix_hits, m.promote_bytes
    states = []
    for i in range(min(args.cold_resume, len(trace))):
        at, orig, prompt, new = trace[i]
        st = srv.submit(Request(request_id=f"resume-{i}", prompt=prompt,
                                max_new_tokens=new,
                                temperature=args.temperature))
        states.append((st, orig))
    while srv.scheduler.has_work:
        t0 = _time.perf_counter()
        srv.step()
        clock.advance(_time.perf_counter() - t0)
    ttfts = [st.first_token_t - st.arrival_t for st, _ in states
             if st.first_token_t is not None]
    promoted = m.pages_promoted - promoted0
    stall = m.page_in_stall_s - stall0
    nbytes = m.promote_bytes - bytes0
    budget = nbytes / HardwareModel.detect().host_bw if nbytes else 0.0
    print(
        f"cold resume: {len(states)} sessions, p95 TTFT "
        f"{(percentile(ttfts, 95) or 0.0) * 1e3:.1f} ms, host prefix "
        f"hits +{m.host_prefix_hits - hits0}, paged in {promoted} pages "
        f"({nbytes / 2**20:.3f} MiB), page-in stall {stall * 1e3:.2f} ms "
        f"(host-link budget {budget * 1e3:.2f} ms)"
    )
    mismatch = 0
    if args.temperature == 0.0:
        # greedy resume of an identical prompt must reproduce the
        # original session token-for-token — restored-from-host KV is
        # the same KV (fp32 spill is bitwise; int8 re-quantizes to the
        # same codewords it was quantized from)
        for st, orig in states:
            want = baseline_tokens.get(orig)
            if want is not None and list(st.tokens) != want:
                mismatch += 1
    return promoted, mismatch


def _fleet_replay(args, engine, hw_section) -> int:
    """--replicas N: the same Poisson trace through ONE replica, then
    through the fleet Router — an apples-to-apples comparison on the
    virtual clock. Replicas are data-parallel (a real deployment steps
    them concurrently), so a fleet tick advances the clock by router
    overhead + the SLOWEST replica's step, not the sum. Both legs warm
    up first (one throwaway request per engine) so compile time never
    pollutes the TTFT comparison."""
    import time as _time

    import numpy as np

    from deepspeed_tpu.profiling.comm_logger import CommsLogger
    from deepspeed_tpu.serving import Request, ServingEngine, ServingMetrics
    from deepspeed_tpu.serving.fleet import Router

    trace = build_trace(args)
    serving = _serving_section(args)

    def make_warmup(i):
        return Request(request_id=f"warmup-{i}",
                       prompt=np.full(2, args.vocab - 1, np.int32),
                       max_new_tokens=2, temperature=0.0)

    def drive(srv, clock, advance):
        pending = list(trace)
        finished = []
        t_wall0 = _time.perf_counter()
        has_work = (lambda: srv.scheduler.has_work) \
            if hasattr(srv, "scheduler") else (lambda: srv.has_work)
        while pending or has_work():
            while pending and pending[0][0] <= clock():
                at, rid, prompt, new = pending.pop(0)
                st = srv.submit(Request(
                    request_id=rid, prompt=prompt, max_new_tokens=new,
                    temperature=args.temperature,
                ))
                if st.finished:
                    finished.append(st)  # shed — surfaces in the stats
            if not has_work():
                clock.advance(max(pending[0][0] - clock(), 1e-6))
                continue
            t0 = _time.perf_counter()
            finished.extend(srv.step())
            advance(srv, _time.perf_counter() - t0, clock)
        return finished, _time.perf_counter() - t_wall0

    # ---- leg 1: single-replica baseline -------------------------------
    base_clock = VirtualClock()
    base = ServingEngine(engine=engine, clock=base_clock,
                         metrics=ServingMetrics(clock=base_clock),
                         serving=serving)
    base.submit(make_warmup(0))
    base.run_until_idle()
    base_fin, base_wall = drive(
        base, base_clock, lambda s, dt, c: c.advance(dt)
    )
    base_tok, base_tps, base_p95 = _replay_stats(base_fin, base_clock)

    # ---- leg 2: the fleet ----------------------------------------------
    fleet_clock = VirtualClock()
    logger = CommsLogger()
    fleet_serving = dict(serving)
    fleet_serving["fleet"] = {
        "enabled": True,
        "replicas": args.replicas,
        "prefill_replicas": args.prefill_replicas,
        "routing": args.routing,
    }
    router = Router(
        engine=engine, clock=fleet_clock, comm_logger=logger,
        steptrace=(
            {"enabled": True, "export_path": args.trace}
            if args.trace else None
        ),
        healthwatch=hw_section,
        serving=fleet_serving,
    )
    if router.tracer is not None:
        logger.registry = router.tracer
    for i, rep in enumerate(router.replicas):
        rep.engine.submit(make_warmup(i))
    router.run_until_idle()

    def fleet_advance(r, wall, clock):
        durs = r.last_tick_durations.values()
        clock.advance(r.last_tick_overhead_s + max(durs, default=1e-6))

    fleet_fin, fleet_wall = drive(router, fleet_clock, fleet_advance)
    fleet_tok, fleet_tps, fleet_p95 = _replay_stats(fleet_fin, fleet_clock)

    # ---- the comparison ------------------------------------------------
    print(router.metrics.summary())
    kv_line = logger.kv_summary(duration_s=fleet_clock())
    if kv_line:
        print(kv_line)
    logger.stop()
    speedup = fleet_tps / base_tps if base_tps > 0 else float("inf")
    overhead = (
        (fleet_p95 - base_p95) / base_p95 * 100.0 if base_p95 > 0 else 0.0
    )
    print(
        f"single-replica: {base_tok} tokens, {base_tps:.1f} tok/s, "
        f"p95 TTFT {base_p95 * 1e3:.1f} ms "
        f"({base_clock():.2f} virtual s, {base_wall:.2f}s wall)"
    )
    print(
        f"fleet (N={args.replicas}, prefill={args.prefill_replicas}, "
        f"{args.routing}): {fleet_tok} tokens, {fleet_tps:.1f} tok/s "
        f"({speedup:.2f}x), p95 TTFT {fleet_p95 * 1e3:.1f} ms "
        f"({overhead:+.1f}% vs single) "
        f"({fleet_clock():.2f} virtual s, {fleet_wall:.2f}s wall)"
    )
    m = router.metrics.snapshot()
    print(
        f"fleet routing: handoffs={m['handoffs']} "
        f"(+{m['handoff_failures']} deferred, {m['handoff_pages']} pages "
        f"moved), prefix_routed={m['prefix_routed']}, "
        f"affinity_routed={m['affinity_routed']}, shed={m['shed']}"
    )
    print(
        f"recompiles: step traces per replica = {router.step_traces} "
        f"(zero-after-warmup criterion: 1 each), lockstep engine "
        f"compiles={engine.num_compiles}"
    )
    if args.trace:
        out = router.trace_export(args.trace)
        print(f"steptrace: wrote aggregated fleet trace {out} "
              f"(validate/report with tools/trace_report.py)")
    if router.healthwatch is not None:
        hw = router.healthwatch
        fired = sorted(hw.counters)
        print(f"healthwatch (fleet-wide): fired rules: "
              f"{', '.join(fired) if fired else 'none'}")
        if args.postmortem and hw.dump_count == 0:
            hw.dump_postmortem(path=args.postmortem, reason="explicit")
    if args.check_health:
        counters = (router.healthwatch.counters
                    if router.healthwatch is not None else {})
        missing = [r for r in args.check_health.split(",")
                   if r and r not in counters]
        if missing:
            print(f"ERROR: expected health rule(s) never fired: "
                  f"{', '.join(missing)}")
            return 1
    done = sum(1 for st in fleet_fin if not st.evict_reason)
    if done != args.requests:
        print(f"ERROR: {args.requests - done} requests unfinished")
        return 1
    # the oracle rides along for free: both legs are deterministic, so
    # any drift between routings IS a bug — check token-for-token
    by_id = {st.request.request_id: st for st in base_fin}
    for st in fleet_fin:
        want = by_id.get(st.request.request_id)
        if want is not None and st.tokens != want.tokens:
            print(f"ERROR: {st.request.request_id} diverged from the "
                  f"single-replica replay ({st.tokens} != {want.tokens})")
            return 1
    if args.check_recompiles:
        bad = [t for t in router.step_traces if t != 1]
        if bad:
            print(f"ERROR: per-replica step traces {router.step_traces} "
                  "— a replica recompiled after warmup (or never ran)")
            return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_serve", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, requests per virtual second")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--token-budget", type=int, default=16)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--min-new", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--model", default="llama",
                    choices=["llama", "mixtral"],
                    help="replay model: tiny llama, or the tiny mixtral "
                         "MoE preset (4 experts, hidden 256) for "
                         "expert-parallel serving (--ep)")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel degree: shard the MoE expert "
                         "banks over an ep mesh axis of this size "
                         "(--model mixtral; tp*ep CPU host devices)")
    ap.add_argument("--moe-a2a", default="auto",
                    choices=["auto", "stock", "chunked"],
                    help="decode-shaped expert-exchange form under ep>1 "
                         "(serving.moe_a2a; bitwise-equal forms)")
    ap.add_argument("--quantize-bits", type=int, default=None,
                    choices=[4, 8],
                    help="weight-only quantization incl. the expert banks "
                         "(packed Pallas streaming matvec)")
    ap.add_argument("--check-moe-parity", action="store_true",
                    help="exit 1 unless the ep-sharded replay reproduces "
                         "a dense-replicated replay of the same trace "
                         "token-for-token (the ISSUE 14 oracle)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--kv-cache-dtype", default="auto",
                    choices=["auto", "bf16", "int8"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-recompiles", action="store_true",
                    help="exit 1 unless the slot step compiled exactly once")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable steptrace and export the replay as Chrome "
                         "trace-event JSON to PATH (inspect with "
                         "tools/trace_report.py or Perfetto)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV arena (page pool + per-slot page "
                         "tables + prefix cache) instead of contiguous "
                         "slot regions")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="physical page-pool size; 0 = auto "
                         "(slots * pages_per_slot, no overcommit)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prefix sharing in --paged mode")
    ap.add_argument("--kv-host-pages", type=int, default=0, metavar="N",
                    help="tiered KV (--paged): back the HBM page pool "
                         "with N host-resident pages — cold pages and "
                         "LRU-evicted prefix chains demote to pinned "
                         "host memory (codec-compressed at rest) and "
                         "page back in under the decode step "
                         "(serving.host_pages; docs/serving.md "
                         "\"KV tiering\")")
    ap.add_argument("--kv-spill-codec", default="fp32",
                    choices=["fp32", "bf16", "int8", "int4"],
                    help="at-rest codec for host-spilled pages "
                         "(serving.spill_codec; fp32 round-trips "
                         "bitwise)")
    ap.add_argument("--cold-resume", type=int, default=0, metavar="K",
                    help="after the replay, re-submit the first K "
                         "prompts as fresh sessions and print their "
                         "page-in TTFT next to the analytic host-link "
                         "budget (the cold-session-resume leg)")
    ap.add_argument("--check-tiered-parity", action="store_true",
                    help="exit 1 unless the tiered replay (a) forced "
                         "zero \"page pool exhausted\" evictions while "
                         "an untiered twin at the same HBM page count "
                         "sheds, and (b) reproduces an untiered twin of "
                         "the same LOGICAL capacity token-for-token "
                         "(the kv-tiering CI oracle; needs "
                         "--kv-host-pages)")
    ap.add_argument("--system-prompt", type=int, default=0, metavar="LEN",
                    help="prepend one shared LEN-token system prompt to "
                         "every request (prefix-heavy trace)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding (serving.spec): each decode "
                         "slot proposes n-gram drafts, the one step "
                         "verifies them — a spec slot claims max_draft+1 "
                         "budget rows")
    ap.add_argument("--max-draft", type=int, default=4,
                    help="draft tokens per decode slot per step (--spec)")
    ap.add_argument("--ngram-n", type=int, default=3,
                    help="n-gram context length of the draft lookup")
    ap.add_argument("--repetitive-prompt", type=int, default=0,
                    metavar="MOTIF",
                    help="tile each prompt from a MOTIF-token per-request "
                         "motif (the repetitive traffic speculative "
                         "decoding accelerates)")
    ap.add_argument("--check-acceptance", action="store_true",
                    help="exit 1 unless acceptance rate > 0 and mean "
                         "accepted tokens/step > 1 (the spec CI gate)")
    ap.add_argument("--healthwatch", action="store_true",
                    help="enable healthwatch on the replay (goodput "
                         "accounting + anomaly watchdogs + flight "
                         "recorder; docs/observability.md)")
    ap.add_argument("--hw-queue-depth", type=int, default=None,
                    metavar="N",
                    help="arm the queue_depth_breach watchdog at N "
                         "(action=dump — the breach leaves a postmortem); "
                         "implies --healthwatch")
    ap.add_argument("--hw-ttft-p95", type=float, default=None,
                    metavar="SECONDS",
                    help="arm the ttft_breach watchdog at a recent-window "
                         "p95 TTFT of SECONDS; implies --healthwatch")
    ap.add_argument("--postmortem", metavar="PATH", default=None,
                    help="flight-recorder postmortem target; dumped by a "
                         "breaching watchdog, or explicitly at replay end "
                         "if no watchdog fired (implies --healthwatch; "
                         "validate with tools/healthwatch.py)")
    ap.add_argument("--check-health", metavar="RULES", default=None,
                    help="comma-separated health/* rule names that MUST "
                         "have fired during the replay (the seeded-"
                         "anomaly CI gate); exit 1 otherwise")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="fleet replay: route the trace across N "
                         "data-parallel replicas behind the prefix-aware "
                         "Router and print fleet tokens/s + p95 TTFT next "
                         "to a single-replica baseline of the same trace "
                         "(serving/fleet/; docs/serving.md \"Fleet\")")
    ap.add_argument("--prefill-replicas", type=int, default=0, metavar="K",
                    help="of --replicas, dedicate K to prefill "
                         "(DistServe-style disaggregation; finished "
                         "prefills hand their KV to decode replicas as "
                         "page transfers — needs --paged)")
    ap.add_argument("--routing", default="prefix",
                    choices=["prefix", "least_loaded", "round_robin"],
                    help="fleet routing policy (--replicas > 1)")
    ap.add_argument("--campaign-ab", metavar="KNOB", default=None,
                    choices=["paged", "spec", "moe_a2a"],
                    help="A/B one serving knob off-vs-on through "
                         "deepspeed_tpu.autotuning.serving_ab (the "
                         "campaign's serving leg) and print the result "
                         "JSON instead of running the replay")
    args = ap.parse_args(argv)
    if (args.hw_queue_depth is not None or args.hw_ttft_p95 is not None
            or args.postmortem or args.check_health):
        args.healthwatch = True
    if args.kv_host_pages > 0 and not args.paged:
        ap.error("--kv-host-pages needs --paged (the host tier backs "
                 "the block-paged arena)")
    if args.check_tiered_parity and args.kv_host_pages <= 0:
        ap.error("--check-tiered-parity needs --kv-host-pages > 0")
    if args.check_tiered_parity and args.replicas > 1:
        ap.error("--check-tiered-parity is a single-engine oracle "
                 "(the fleet replay has its own serial-replay oracle)")

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
    from deepspeed_tpu.profiling.comm_logger import CommsLogger
    from deepspeed_tpu.serving import Request, ServingEngine, ServingMetrics

    if args.ep > 1 and args.model != "mixtral":
        ap.error("--ep > 1 needs --model mixtral (expert parallelism "
                 "shards MoE expert banks)")
    model = _build_model(args)
    if args.campaign_ab:
        from deepspeed_tpu.autotuning import serving_ab

        values = (
            ("stock", "chunked") if args.campaign_ab == "moe_a2a"
            else (False, True)
        )
        result = serving_ab(
            model, _serving_section(args), args.campaign_ab,
            values=values, requests=min(args.requests, 8),
        )
        print(json.dumps(result))
        return 0
    topology = None
    if args.tp > 1 or args.ep > 1:
        n = max(args.tp, 1) * max(args.ep, 1)
        topology = MeshTopology(
            dims=ParallelDims(tp=args.tp, ep=max(args.ep, 1)),
            devices=jax.devices()[:n],
        )
    engine = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, topology=topology,
        kv_cache_dtype=args.kv_cache_dtype,
        quantize_bits=args.quantize_bits,
        rng=jax.random.PRNGKey(args.seed),
    )
    clock = VirtualClock()
    logger = CommsLogger()
    hw_section = None
    if args.healthwatch:
        rules = {}
        if args.hw_queue_depth is not None:
            rules["queue_depth_breach"] = {
                "threshold": args.hw_queue_depth, "action": "dump",
            }
        if args.hw_ttft_p95 is not None:
            rules["ttft_breach"] = {
                "p95_s": args.hw_ttft_p95, "action": "dump",
            }
        hw_section = {
            "enabled": True,
            "rules": rules,
            "postmortem_path": args.postmortem,
            "install_signal_handler": False,  # replay tool, not a prod run
        }
    if args.replicas > 1:
        return _fleet_replay(args, engine, hw_section)
    srv = ServingEngine(
        engine=engine,
        clock=clock,
        metrics=ServingMetrics(clock=clock),
        comm_logger=logger,
        steptrace=(
            {"enabled": True, "export_path": args.trace}
            if args.trace else None
        ),
        healthwatch=hw_section,
        serving=_serving_section(args),
    )
    if srv.tracer is not None:
        # the comms logger's stream records land on the same timeline
        # (steptrace --trace or healthwatch both configure the registry)
        logger.registry = srv.tracer
    trace = build_trace(args)
    pending = list(trace)
    finished = []
    t_wall0 = time.perf_counter()
    while pending or srv.scheduler.has_work:
        while pending and pending[0][0] <= clock():
            at, rid, prompt, new = pending.pop(0)
            srv.submit(Request(
                request_id=rid, prompt=prompt, max_new_tokens=new,
                temperature=args.temperature,
            ))
        if not srv.scheduler.has_work:
            clock.advance(max(pending[0][0] - clock(), 1e-6))  # idle: jump
            continue
        t0 = time.perf_counter()
        finished.extend(srv.step())
        clock.advance(time.perf_counter() - t0)
    wall = time.perf_counter() - t_wall0

    m = srv.metrics.snapshot()
    print(srv.metrics.summary())
    kv_line = logger.kv_summary(duration_s=clock())
    if kv_line:
        print(kv_line)
    logger.stop()
    print(
        f"replay: {args.requests} requests over {clock():.2f} virtual s "
        f"({wall:.2f}s wall), tokens/s={m['tokens_out'] / max(clock(), 1e-9):.1f}"
    )
    print(
        f"p50/p95 TTFT = {m['ttft_p50_s'] * 1e3:.1f}/"
        f"{m['ttft_p95_s'] * 1e3:.1f} ms, p50/p95 TPOT = "
        f"{m['tpot_p50_s'] * 1e3:.1f}/{m['tpot_p95_s'] * 1e3:.1f} ms"
    )
    if args.paged:
        print(
            f"paged arena: {srv.num_pages} pages x {srv.page_size} tok "
            f"({srv.pages_per_slot}/slot), pages_in_use={m['pages_in_use']} "
            f"(util {m['arena_utilization']:.2f}), prefix hit rate "
            f"{m['prefix_hit_rate']:.2f} ({m['cached_prompt_tokens']} cached "
            f"prompt tokens), cow_copies={m['cow_copies']}, "
            f"prefill_chunks={m['prefill_chunks']}"
        )
    if args.kv_host_pages > 0:
        print(
            f"kv tiering: +{srv.host_pages} host pages @ "
            f"{args.kv_spill_codec}, spilled={m['pages_spilled']} "
            f"({m['spill_bytes'] / 2**20:.3f} MiB) "
            f"promoted={m['pages_promoted']} "
            f"({m['promote_bytes'] / 2**20:.3f} MiB), page-in stall "
            f"{m['page_in_stall_s'] * 1e3:.2f} ms, host prefix hit rate "
            f"{m['host_prefix_hit_rate']:.2f}, resident now "
            f"{m['host_pages_resident']}"
        )
    if args.spec:
        print(
            f"spec: {m['spec_steps']} verify windows, acceptance rate "
            f"{m['acceptance_rate']:.3f} "
            f"({m['draft_tokens_accepted']}/{m['draft_tokens_proposed']} "
            f"drafts), mean accepted tokens/step "
            f"{m['mean_accepted_tokens_per_step']:.2f}"
        )
    print(
        f"recompiles: serving step traces={srv.step_traces} "
        f"(zero-after-warmup criterion: 1), lockstep engine compiles="
        f"{engine.num_compiles}"
    )
    resume_promoted, resume_mismatch = 0, 0
    if args.cold_resume > 0:
        baseline_tokens = {
            st.request.request_id: list(st.tokens) for st in finished
        }
        resume_promoted, resume_mismatch = _cold_resume(
            args, srv, clock, trace, baseline_tokens
        )
    if args.trace:
        out = srv.trace_export(args.trace)
        print(f"steptrace: wrote {out} "
              f"(validate/report with tools/trace_report.py)")
    if srv.healthwatch is not None:
        hw = srv.healthwatch
        g = hw.goodput()
        fired = sorted(hw.counters)
        print(
            f"healthwatch: goodput {g['goodput_fraction']:.3f}, fired "
            f"rules: {', '.join(fired) if fired else 'none'}"
        )
        if args.postmortem and hw.dump_count == 0:
            # no watchdog dumped — leave the end-of-replay evidence
            hw.dump_postmortem(path=args.postmortem, reason="explicit")
        if hw.last_postmortem:
            print(f"healthwatch: postmortem -> {hw.last_postmortem} "
                  f"(validate with tools/healthwatch.py)")
    if args.check_health:
        counters = (srv.healthwatch.counters
                    if srv.healthwatch is not None else {})
        missing = [r for r in args.check_health.split(",")
                   if r and r not in counters]
        if missing:
            print(f"ERROR: expected health rule(s) never fired: "
                  f"{', '.join(missing)}")
            return 1
    if args.model == "mixtral":
        hist = "/".join(
            str(int(m.get(f"moe_tokens_expert_{i}", 0)))
            for i in range(model.config.num_experts)
        )
        print(
            f"moe: ep={args.ep} form={srv.moe_a2a_form}, tokens/expert "
            f"[{hist}], load imbalance {m.get('moe_load_imbalance', 0):.2f}, "
            f"dropped {m.get('moe_dropped_fraction', 0):.3f}, a2a "
            f"{m.get('moe_a2a_bytes', 0) / (1 << 20):.2f} MiB"
        )
    if m["finished"] != args.requests:
        print(f"ERROR: {args.requests - m['finished']} requests unfinished")
        return 1
    if args.check_recompiles and srv.step_traces != 1:
        print("ERROR: the slot step recompiled after warmup")
        return 1
    if args.check_tiered_parity:
        exhausted = int(
            srv.metrics.evict_reasons.get("page pool exhausted", 0)
        )
        # twin 1: untiered, same LOGICAL capacity — the token oracle
        want, _ = _twin_replay(
            args, engine, trace,
            num_pages=srv.num_pages + srv.host_pages,
        )
        # twin 2: untiered, same HBM page count — must be the one that
        # sheds (the tier bought real capacity, not just latency)
        _, twin_exhausted = _twin_replay(
            args, engine, trace, num_pages=srv.num_pages
        )
        got = {st.request.request_id: list(st.tokens) for st in finished}
        print(
            f"tiered parity: tiered pool-exhausted evictions="
            f"{exhausted}, untiered twin at {srv.num_pages} HBM pages "
            f"sheds {twin_exhausted}, token oracle over "
            f"{len(want)} requests"
        )
        if exhausted:
            print(f"ERROR: the tiered replay forced {exhausted} "
                  "\"page pool exhausted\" evictions — the host tier "
                  "failed to absorb the oversubscription")
            return 1
        if twin_exhausted == 0:
            print("ERROR: the untiered twin never exhausted its pool — "
                  "the trace does not oversubscribe; raise --requests "
                  "or shrink --num-pages")
            return 1
        for rid, toks in want.items():
            if rid in got and got[rid] != toks:
                print(f"ERROR: {rid} diverged from the untiered "
                      f"equal-capacity replay ({got[rid]} != {toks})")
                return 1
        if args.cold_resume > 0:
            if resume_promoted == 0:
                print("ERROR: cold resume never paged anything in — "
                      "the host tier held no chain for the resumed "
                      "prompts")
                return 1
            if resume_mismatch:
                print(f"ERROR: {resume_mismatch} resumed sessions "
                      "diverged from their original greedy replay "
                      "(restored-from-host KV is wrong)")
                return 1
    if args.check_moe_parity:
        want = _moe_parity_replay(args, trace)
        got = {st.request.request_id: list(st.tokens) for st in finished}
        for rid, toks in want.items():
            if got.get(rid) != toks:
                print(f"ERROR: {rid} diverged from the dense-replicated "
                      f"replay ({got.get(rid)} != {toks})")
                return 1
        print(f"moe parity: ep={args.ep} replay == dense-replicated "
              f"replay token-for-token ({len(want)} requests)")
    if args.check_acceptance:
        if m["acceptance_rate"] <= 0.0:
            print("ERROR: no draft token was ever accepted")
            return 1
        if m["mean_accepted_tokens_per_step"] <= 1.0:
            print("ERROR: mean accepted tokens/step did not exceed 1 "
                  "(speculation bought nothing)")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
