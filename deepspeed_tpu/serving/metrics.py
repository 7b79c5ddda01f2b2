"""Serving metrics: TTFT/TPOT, queue depth, occupancy, tokens/s.

Parity: the serving-side telemetry DeepSpeed-MII exposes per deployment,
comm_logger-styled: cheap counters updated by scheduler/engine hooks, a
``summary()`` table on demand, and a ``write_to(monitor, step)`` bridge
into the monitor/ backends (TensorBoard/W&B/CSV).

Glossary (docs/serving.md):

- **TTFT** — time to first token: first sampled token minus arrival.
- **TPOT** — time per output token: (finish - first token) / (tokens - 1)
  for requests that produced more than one token. The denominator is
  TOKENS ACTUALLY EMITTED, never decode steps: with speculative decoding
  a step emits 1..k+1 tokens per slot and ``on_token`` fires once per
  emitted token, so spec-on TPOT (and tokens/s) stay honest.
- **queue depth** — requests admitted but not yet slotted (gauge).
- **slot occupancy** — in-flight requests / max_slots (gauge).
- **tokens/s** — sampled tokens over the engine-step window.
- **acceptance rate** — accepted draft tokens / proposed draft tokens
  (speculative decoding; 0.0 with spec off).
- **mean accepted tokens/step** — tokens emitted per verify window
  (accepted drafts + the bonus token); 1.0 means no draft ever accepted,
  > 1 is the speculative speedup multiplier on decode steps.
"""

from __future__ import annotations

import math
import time
from collections import defaultdict, deque
from typing import Dict, List, Optional


def _finite(v, default: float = 0.0):
    """Sanitize one reported value: NaN/inf (or an unconvertible input)
    becomes ``default`` so the summary line and the CSV/monitor bridge
    NEVER carry a NaN — an empty window reports 0, not poison. Integer
    counters pass through unchanged (the snapshot JSON keeps its
    shape: ``"submitted": 3``, not ``3.0``)."""
    if isinstance(v, int):  # bool is an int too; both are finite
        return v
    try:
        f = float(v)
    except (TypeError, ValueError):
        return default
    return f if math.isfinite(f) else default


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile over the FINITE samples; 0.0 on an empty
    (or all-non-finite) window — the summary never dies and never
    reports NaN before the first request completes."""
    xs = sorted(v for v in values if isinstance(v, (int, float))
                and math.isfinite(v))
    if not xs:
        return 0.0
    idx = min(len(xs) - 1, max(0, int(round(p / 100.0 * (len(xs) - 1)))))
    return xs[idx]


def recent_percentile(values: List[float], p: float,
                      window: int = 32) -> Optional[float]:
    """Percentile over the trailing ``window`` finite samples, or None
    when the window is empty — the healthwatch TTFT watchdog needs the
    tri-state (None = "no evidence yet", never a fake 0 that would mask
    a breach or fire one)."""
    xs = [v for v in values[-int(window):]
          if isinstance(v, (int, float)) and math.isfinite(v)]
    if not xs:
        return None
    return percentile(xs, p)


class ServingMetrics:
    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self._t0 = clock()
        # optional steptrace request tracer (profiling/steptrace.py
        # ServeTracer): the lifecycle hooks below forward to it so a
        # traced replay gets per-request QUEUED→PREFILL→DECODE→DONE span
        # trees for free; None (default) is the zero-overhead path
        self.tracer = None
        # optional healthwatch (profiling/healthwatch.py): when the
        # serving engine attaches one, snapshot()/summary() report its
        # running goodput fraction; None is the zero-overhead path
        self.healthwatch = None
        # counters
        self.submitted = 0
        self.admitted = 0
        self.rejected = 0
        self.evicted = 0
        self.finished = 0
        self.steps = 0
        self.tokens_out = 0
        self.scheduled_tokens = 0     # real tokens fed (prefill + decode)
        #   whose step was folded; a discarded row's are not among them
        self.prompt_tokens = 0        # those of them in prompt chunks
        self.decode_tokens = 0        # and the rest: decode rows, verify
        #   windows, a fully cached prompt's final-token feed (their sum
        #   is ``scheduled_tokens``)
        self.chunk_steps = 0          # folded steps that held a prompt
        #   chunk (of ``steps``)
        self.overlapped_steps = 0     # steps dispatched while the one
        #   before was not yet fetched (of ``steps``)
        self.discarded_rows = 0       # rows computed for a request that
        #   had ended or gone by the time its step was folded
        # paged arena / prefix cache
        self.prefix_lookups = 0       # slot admissions that consulted it
        self.prefix_hits = 0          # admissions with >= 1 cached token
        self.cached_prompt_tokens = 0  # prompt tokens skipped via cache
        self.prompt_tokens_seen = 0   # prompt tokens over those lookups
        self.cow_copies = 0           # in-step copy-on-write page copies
        self.prefill_chunks = 0       # scheduled prompt chunks (a fully-
        #   cached prompt's lone final-token feed does not count)
        self.cached_tail_feeds = 0    # those excluded final-token feeds
        # tiered KV (serving.host_pages > 0, ISSUE 18)
        self.pages_spilled = 0        # HBM pages demoted to the host tier
        self.pages_promoted = 0       # host pages staged back under steps
        self.spill_bytes = 0          # at-rest (codec-compressed) bytes out
        self.promote_bytes = 0        # at-rest bytes decoded back in
        self.page_in_stall_s = 0.0    # host-side blob decode + staging
        #   time (the part of page-in NOT hidden under device math)
        self.host_prefix_hits = 0     # admissions that extended a prefix
        #   hit with >= 1 HOST-tier page (chains that survived eviction)
        self.host_cached_prompt_tokens = 0  # prompt tokens covered by
        #   those host-resident blocks (promoted instead of refed)
        # speculative decoding
        self.spec_steps = 0           # verify windows executed (slot-steps
        #   that carried >= 1 draft row)
        self.draft_tokens_proposed = 0
        self.draft_tokens_accepted = 0
        self.spec_tokens_out = 0      # tokens emitted by verify windows
        #   (accepted drafts + bonus tokens)
        # MoE serving (expert-parallel decode, ISSUE 14)
        self.moe_steps = 0            # steps that routed through experts
        self.moe_tokens_per_expert: List[int] = []  # cumulative histogram
        #   of capacity slots landed per expert (summed over layers)
        self.moe_routed_tokens = 0    # token-expert assignments kept
        self.moe_dropped_fraction = 0.0  # last step's dropped fraction
        #   (valid token-expert assignments that overflowed capacity)
        self.moe_a2a_bytes = 0        # cumulative expert-exchange wire
        #   bytes (the analytic moe_decode_a2a stream; 0 without ep)
        self.filter_steps = 0         # steps in which a live slot asked for
        #   top-k or top-p, so the sampler's sorts ran (of ``steps``)
        # gauges (last observed)
        self.head_rows_per_step = 0   # rows a step projects to the
        #   vocabulary: max_slots x (max_draft + 1), set once an engine
        self.dense_rows_per_step = 0  # rows a step's row-by-row layers
        #   (norms, projections, MLPs, routers) run over: token_budget where
        #   the step packs the plan's tokens (ServingEngine.row_layout),
        #   max_slots x token_budget where it keeps the slot layout
        self.cache_rows_per_step = 0  # update rows a layer's cache write
        #   takes a pool: token_budget where a packed step scatters the rows
        #   it computed into a page pool, max_slots x token_budget in the
        #   slot layout and in a contiguous arena (a slice a slot)
        self.relaid_param_leaves = 0  # parameter leaves the engine re-laid
        #   into the layout its compiled step reads them in
        #   (ServingEngine.param_layout), their bytes a device and the
        #   seconds the re-lay took: the last one's (construction, or the
        #   first step after engine.params was replaced)
        self.relaid_param_bytes = 0
        self.param_relayout_s = 0.0
        self.attention_paged_kernel = 0.0  # 1 when the compiled step's
        #   attention is the paged Pallas kernel (ServingEngine
        #   .attention_path; 0 = the dense XLA lines or not compiled yet)
        self.expert_touched_kernel = 0.0  # 1 when the compiled step's
        #   expert banks go through the kernel that reads the touched
        #   experts' alone (ServingEngine.expert_path; 0 = the einsum over
        #   every held expert, no routed layer, or not compiled yet)
        self.queue_depth = 0
        self.slot_occupancy = 0.0
        self.pages_in_use = 0
        self.pages_free = 0
        self.arena_utilization = 0.0
        self.prefix_cache_entries = 0
        self.host_pages_resident = 0  # host-store keys alive (gauge)
        # pages and keys by layer kind (a model with window layers keeps a
        # second pool; ``pages_in_use`` / ``pages_free`` above are the full
        # layers' pool)
        self.window_pages_in_use = 0
        self.window_pages_free = 0
        self.window_pages_released = 0  # given back behind the window
        # one member's share of an expert-parallel layer: real tokens (summed
        # over layers) none of whose experts is held here
        self.moe_unrouted_tokens = 0
        self.moe_experts_touched = 0
        # a latent model with an indexer: cached tokens at or before every
        # real query token (what the indexer scores), beside
        # attended_keys["sparse"], what its selection lets attention see
        self.context_keys = 0
        # per kind, for ONE layer of the kind, summed over steps from the
        # plan: the keys visible to every real query token, and the keys
        # in the blocks the paged kernel's loops read for them
        self.attended_keys: Dict[str, int] = defaultdict(int)
        self.fetched_keys: Dict[str, int] = defaultdict(int)
        # per kind whose pages the paged call walks: the slots whose program
        # computed the small tile of its query stack alone, summed over steps
        # (ops/pallas/paged_attention.py small_tile_slots)
        self.small_tile_slots: Dict[str, int] = defaultdict(int)
        self.attention_paged_kernel_kinds: Dict[str, float] = {}
        # a model with state layers: the arena's bytes that are no page
        # (gauge, set once an engine) and the states that began at zero (a
        # request's first chunk); the compiled step's path a mixer kind is
        # under attention_paged_kernel_kinds (1 = the kind's Pallas kernel)
        self.state_bytes = 0
        self.state_resets = 0
        # the residual streams of a hyper-connected model (0 = the one)
        self.hyper_streams = 0
        # the bytes of an indexer's key pool beside K / V pools (gauge, set
        # once an engine; 0 = no such pool)
        self.index_pool_bytes = 0
        self._max_slots = 1
        self._num_pages = 0
        self._host_pages = 0
        # per-request samples
        self.ttft_s: List[float] = []
        self.tpot_s: List[float] = []
        self.queue_wait_s: List[float] = []
        self.evict_reasons: Dict[str, int] = defaultdict(int)

    # ------------------------------------------------- scheduler hooks
    def on_submit(self, state, now: float, queue_depth: int = 0) -> None:
        self.submitted += 1
        self.queue_depth = queue_depth
        if self.tracer is not None:
            self.tracer.on_submit(state)

    def on_admit(self, state, now: float, queue_depth: int = 0) -> None:
        self.admitted += 1
        self.queue_depth = queue_depth
        self.queue_wait_s.append(now - state.arrival_t)
        if self.tracer is not None:
            self.tracer.on_admit(state)

    def on_evict(self, state, now: float) -> None:
        # graceful admission rejection and timeout eviction both land
        # here; the reason string separates them
        self.evicted += 1
        if (state.evict_reason or "").startswith("queue full"):
            self.rejected += 1
        self.evict_reasons[state.evict_reason or "unknown"] += 1
        if self.tracer is not None:
            self.tracer.on_evict(state)

    def on_plan(self, plan, now: float, queue_depth: int = 0,
                occupancy: int = 0) -> None:
        self.queue_depth = queue_depth
        self.slot_occupancy = occupancy / max(self._max_slots, 1)

    def on_rows(self, tokens: int, discarded: int = 0,
                prompt_tokens: int = 0, chunk_step: bool = False) -> None:
        """One folded step: the real tokens of the rows whose results
        reached their requests (``prompt_tokens`` of them in prompt chunks,
        the rest decode rows), whether the step held a prompt chunk, and
        the rows that reached nobody (their request ended or was evicted
        while the step was in flight). Booked at the fold, with ``steps``,
        so they always describe the same steps."""
        self.scheduled_tokens += int(tokens)
        self.prompt_tokens += int(prompt_tokens)
        self.decode_tokens += int(tokens) - int(prompt_tokens)
        self.chunk_steps += bool(chunk_step)
        self.discarded_rows += int(discarded)

    def on_token(self, state, now: float) -> None:
        """One EMITTED token (fires once per token, not per step — a
        speculative verify window calls this 1..k+1 times, keeping
        tokens/s and TPOT divided by tokens actually emitted)."""
        self.tokens_out += 1
        if self.tracer is not None:
            self.tracer.on_token(state)

    def on_spec(self, state, proposed: int, accepted: int,
                emitted: int) -> None:
        """One executed verify window: ``proposed`` draft rows scheduled,
        ``accepted`` drafts matched the verifier's targets, ``emitted``
        = accepted + the bonus token (possibly eos-clamped)."""
        self.spec_steps += 1
        self.draft_tokens_proposed += int(proposed)
        self.draft_tokens_accepted += int(accepted)
        self.spec_tokens_out += int(emitted)
        if self.tracer is not None:
            self.tracer.on_spec(state, proposed, accepted)

    def on_finish(self, state, now: float) -> None:
        self.finished += 1
        if self.tracer is not None:
            self.tracer.on_finish(state)
        if state.first_token_t is not None:
            self.ttft_s.append(state.first_token_t - state.arrival_t)
            n = len(state.tokens)
            if n > 1 and state.finish_t is not None:
                self.tpot_s.append(
                    (state.finish_t - state.first_token_t) / (n - 1)
                )

    def on_prefix_lookup(self, cached_tokens: int, prompt_len: int,
                         host_tokens: int = 0) -> None:
        """One slot admission's cache consult. ``cached_tokens`` counts
        EVERY skipped prompt token (HBM-resident hit + host-tier
        extension); ``host_tokens`` is the host-tier share of it."""
        self.prefix_lookups += 1
        self.prompt_tokens_seen += int(prompt_len)
        if cached_tokens > 0:
            self.prefix_hits += 1
            self.cached_prompt_tokens += int(cached_tokens)
        if host_tokens > 0:
            self.host_prefix_hits += 1
            self.host_cached_prompt_tokens += int(host_tokens)

    def on_cow(self) -> None:
        self.cow_copies += 1

    def on_spill(self, nbytes: int = 0) -> None:
        """One page demoted HBM → host (at-rest, codec-compressed
        ``nbytes``); fired by PageSpiller.demote AFTER the put succeeded
        — a full-store failure mutates nothing and counts nothing."""
        self.pages_spilled += 1
        self.spill_bytes += int(_finite(nbytes))

    def on_page_in(self, pages: int = 1, nbytes: int = 0,
                   stall_s: float = 0.0) -> None:
        """One step's promotion staging: ``pages`` host pages decoded
        into the rotating staging buffer (``nbytes`` at rest),
        ``stall_s`` the host-side decode+staging time — the slice of
        page-in that is NOT hidden under the device step."""
        self.pages_promoted += int(pages)
        self.promote_bytes += int(_finite(nbytes))
        self.page_in_stall_s += float(_finite(stall_s))

    def on_prefill_chunk(self, cached_tail: bool = False) -> None:
        if cached_tail:
            self.cached_tail_feeds += 1
        else:
            self.prefill_chunks += 1

    def on_moe(self, tokens_per_expert, dropped_fraction,
               a2a_bytes: int = 0, unrouted=None, touched=None) -> None:
        """One MoE serving step's expert load-balance counters (ISSUE 14
        satellite): ``tokens_per_expert`` is the step's [E] capacity-slot
        histogram (summed over layers), ``dropped_fraction`` the valid
        token-expert assignments that overflowed capacity, ``a2a_bytes``
        the analytic expert-exchange wire bytes. NaN-hardened like the
        TTFT percentiles — a poisoned device value can never reach the
        summary line or the serve/* bridge."""
        self.moe_steps += 1
        hist = [int(_finite(v)) for v in list(tokens_per_expert)]
        if len(self.moe_tokens_per_expert) != len(hist):
            self.moe_tokens_per_expert = [0] * len(hist)
        self.moe_tokens_per_expert = [
            a + b for a, b in zip(self.moe_tokens_per_expert, hist)
        ]
        self.moe_routed_tokens += sum(hist)
        self.moe_dropped_fraction = float(_finite(dropped_fraction))
        self.moe_a2a_bytes += int(_finite(a2a_bytes))
        if unrouted is not None:
            self.moe_unrouted_tokens += int(_finite(unrouted))
        if touched is not None:  # held experts with a row, over the layers
            self.moe_experts_touched += int(_finite(touched))

    @property
    def moe_load_imbalance(self) -> float:
        """max/mean of the cumulative tokens-per-expert histogram — 1.0
        is perfect balance, E is total collapse onto one expert; 0.0
        before any MoE step ran."""
        hist = self.moe_tokens_per_expert
        total = sum(hist)
        if not hist or total <= 0:
            return 0.0
        return max(hist) / (total / len(hist))

    def on_window_pages(self, pool, released: int) -> None:
        """The window layers' pool after a tick (a model that keeps pages
        by layer kind)."""
        self.window_pages_free = pool.free_count
        self.window_pages_in_use = pool.num_pages - pool.free_count
        self.window_pages_released = int(released)

    def on_pages(self, pool, cache_entries: int = 0,
                 host_resident: int = 0) -> None:
        """Pool gauges from the scheduler's PagePool after a tick."""
        self.pages_free = pool.free_count
        self.pages_in_use = pool.num_pages - pool.free_count
        self.arena_utilization = self.pages_in_use / max(pool.num_pages, 1)
        self.prefix_cache_entries = int(cache_entries)
        self.host_pages_resident = int(host_resident)

    @property
    def prefix_hit_rate(self) -> float:
        """Cached prompt tokens over prompt tokens admitted (the token-
        weighted hit rate; 0.0 before any lookup)."""
        return (
            self.cached_prompt_tokens / self.prompt_tokens_seen
            if self.prompt_tokens_seen else 0.0
        )

    @property
    def host_prefix_hit_rate(self) -> float:
        """HOST-tier share of the token-weighted hit rate: prompt tokens
        covered by host-resident blocks (chains that survived HBM
        eviction) over prompt tokens admitted; 0.0 before any lookup."""
        return (
            self.host_cached_prompt_tokens / self.prompt_tokens_seen
            if self.prompt_tokens_seen else 0.0
        )

    @property
    def acceptance_rate(self) -> float:
        """Accepted draft tokens over proposed draft tokens (0.0 before
        any verify window ran)."""
        return (
            self.draft_tokens_accepted / self.draft_tokens_proposed
            if self.draft_tokens_proposed else 0.0
        )

    @property
    def mean_accepted_tokens_per_step(self) -> float:
        """Tokens emitted per verify window (accepted drafts + bonus);
        1.0 = no acceptance, 0.0 before any window ran."""
        return (
            self.spec_tokens_out / self.spec_steps if self.spec_steps
            else 0.0
        )

    # --------------------------------------------------- engine hooks
    def configure(self, max_slots: int, num_pages: int = 0,
                  host_pages: int = 0) -> None:
        self._max_slots = max(int(max_slots), 1)
        self._num_pages = max(int(num_pages), 0)
        self._host_pages = max(int(host_pages), 0)

    def on_step(self, filtered: bool = False,
                overlapped: bool = False) -> None:
        self.steps += 1
        self.filter_steps += bool(filtered)
        self.overlapped_steps += bool(overlapped)

    def on_keys(self, kind: str, attended: int, fetched: int,
                small_tile_slots: Optional[int] = None) -> None:
        """One step's attention work in one layer of ``kind``."""
        self.attended_keys[kind] += int(attended)
        self.fetched_keys[kind] += int(fetched)
        if small_tile_slots is not None:
            self.small_tile_slots[kind] += int(small_tile_slots)

    # ------------------------------------------------------ reporting
    @property
    def elapsed(self) -> float:
        return self.clock() - self._t0

    def tokens_per_s(self, window_s: Optional[float] = None) -> float:
        dur = self.elapsed if window_s is None else window_s
        return self.tokens_out / dur if dur > 0 else 0.0

    def snapshot(self) -> Dict[str, float]:
        snap = {
            "submitted": self.submitted,
            "admitted": self.admitted,
            "rejected": self.rejected,
            "evicted": self.evicted,
            "finished": self.finished,
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            "scheduled_tokens": self.scheduled_tokens,
            "prompt_tokens": self.prompt_tokens,
            "decode_tokens": self.decode_tokens,
            "chunk_steps": self.chunk_steps,
            "overlapped_steps": self.overlapped_steps,
            "discarded_rows": self.discarded_rows,
            "queue_depth": self.queue_depth,
            "slot_occupancy": self.slot_occupancy,
            "tokens_per_s": self.tokens_per_s(),
            "ttft_p50_s": percentile(self.ttft_s, 50),
            "ttft_p95_s": percentile(self.ttft_s, 95),
            "tpot_p50_s": percentile(self.tpot_s, 50),
            "tpot_p95_s": percentile(self.tpot_s, 95),
            "queue_wait_p95_s": percentile(self.queue_wait_s, 95),
            "prefix_hit_rate": self.prefix_hit_rate,
            "prefix_hits": self.prefix_hits,
            "cached_prompt_tokens": self.cached_prompt_tokens,
            "cow_copies": self.cow_copies,
            "prefill_chunks": self.prefill_chunks,
            "pages_in_use": self.pages_in_use,
            "arena_utilization": self.arena_utilization,
            "prefix_cache_entries": self.prefix_cache_entries,
            "spec_steps": self.spec_steps,
            "draft_tokens_proposed": self.draft_tokens_proposed,
            "draft_tokens_accepted": self.draft_tokens_accepted,
            "acceptance_rate": self.acceptance_rate,
            "mean_accepted_tokens_per_step":
                self.mean_accepted_tokens_per_step,
            "attention_paged_kernel": self.attention_paged_kernel,
            "expert_touched_kernel": self.expert_touched_kernel,
            "head_rows_per_step": self.head_rows_per_step,
            "dense_rows_per_step": self.dense_rows_per_step,
            "cache_rows_per_step": self.cache_rows_per_step,
            "relaid_param_leaves": self.relaid_param_leaves,
            "relaid_param_bytes": self.relaid_param_bytes,
            "param_relayout_s": self.param_relayout_s,
            "filter_steps": self.filter_steps,
        }
        for kind in self.attended_keys:
            snap[f"attended_keys_{kind}"] = self.attended_keys[kind]
            snap[f"fetched_keys_{kind}"] = self.fetched_keys[kind]
        for kind, slots in self.small_tile_slots.items():
            snap[f"small_tile_slots_{kind}"] = slots
        for kind, on in self.attention_paged_kernel_kinds.items():
            snap[f"attention_paged_kernel_{kind}"] = on
        if self.context_keys:
            snap["context_keys"] = self.context_keys
        if self.state_bytes:
            snap["state_bytes"] = self.state_bytes
            snap["state_resets"] = self.state_resets
        if self.hyper_streams:
            snap["hyper_streams"] = self.hyper_streams
        if self.index_pool_bytes:
            snap["index_pool_bytes"] = self.index_pool_bytes
        if "window" in self.attended_keys:
            snap.update({
                "pages_free": self.pages_free,
                "window_pages_in_use": self.window_pages_in_use,
                "window_pages_free": self.window_pages_free,
                "window_pages_released": self.window_pages_released,
            })
        if (self._host_pages or self.pages_spilled or self.pages_promoted
                or self.host_pages_resident):
            snap.update({
                "pages_spilled": self.pages_spilled,
                "pages_promoted": self.pages_promoted,
                "spill_bytes": self.spill_bytes,
                "promote_bytes": self.promote_bytes,
                "page_in_stall_s": self.page_in_stall_s,
                "host_pages_resident": self.host_pages_resident,
                "host_prefix_hits": self.host_prefix_hits,
                "host_cached_prompt_tokens": self.host_cached_prompt_tokens,
                "host_prefix_hit_rate": self.host_prefix_hit_rate,
            })
        if self.moe_steps:
            snap.update({
                "moe_steps": self.moe_steps,
                "moe_routed_tokens": self.moe_routed_tokens,
                "moe_dropped_fraction": self.moe_dropped_fraction,
                "moe_load_imbalance": self.moe_load_imbalance,
                "moe_a2a_bytes": self.moe_a2a_bytes,
                "moe_unrouted_tokens": self.moe_unrouted_tokens,
                "moe_experts_touched": self.moe_experts_touched,
            })
            # the per-expert histogram rides the snapshot (and the
            # serve/* bridge) as bounded scalar keys — E is small
            snap.update({
                f"moe_tokens_expert_{i}": v
                for i, v in enumerate(self.moe_tokens_per_expert)
            })
        if self.healthwatch is not None:
            snap["goodput"] = self.healthwatch.goodput_fraction()
        # empty-window hardening: every reported value is finite — no
        # NaN ever reaches the summary line or the CSV/monitor bridge
        return {k: _finite(v) for k, v in snap.items()}

    def summary(self) -> str:
        """comm_logger-style table."""
        s = self.snapshot()
        lines = [
            "serving metrics",
            f"{'requests':<18}submitted={self.submitted} "
            f"admitted={self.admitted} finished={self.finished} "
            f"rejected={self.rejected} evicted={self.evicted}",
            f"{'throughput':<18}{s['tokens_per_s']:.1f} tok/s over "
            f"{self.elapsed:.2f}s ({self.steps} steps, "
            f"{self.chunk_steps} with a prompt chunk; "
            f"{self.scheduled_tokens} scheduled tokens = "
            f"{self.prompt_tokens} prompt + {self.decode_tokens} decode)",
            f"{'ttft':<18}p50={s['ttft_p50_s'] * 1e3:.1f}ms "
            f"p95={s['ttft_p95_s'] * 1e3:.1f}ms",
            f"{'tpot':<18}p50={s['tpot_p50_s'] * 1e3:.1f}ms "
            f"p95={s['tpot_p95_s'] * 1e3:.1f}ms",
            f"{'gauges':<18}queue_depth={self.queue_depth} "
            f"slot_occupancy={self.slot_occupancy:.2f}"
            + (f" goodput={s['goodput']:.2f}" if "goodput" in s else ""),
        ]
        if self._num_pages:
            lines.append(
                f"{'paged arena':<18}pages_in_use={self.pages_in_use}/"
                f"{self._num_pages} (util {self.arena_utilization:.2f}), "
                f"prefix hit rate {self.prefix_hit_rate:.2f} "
                f"({self.prefix_hits}/{self.prefix_lookups} requests, "
                f"{self.cached_prompt_tokens} tokens), "
                f"cow_copies={self.cow_copies}, "
                f"prefill_chunks={self.prefill_chunks} "
                f"(+{self.cached_tail_feeds} cached-tail feeds)"
            )
        if self._host_pages or self.pages_spilled or self.pages_promoted:
            lines.append(
                f"{'kv tiering':<18}spilled={self.pages_spilled} pages "
                f"({self.spill_bytes / (1 << 20):.2f} MiB at rest), "
                f"promoted={self.pages_promoted} "
                f"({self.promote_bytes / (1 << 20):.2f} MiB), "
                f"host_resident={self.host_pages_resident}/"
                f"{self._host_pages}, host prefix hit rate "
                f"{self.host_prefix_hit_rate:.2f} "
                f"({self.host_cached_prompt_tokens} tokens), "
                f"page_in_stall={self.page_in_stall_s * 1e3:.1f}ms"
            )
        if self.spec_steps:
            lines.append(
                f"{'speculative':<18}acceptance "
                f"{self.acceptance_rate:.2f} "
                f"({self.draft_tokens_accepted}/"
                f"{self.draft_tokens_proposed} drafts), mean accepted "
                f"tokens/step {self.mean_accepted_tokens_per_step:.2f} "
                f"over {self.spec_steps} verify windows"
            )
        if self.moe_steps:
            hist = "/".join(str(v) for v in self.moe_tokens_per_expert)
            lines.append(
                f"{'moe serving':<18}tokens/expert [{hist}] over "
                f"{self.moe_steps} steps, load imbalance "
                f"{self.moe_load_imbalance:.2f}, dropped "
                f"{self.moe_dropped_fraction:.3f}, a2a "
                f"{self.moe_a2a_bytes / (1 << 20):.2f} MiB"
            )
        if self.evict_reasons:
            reasons = ", ".join(
                f"{k}: {v}" for k, v in sorted(self.evict_reasons.items())
            )
            lines.append(f"{'evictions':<18}{reasons}")
        return "\n".join(lines)

    def write_to(self, monitor, step: int) -> None:
        """Feed the monitor/ backends through the steptrace registry's
        single ``write_events`` bridge, under the documented ``serve/*``
        namespace (one coherent scheme with ``train/*``/``comm/*``/
        ``plan/*`` — docs/observability.md)."""
        from ..profiling.steptrace import write_events

        write_events(monitor, [
            (f"serve/{k}", float(v), int(step))
            for k, v in self.snapshot().items()
        ])


class FleetMetrics:
    """Aggregate view over a fleet's per-replica :class:`ServingMetrics`
    plus the router's own counters (serving/fleet/router.py). Counters
    sum across replicas; latency percentiles merge the per-replica sample
    lists (a request's TTFT is a fleet-level fact — it does not matter
    which replica served it); gauges that are depths sum, ratios average
    over replicas. Duck-types the attributes the healthwatch serving
    watchdogs read (``queue_depth``, ``ttft_s``, and the zero_progress
    trio ``tokens_out``/``scheduled_tokens``/``slot_occupancy``), so
    the queue/TTFT/livelock rules evaluate FLEET-wide when the router
    owns the healthwatch.

    Exported under the ``serve/fleet/*`` namespace (per-replica metrics
    keep ``serve/*`` on their own engines) — docs/observability.md."""

    # replica counters that sum into the fleet snapshot
    _SUM_KEYS = (
        "submitted", "admitted", "rejected", "evicted", "finished",
        "steps", "tokens_out", "scheduled_tokens", "prompt_tokens",
        "decode_tokens", "chunk_steps", "overlapped_steps",
        "discarded_rows", "prefix_hits", "cached_prompt_tokens",
        "cow_copies", "prefill_chunks", "cached_tail_feeds", "spec_steps",
        "draft_tokens_proposed", "draft_tokens_accepted", "pages_in_use",
        "pages_spilled",
        "pages_promoted", "spill_bytes", "promote_bytes",
        "host_prefix_hits", "host_cached_prompt_tokens",
        "host_pages_resident",
    )

    def __init__(self, replica_metrics: List["ServingMetrics"],
                 clock=time.monotonic):
        self.replicas = list(replica_metrics)
        self.clock = clock
        self._t0 = clock()
        # router counters (fed by Router, not by replicas)
        self.routed = 0             # requests dispatched to a replica
        self.shed = 0               # fleet-level graceful rejections
        self.shed_reasons: Dict[str, int] = defaultdict(int)
        self.handoffs = 0           # completed prefill→decode transfers
        self.handoff_failures = 0   # attempts deferred (no slot/pages)
        self.handoff_pages = 0      # pages moved across pools
        self.affinity_routed = 0    # routed by session stickiness
        self.prefix_routed = 0      # routed by a non-zero chain match
        self.ticks = 0              # router ticks that stepped >= 1 replica
        # fleet-level TTFT samples in true COMPLETION order (the router
        # appends as requests finish, whichever replica served them) —
        # bounded, because its only consumers are recent-window reads:
        # the shed_ttft_p95_s gate and the ttft_breach watchdog. A
        # replica-order concatenation of the full per-replica lists
        # would make a trailing window read mostly the LAST replica's
        # history (and cost O(total requests) per submit).
        self.recent_ttft_s: "deque[float]" = deque(maxlen=256)

    # ------------------------------------------------------ router hooks
    def on_route(self, via: str) -> None:
        self.routed += 1
        if via == "affinity":
            self.affinity_routed += 1
        elif via == "prefix":
            self.prefix_routed += 1

    def on_shed(self, reason: str) -> None:
        self.shed += 1
        self.shed_reasons[reason] += 1

    def on_handoff(self, ok: bool, pages: int = 0) -> None:
        if ok:
            self.handoffs += 1
            self.handoff_pages += int(pages)
        else:
            self.handoff_failures += 1

    def on_tick(self) -> None:
        self.ticks += 1

    def on_finish_ttft(self, ttft_s: float) -> None:
        """One finished request's TTFT, appended by the router in fleet
        completion order."""
        self.recent_ttft_s.append(float(ttft_s))

    # ----------------------------------------- healthwatch duck-typing
    @property
    def queue_depth(self) -> int:
        """Fleet queue depth: requests admitted but not yet slotted,
        summed across replicas (the queue_depth_breach watchdog input)."""
        return sum(int(m.queue_depth) for m in self.replicas)

    @property
    def ttft_s(self) -> List[float]:
        """Recent TTFT samples in fleet COMPLETION order (bounded) — the
        ttft_breach watchdog's recent-window input. All-time percentiles
        live in :meth:`snapshot`, which merges the full per-replica
        lists."""
        return list(self.recent_ttft_s)

    @property
    def tokens_out(self) -> int:
        """Fleet-wide emitted tokens (zero_progress watchdog input)."""
        return sum(int(m.tokens_out) for m in self.replicas)

    @property
    def scheduled_tokens(self) -> int:
        """Fleet-wide scheduled tokens — prefill chunks count as
        progress for the zero_progress watchdog even before a request's
        first sampled token."""
        return sum(int(m.scheduled_tokens) for m in self.replicas)

    @property
    def slot_occupancy(self) -> float:
        """Mean slot occupancy across replicas: the zero_progress
        watchdog only treats frozen counters as a stall while work is
        actually slotted somewhere."""
        return (sum(float(m.slot_occupancy) for m in self.replicas)
                / max(len(self.replicas), 1))

    # ------------------------------------------------------ reporting
    @property
    def elapsed(self) -> float:
        return self.clock() - self._t0

    def tokens_per_s(self) -> float:
        total = sum(m.tokens_out for m in self.replicas)
        dur = self.elapsed
        return total / dur if dur > 0 else 0.0

    def snapshot(self) -> Dict[str, float]:
        snap: Dict[str, float] = {
            k: sum(getattr(m, k) for m in self.replicas)
            for k in self._SUM_KEYS
        }
        ttft: List[float] = []
        tpot: List[float] = []
        qwait: List[float] = []
        for m in self.replicas:
            ttft.extend(m.ttft_s)
            tpot.extend(m.tpot_s)
            qwait.extend(m.queue_wait_s)
        snap.update({
            "replicas": len(self.replicas),
            "queue_depth": self.queue_depth,
            "slot_occupancy": (
                sum(m.slot_occupancy for m in self.replicas)
                / max(len(self.replicas), 1)
            ),
            "tokens_per_s": self.tokens_per_s(),
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p95_s": percentile(ttft, 95),
            "tpot_p50_s": percentile(tpot, 50),
            "tpot_p95_s": percentile(tpot, 95),
            "queue_wait_p95_s": percentile(qwait, 95),
            "routed": self.routed,
            "shed": self.shed,
            "handoffs": self.handoffs,
            "handoff_failures": self.handoff_failures,
            "handoff_pages": self.handoff_pages,
            "affinity_routed": self.affinity_routed,
            "prefix_routed": self.prefix_routed,
            "ticks": self.ticks,
        })
        return {k: _finite(v) for k, v in snap.items()}

    def per_replica(self) -> List[Dict[str, float]]:
        """The un-aggregated view: one ServingMetrics snapshot per
        replica, in replica order."""
        return [m.snapshot() for m in self.replicas]

    def summary(self) -> str:
        s = self.snapshot()
        lines = [
            f"fleet metrics ({len(self.replicas)} replicas)",
            f"{'requests':<18}submitted={s['submitted']} "
            f"routed={self.routed} finished={s['finished']} "
            f"shed={self.shed} evicted={s['evicted']}",
            f"{'throughput':<18}{s['tokens_per_s']:.1f} tok/s over "
            f"{self.elapsed:.2f}s ({s['steps']} replica steps, "
            f"{self.ticks} router ticks)",
            f"{'ttft':<18}p50={s['ttft_p50_s'] * 1e3:.1f}ms "
            f"p95={s['ttft_p95_s'] * 1e3:.1f}ms",
            f"{'tpot':<18}p50={s['tpot_p50_s'] * 1e3:.1f}ms "
            f"p95={s['tpot_p95_s'] * 1e3:.1f}ms",
            f"{'routing':<18}affinity={self.affinity_routed} "
            f"prefix={self.prefix_routed} "
            f"handoffs={self.handoffs} "
            f"(+{self.handoff_failures} deferred, "
            f"{self.handoff_pages} pages moved)",
        ]
        per_rep = " ".join(
            f"r{i}={m.tokens_out}" for i, m in enumerate(self.replicas)
        )
        lines.append(f"{'tokens by replica':<18}{per_rep}")
        if self.shed_reasons:
            reasons = ", ".join(
                f"{k}: {v}" for k, v in sorted(self.shed_reasons.items())
            )
            lines.append(f"{'shed':<18}{reasons}")
        return "\n".join(lines)

    def write_to(self, monitor, step: int) -> None:
        """Fleet aggregates under ``serve/fleet/*`` through the one
        write_events bridge; each replica's own engine keeps writing its
        ``serve/*`` series (docs/observability.md, "Fleet namespace")."""
        from ..profiling.steptrace import write_events

        write_events(monitor, [
            (f"serve/fleet/{k}", float(v), int(step))
            for k, v in self.snapshot().items()
        ])
