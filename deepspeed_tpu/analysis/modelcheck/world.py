"""fleetcheck world: the REAL host-plane objects under a null device.

A :class:`World` instantiates one scenario's control plane exactly as
production wires it — real :class:`~deepspeed_tpu.serving.scheduler.
Scheduler` (with real :class:`~deepspeed_tpu.serving.paging.PagePool`,
:class:`PrefixCache`, :class:`HostPageStore`, :class:`PageSpiller`),
real :class:`~deepspeed_tpu.serving.fleet.replica.ReplicaHandle` +
:func:`~deepspeed_tpu.serving.fleet.handoff.handoff`, and the real
:class:`~deepspeed_tpu.serving.fleet.router.Router` routing/shedding
methods — but with the device engine replaced by a null engine and the
clock replaced by a fake. The model checker then applies CONTROLLED
events:

- ``("submit", i)`` / ``("resubmit", i)`` — request ``i`` arrives /
  retries after eviction,
- ``("advance", k)`` — the fake clock jumps by ``advance_dts[k]``
  (enables timeout eviction and backoff expiry),
- ``("tick", rid, outcomes)`` — one scheduler tick on replica ``rid``:
  ``plan()``, the null device "executes" it, ``complete()`` folds it
  back. ``outcomes`` decides what each SAMPLING slot produced — a tuple
  of ``"tok" | "eos" | "acc"`` per sampler in plan order, an int
  bitmask (seeded random walks), or None (the all-EOS drain policy),
- ``("handoff",)`` — one router handoff pass (prefill→decode moves).

Everything else in the ISSUE's alphabet — timeout-evict, LRU-evict,
demote, promote, deferral — is a deterministic CONSEQUENCE of those
controlled events; the world observes them through the scheduler's own
metrics hooks and the prefix cache's listener seam and records them in
``world.log``, so counterexample traces show the full causal story.

Replay-from-scratch is the state model: a World is cheap to build, and
a trace of events reproduces a state bit-for-bit (the determinism the
satellite audit enforces). There is deliberately NO deepcopy anywhere.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...serving import faults
from ...serving.paging import HostPageStore, PageSpiller
from ...serving.request import Request, RequestState, RequestStatus
from ...serving.scheduler import Scheduler
from .invariants import CheckFailure, check_event, check_world
from .scenarios import Scenario

__all__ = ["World", "FakeClock", "ReplayDrift", "build_world"]


class ReplayDrift(RuntimeError):
    """A trace replayed into a different state than it was recorded
    from — the determinism regression fleetcheck exists to prevent."""


class FakeClock:
    """Injectable monotonic clock: ticks cost nothing, "advance" events
    move it explicitly."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


class _NullEngine:
    """The slice of the ServingEngine surface the host plane touches:
    scheduler access, submit delegation, and page-payload export/import
    (the fleet handoff's device half — a no-op here; what fleetcheck
    verifies is the HOST-side page/slot accounting around it)."""

    def __init__(self, scheduler: Scheduler, spiller: Optional[PageSpiller]):
        self.scheduler = scheduler
        self.spiller = spiller

    def submit(self, request: Request) -> RequestState:
        return self.scheduler.submit(request)

    def export_kv_pages(self, page_ids: Sequence[int]):
        return {"pages": tuple(int(p) for p in page_ids)}

    def import_kv_pages(self, payload, dst_pages: Sequence[int]) -> None:
        del payload, dst_pages


def _null_export(page_ids: Sequence[int]) -> Dict[str, np.ndarray]:
    """PageSpiller export_fn: a tiny constant int8 leaf per page.
    Integer leaves take encode_page's RAW path — no codec math, no jax
    dispatch — while still exercising the real HostPageStore put/get/
    drop lifecycle and pinned-buffer recycling."""
    return {"kv": np.zeros((1, len(list(page_ids)), 2), np.int8)}


class _Recorder:
    """Duck-typed ServingMetrics consumer: turns the scheduler's metric
    hooks into observed-event log lines (admit, evict, demote, finish)
    and feeds the H6 backoff ledger. Every method the Scheduler or
    PageSpiller may call must exist here."""

    def __init__(self, world: "World", rid: int):
        self._w = world
        self._rid = rid

    # ---- lifecycle hooks the checker observes
    def on_admit(self, state, now, queue_depth=0):
        self._w.log.append(("admit", self._rid, self._w.req_index(state)))

    def on_evict(self, state, now):
        self._w.log.append((
            "evict", self._rid, self._w.req_index(state),
            state.evict_reason,
        ))
        self._w.record_backoff(state, now)

    def on_finish(self, state, now):
        self._w.log.append(("finish", self._rid,
                            self._w.req_index(state)))

    def on_spill(self, nbytes=0):
        self._w.log.append(("demote", self._rid))

    # ---- hooks observed elsewhere or not needed: keep as no-ops
    def on_submit(self, state, now, queue_depth=0):
        pass

    def on_plan(self, plan, now, queue_depth=0, occupancy=0):
        pass

    def on_token(self, state, now):
        pass

    def on_rows(self, tokens, discarded=0, prompt_tokens=0,
                chunk_step=False):
        pass

    def on_spec(self, state, proposed, accepted, emitted):
        pass

    def on_prefix_lookup(self, cached_tokens, prompt_len, host_tokens=0):
        pass

    def on_cow(self):
        pass

    def on_prefill_chunk(self, cached_tail=False):
        pass

    def on_pages(self, pool, cache_entries=0, host_resident=0):
        pass

    def on_page_in(self, pages=1, nbytes=0, stall_s=0.0):
        pass

    def cache_listener(self, event, kind, h, page):
        if event == "evict":
            self._w.log.append((f"lru-evict-{kind}", self._rid))


def _build_router(world: "World"):
    """A real Router driven headless: the routing/shedding/handoff
    methods are the production ones; only the heavyweight constructor
    (init_inference + ServingEngine replicas) is bypassed, since the
    world already built the replicas over null engines."""
    from ...config import FleetConfig, ServingConfig
    from ...serving.fleet.router import Router
    from ...serving.metrics import FleetMetrics

    sc = world.scenario
    r = Router.__new__(Router)
    serving = ServingConfig()
    serving.max_slots = sc.max_slots
    serving.queue_limit = sc.queue_limit
    serving.eviction_backoff_s = sc.eviction_backoff_s
    serving.max_tokens = sc.max_tokens
    r.serving = serving
    r.fleet = FleetConfig(
        enabled=True, replicas=sc.replicas,
        prefill_replicas=sc.prefill_replicas,
        routing=sc.routing, affinity=sc.affinity,
        queue_limit=sc.fleet_queue_limit,
    )
    r.clock = world.clock
    r.replicas = world.replicas
    r._intake = [rep for rep in world.replicas
                 if rep.role in ("prefill", "mixed")]
    r._decode = [rep for rep in world.replicas if rep.role == "decode"]
    r.index = None  # prefix routing needs the event-mirrored index; the
    #   presets route least_loaded/round_robin (GlobalPrefixIndex has its
    #   own unit suite)
    r.metrics = FleetMetrics([], clock=world.clock)
    r._sessions = {}
    r._rr = 0
    r.healthwatch = None
    r.tracer = None
    r.last_tick_durations = {}
    r.last_tick_overhead_s = 0.0
    return r


def build_world(scenario: Scenario) -> "World":
    return World(scenario)


class World:
    def __init__(self, scenario: Scenario):
        sc = scenario
        self.scenario = sc
        self.clock = FakeClock()
        self.log: List[tuple] = []        # observed consequences
        self.trace: List[tuple] = []      # controlled events applied
        self.backoff: Dict[Tuple[int, int], float] = {}  # (req, attempt)
        #   -> retry_after - now at eviction time (H6 ledger)
        self.tokens_emitted = 0
        self.tokens_scheduled = 0
        self.n_advances = 0
        self.resubmits = [0] * len(sc.requests)

        # ---- requests (numpy rng arrays: no jax dispatch per replay)
        self.requests: List[Request] = []
        self.states: List[Optional[RequestState]] = [None] * len(
            sc.requests
        )
        self._req_idx: Dict[str, int] = {}
        for i, spec in enumerate(sc.requests):
            rid = f"q{i}"
            self.requests.append(Request(
                request_id=rid,
                prompt=np.asarray(spec.prompt, np.int32),
                max_new_tokens=int(spec.max_new),
                repetition_penalty=float(spec.penalty),
                eos_token_id=int(sc.eos_token),
                rng=np.zeros(2, np.uint32),
                session_id=spec.session,
            ))
            self._req_idx[rid] = i

        # ---- replicas: real schedulers (+tiers) over null engines
        from ...serving.fleet.replica import (ROLE_DECODE, ROLE_MIXED,
                                              ROLE_PREFILL, ReplicaHandle)

        self.replicas: List[ReplicaHandle] = []
        self.stores: List[Optional[HostPageStore]] = []
        k = int(sc.prefill_replicas)
        for i in range(int(sc.replicas)):
            role = ROLE_PREFILL if i < k else (
                ROLE_DECODE if k else ROLE_MIXED
            )
            num_pages = sc.num_pages
            max_slots = sc.max_slots
            if role == ROLE_DECODE:
                if sc.decode_num_pages is not None:
                    num_pages = sc.decode_num_pages
                if sc.decode_max_slots is not None:
                    max_slots = sc.decode_max_slots
            recorder = _Recorder(self, i)
            spiller = None
            store = None
            if sc.host_pages > 0:
                store = HostPageStore(sc.host_pages, codec="fp32")
                spiller = PageSpiller(store, _null_export,
                                      metrics=recorder)
            sched = Scheduler(
                max_slots=max_slots,
                token_budget=sc.token_budget,
                queue_limit=sc.queue_limit,
                request_timeout_s=sc.request_timeout_s,
                eviction_backoff_s=sc.eviction_backoff_s,
                max_tokens=sc.max_tokens,
                clock=self.clock,
                metrics=recorder,
                page_size=sc.page_size,
                num_pages=num_pages,
                pages_per_slot=sc.pages_per_slot,
                # decode replicas never prefill (Router.__init__ rule)
                prefix_cache=sc.prefix_cache and role != ROLE_DECODE,
                spec_max_draft=sc.spec_max_draft,
                spiller=spiller,
            )
            if sched.prefix_cache is not None:
                sched.prefix_cache.listener = recorder.cache_listener
            self.replicas.append(
                ReplicaHandle(i, _NullEngine(sched, spiller), role)
            )
            self.stores.append(store)

        self.router = _build_router(self) if sc.replicas > 1 else None

    # ------------------------------------------------------------ helpers
    def req_index(self, state: RequestState) -> int:
        return self._req_idx[state.request.request_id]

    def record_backoff(self, state: RequestState, now: float) -> None:
        if state.retry_after is not None:
            self.backoff[(self.req_index(state), int(state.attempts))] = (
                float(state.retry_after) - float(now)
            )

    def scheduler(self, rid: int) -> Scheduler:
        return self.replicas[rid].engine.scheduler

    def replica_of(self, state: RequestState) -> Optional[int]:
        """Which replica's slots hold ``state`` (None = unslotted)."""
        owners = [
            rep.replica_id for rep in self.replicas
            if state.slot is not None
            and state.slot < len(rep.engine.scheduler.slots)
            and rep.engine.scheduler.slots[state.slot] is state
        ]
        if len(owners) > 1:
            raise CheckFailure(
                "H5", f"request {state.request.request_id} slotted on "
                      f"replicas {owners} simultaneously"
            )
        return owners[0] if owners else None

    def quiescent(self) -> bool:
        """All SUBMITTED requests terminal (DONE or EVICTED)."""
        return all(
            st is None or st.status in (RequestStatus.DONE,
                                        RequestStatus.EVICTED)
            for st in self.states
        )

    @property
    def progress(self) -> int:
        """Cumulative token progress: emitted + scheduled (prefill
        chunks count — a long prefill is progress even before its first
        sampled token; promote-only thrash is NOT)."""
        return self.tokens_emitted + self.tokens_scheduled

    # ----------------------------------------------------------- events
    def apply(self, ev: tuple, check: bool = True) -> None:
        """Apply one controlled event; with ``check``, run the H1–H7
        registry afterwards (raises :class:`CheckFailure`)."""
        kind = ev[0]
        self.trace.append(ev)
        if kind == "submit":
            self._submit(ev[1])
        elif kind == "resubmit":
            self._resubmit(ev[1])
        elif kind == "advance":
            self.clock.advance(self.scenario.advance_dts[ev[1]])
            self.n_advances += 1
        elif kind == "tick":
            self._tick(ev[1], ev[2], check=check)
        elif kind == "handoff":
            self._handoff(check=check)
        else:
            raise ValueError(f"unknown event {ev!r}")
        if check:
            check_world(self)

    def _submit(self, i: int) -> None:
        if self.states[i] is not None:
            raise ReplayDrift(f"request q{i} submitted twice")
        now = self.clock()
        if self.router is not None:
            st = self.router.submit(self.requests[i])
        else:
            st = self.scheduler(0).submit(self.requests[i])
        self.states[i] = st
        if st.status is RequestStatus.EVICTED:
            # router-level sheds never pass through a scheduler metrics
            # hook — ledger them here (idempotent keying covers the
            # scheduler-rejection path that already recorded)
            self.record_backoff(st, now)
            self.log.append(("shed", -1, i, st.evict_reason))

    def _resubmit(self, i: int) -> None:
        st = self.states[i]
        if st is None or st.status is not RequestStatus.EVICTED:
            raise ReplayDrift(f"resubmit of non-evicted q{i}")
        now = self.clock()
        self.resubmits[i] += 1
        if self.router is not None:
            st = self.router.resubmit(st)
        else:
            st = self.scheduler(0).resubmit(st)
        self.states[i] = st
        if st.status is RequestStatus.EVICTED:
            self.record_backoff(st, now)
            self.log.append(("shed", -1, i, st.evict_reason))

    def _outcomes_for(self, samplers, outcomes):
        """Normalize an outcomes operand to one symbol per sampler.
        Tuple = explicit (exhaustive BFS); int = 2 bits per sampler
        (seeded random walks: 00/01 tok, 10 eos, 11 acc-if-spec);
        None = all-EOS (the liveness drain policy)."""
        if outcomes is None:
            return ["eos"] * len(samplers)
        if isinstance(outcomes, int):
            out = []
            for j, w in enumerate(samplers):
                bits = (outcomes >> (2 * j)) & 0b11
                if bits == 0b10:
                    out.append("eos")
                elif bits == 0b11 and w.spec_len >= 1:
                    out.append("acc")
                else:
                    out.append("tok")
            return out
        if len(outcomes) != len(samplers):
            raise ReplayDrift(
                f"tick outcomes arity {len(outcomes)} != samplers "
                f"{len(samplers)} — non-deterministic replay"
            )
        return list(outcomes)

    def _tick(self, rid: int, outcomes, check: bool = True) -> None:
        sc = self.scenario
        rep = self.replicas[rid]
        sched = rep.engine.scheduler
        plan = sched.plan()
        if plan is None:
            if outcomes not in (None, ()) and outcomes != 0:
                raise ReplayDrift(f"idle tick on r{rid} got outcomes "
                                  f"{outcomes!r}")
            return
        # the engine's stage handling: decode each promoted page's blob
        # out of the store (real get + pinned-buffer path); the jitted
        # scatter itself is device work the null engine skips
        for s in plan.stage:
            rep.engine.spiller.load(s.key)
            self.log.append(("promote", rid, self.req_index(s.state)))
        samplers = [w for w in plan.work if w.sample]
        if check:
            check_event(self, rid, plan)
        syms = self._outcomes_for(samplers, outcomes)
        n_slots = sched.max_slots
        width = max(int(sched.spec_max_draft), 0) + 1
        next_tokens = np.zeros((n_slots, width), np.int32)
        n_emit = np.zeros(n_slots, np.int32)
        emitted = 0
        for w, sym in zip(samplers, syms):
            remaining = (w.state.request.max_new_tokens
                         - len(w.state.tokens))
            if sym == "eos":
                n = 1
                next_tokens[w.slot, 0] = sc.eos_token
            elif sym == "acc":
                # accept every draft + the bonus token (the planner caps
                # spec_len at remaining - 1, so this never overruns)
                n = min(w.spec_len + 1, remaining)
                next_tokens[w.slot, :n] = sc.tok_token
            else:
                n = 1
                next_tokens[w.slot, 0] = sc.tok_token
            n_emit[w.slot] = n
            emitted += n
        self.tokens_scheduled += plan.total_tokens
        sched.complete(plan, next_tokens, n_emit=n_emit)
        self.tokens_emitted += emitted

    def _handoff(self, check: bool = True) -> None:
        if self.router is None:
            raise ReplayDrift("handoff event without a fleet")
        before = {
            i: self.replica_of(st)
            for i, st in enumerate(self.states) if st is not None
        }
        moved = self.router._run_handoffs()
        if moved:
            self.log.append(("handoff", moved))
        else:
            self.log.append(("handoff-deferred",))
        if check:
            for i, st in enumerate(self.states):
                if st is None:
                    continue
                after = self.replica_of(st)
                if (after is not None and before.get(i) is not None
                        and after != before[i]
                        and st.request.repetition_penalty != 1.0):
                    raise CheckFailure(
                        "H7", f"penalized request q{i} was handed off "
                              f"(r{before[i]} -> r{after}) — the seen "
                              f"matrix cannot survive a handoff"
                    )

    # ------------------------------------------------- event enumeration
    def enabled_nontick(self) -> List[tuple]:
        """Controlled events enabled in THIS state, excluding ticks
        (tick arity needs a plan probe — explore.py owns that)."""
        sc = self.scenario
        evs: List[tuple] = []
        for i, st in enumerate(self.states):
            if st is None:
                evs.append(("submit", i))
            elif (st.status is RequestStatus.EVICTED
                  and self.resubmits[i] < sc.max_resubmits):
                evs.append(("resubmit", i))
        if self.n_advances < sc.max_advances:
            for k in range(len(sc.advance_dts)):
                evs.append(("advance", k))
        if self.router is not None and self.router._decode:
            if any(rep.role == "prefill" and rep.decode_candidates()
                   for rep in self.replicas):
                evs.append(("handoff",))
        return evs

    def tickable(self) -> List[int]:
        return [rep.replica_id for rep in self.replicas
                if rep.engine.scheduler.has_work]


def replay(scenario: Scenario, trace: Sequence[tuple],
           check: bool = False) -> World:
    """Reconstruct the state a trace leads to, from scratch. With
    ``check`` the invariant registry runs after every event — the
    counterexample round-trip mode."""
    with faults.arming(*scenario.mutations):
        w = World(scenario)
        for ev in trace:
            w.apply(ev, check=check)
    return w
