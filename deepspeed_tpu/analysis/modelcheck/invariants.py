"""fleetcheck invariants H1–H7: checker-side safety oracles.

Every invariant is recomputed HERE, from first principles, over the live
host objects — deliberately NOT by calling the scheduler's own
``assert_page_invariants`` (a mutant that forgets to assert internally
must still be caught; the seeded ``handoff_leak`` fault does exactly
that). The scheduler's internal asserts still run where production runs
them, and any AssertionError they raise surfaces as an
``INTERNAL_ASSERT`` violation in explore.py.

The registry (ids are the contract the CLI, docs, CI greps and the
--mutate smokes all name):

- **H1  pool conservation** — per PagePool: free + live == num_pages,
  the free list holds only refcount-0 pages, no negative refcounts.
- **H2  cross-tier key ledger** — per HostPageStore: the resident key
  set equals exactly {in-flight promotions} ∪ {slot host_pages keys} ∪
  {prefix-cache host-tier keys}; pins reference resident keys only.
- **H3  refcount parity** — per pool: every page's refcount equals the
  number of independently-recomputed holders (slot page tables + prefix
  cache LRU entries). A leaked page (refs with no holder) or a
  use-after-free (holder with no ref) lands here.
- **H4  reference validity** — page ids in range, no slot referencing a
  free page, ``-1`` placeholders paired with host_pages entries, and
  terminal (DONE/EVICTED) states holding no page or key references.
- **H5  handoff / slot atomicity** — a request is slotted on at most
  one replica, live states are slotted-or-queued exactly where their
  status says, and no state sits in two admission queues.
- **H6  backoff monotonicity** — the retry_after hint's backoff delta
  is positive and non-decreasing in the request's attempt count.
- **H7  penalized-bypass discipline** — a repetition-penalized request
  never reuses prefix-cache tokens, never carries draft state, is never
  scheduled with a nonzero spec window (per-plan check), and is never
  handed off across replicas (checked at the handoff event).

Liveness ids (explore.py): **LIVELOCK** (fingerprint recurrence at
equal cumulative progress during the all-EOS drain) and
**NO_QUIESCENCE** (drain horizon exhausted).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from ...serving.request import RequestStatus

__all__ = ["CheckFailure", "check_world", "check_event", "INVARIANTS"]

INVARIANTS: Dict[str, str] = {
    "H1": "per-pool page conservation (free + live == num_pages)",
    "H2": "cross-tier host key ledger (store keys == referenced keys)",
    "H3": "refcount parity (pool refcounts == recomputed holders)",
    "H4": "page-reference validity (range, -1/host pairing, terminals)",
    "H5": "handoff/slot atomicity (one replica, status <-> placement)",
    "H6": "retry_after backoff positive + monotone in attempts",
    "H7": "penalized requests bypass prefix/spec/handoff",
    "LIVELOCK": "zero-progress cycle under the all-EOS drain",
    "NO_QUIESCENCE": "drain horizon exhausted before quiescence",
    "INTERNAL_ASSERT": "a production-side assertion tripped",
}


class CheckFailure(Exception):
    """One invariant violated; ``invariant`` names the registry id."""

    def __init__(self, invariant: str, message: str):
        self.invariant = invariant
        super().__init__(message)


def _holders(sched) -> Tuple[Dict[int, int], List]:
    """Recompute expected per-page refcounts from holders: slotted /
    queued request page tables + prefix-cache LRU entries."""
    exp: Dict[int, int] = {}
    live_states = [s for s in sched.slots if s is not None]
    live_states += list(sched.queue)
    for st in live_states:
        for p in st.pages:
            if p != -1:
                exp[p] = exp.get(p, 0) + 1
    if sched.prefix_cache is not None:
        for p in sched.prefix_cache.held_pages:
            exp[p] = exp.get(p, 0) + 1
    return exp, live_states


def _check_counts(rid: int, pool, exp: Dict[int, int], what: str = "pool"
                  ) -> None:
    """H1 and H3 for one PagePool against independently recomputed
    holders ``exp`` (page -> references)."""
    n = pool.num_pages
    # H1: conservation
    if pool.free_count + pool.live_count != n:
        raise CheckFailure(
            "H1", f"r{rid}: {what} conservation broken — free "
                  f"{pool.free_count} + live {pool.live_count} != {n}"
        )
    for p in pool._free:
        if pool.refcount[p] != 0:
            raise CheckFailure(
                "H1", f"r{rid}: page {p} on the free list with refcount "
                      f"{int(pool.refcount[p])}"
            )
    if (pool.refcount < 0).any():
        raise CheckFailure("H1", f"r{rid}: negative refcount in {what}")

    # H3: refcount parity against independently recomputed holders
    for p in range(n):
        actual = int(pool.refcount[p])
        want = exp.get(p, 0)
        if actual != want:
            kind = ("page leak (refs with no holder)" if actual > want
                    else "dangling holder (holder with no ref)")
            raise CheckFailure(
                "H3", f"r{rid}: refcount parity broken on page {p}: "
                      f"{what} says {actual}, holders say {want} — {kind}"
            )


def _check_window_pool(rid: int, sched) -> None:
    """H1, H3 and H4 for the window layers' pool (a model with window
    attention layers keeps pages by layer kind): its only holders are the
    slotted requests' ``win_pages``."""
    pool = sched.window_pool
    exp: Dict[int, int] = {}
    for st in sched.slots:
        for p in (st.win_pages if st is not None else ()):
            if not (0 <= p < pool.num_pages):
                raise CheckFailure(
                    "H4", f"r{rid}: {st.request.request_id} references "
                          f"out-of-range window page {p}"
                )
            exp[p] = exp.get(p, 0) + 1
    _check_counts(rid, pool, exp, "window pool")
    for st in sched.queue:
        if st.win_pages:
            raise CheckFailure(
                "H4", f"r{rid}: queued {st.request.request_id} holds "
                      f"window pages"
            )


def _check_pool(world, rid: int, sched) -> None:
    pool = sched.pool
    n = pool.num_pages
    exp, live_states = _holders(sched)
    _check_counts(rid, pool, exp)
    if getattr(sched, "window_pool", None) is not None:
        _check_window_pool(rid, sched)

    # H4: reference validity
    for st in live_states:
        rid_s = st.request.request_id
        for li, p in enumerate(st.pages):
            if p == -1:
                if li not in st.host_pages:
                    raise CheckFailure(
                        "H4", f"r{rid}: {rid_s} logical page {li} is -1 "
                              f"with no host_pages entry"
                    )
                continue
            if not (0 <= p < n):
                raise CheckFailure(
                    "H4", f"r{rid}: {rid_s} references out-of-range "
                          f"page {p}"
                )
            if pool.refcount[p] <= 0:
                raise CheckFailure(
                    "H4", f"r{rid}: {rid_s} references FREED page {p}"
                )
        for li in st.host_pages:
            if li >= len(st.pages) or st.pages[li] != -1:
                raise CheckFailure(
                    "H4", f"r{rid}: {rid_s} host_pages[{li}] not backed "
                          f"by a -1 placeholder"
                )


def _check_store(world, rid: int, sched, store) -> None:
    exp_keys = set(sched._inflight)
    for st in sched.slots:
        if st is not None:
            exp_keys.update(k for k, _ in st.host_pages.values())
    cache = sched.prefix_cache
    if cache is not None:
        exp_keys.update(skey for skey, _ in cache._host_full.values())
    actual = set(store.keys())
    if actual != exp_keys:
        leaked = sorted(actual - exp_keys)
        dangling = sorted(exp_keys - actual)
        raise CheckFailure(
            "H2", f"r{rid}: host key ledger broken — "
                  f"leaked keys {leaked}, dangling refs {dangling}"
        )
    if cache is not None:
        for skey, pins in cache._host_pins.items():
            if pins <= 0:
                raise CheckFailure(
                    "H2", f"r{rid}: non-positive pin count {pins} on "
                          f"host key {skey}"
                )
            if skey not in actual:
                raise CheckFailure(
                    "H2", f"r{rid}: pinned host key {skey} not resident"
                )


def _check_placement(world) -> None:
    for i, st in enumerate(world.states):
        if st is None:
            continue
        owner = world.replica_of(st)  # raises H5 on double-slotting
        queued_on = [
            rep.replica_id for rep in world.replicas
            if st in rep.engine.scheduler.queue
        ]
        if len(queued_on) > 1:
            raise CheckFailure(
                "H5", f"q{i} sits in {len(queued_on)} admission queues"
            )
        if st.status in (RequestStatus.PREFILL, RequestStatus.DECODE):
            if owner is None:
                raise CheckFailure(
                    "H5", f"q{i} is {st.status.value} but slotted on no "
                          f"replica"
                )
            if st.slot is None:
                raise CheckFailure("H5", f"q{i} active with slot=None")
        elif st.status is RequestStatus.QUEUED:
            if not queued_on or owner is not None:
                raise CheckFailure(
                    "H5", f"q{i} is queued but placement says "
                          f"slotted={owner} queues={queued_on}"
                )
        else:  # DONE / EVICTED
            if owner is not None or queued_on:
                raise CheckFailure(
                    "H5", f"q{i} is terminal ({st.status.value}) but "
                          f"still placed (slot on r{owner}, "
                          f"queues {queued_on})"
                )
            if st.pages or st.host_pages:
                raise CheckFailure(
                    "H4", f"q{i} is terminal but still holds "
                          f"{len(st.pages)} pages / "
                          f"{len(st.host_pages)} host keys"
                )


def _check_backoff(world) -> None:
    by_req: Dict[int, List[Tuple[int, float]]] = {}
    for (req, attempt), delta in world.backoff.items():
        by_req.setdefault(req, []).append((attempt, delta))
    for req, entries in by_req.items():
        entries.sort()
        prev = 0.0
        for attempt, delta in entries:
            if delta <= 0:
                raise CheckFailure(
                    "H6", f"q{req} attempt {attempt}: non-positive "
                          f"backoff delta {delta}"
                )
            if delta + 1e-9 < prev:
                raise CheckFailure(
                    "H6", f"q{req} attempt {attempt}: backoff delta "
                          f"{delta} shrank below previous {prev}"
                )
            prev = delta


def _check_penalized(world) -> None:
    for i, st in enumerate(world.states):
        if st is None or st.request.repetition_penalty == 1.0:
            continue
        if st.cached_tokens:
            raise CheckFailure(
                "H7", f"q{i} is penalized but reused "
                      f"{st.cached_tokens} prefix-cache tokens — its "
                      f"seen matrix would depend on cache warmth"
            )
        if st.draft_tail:
            raise CheckFailure(
                "H7", f"q{i} is penalized but carries a draft tail"
            )


def check_world(world) -> None:
    """Run the full registry over every replica + the global state.
    Raises :class:`CheckFailure` naming the first violated invariant."""
    for rep in world.replicas:
        sched = rep.engine.scheduler
        rid = rep.replica_id
        if sched.paged:
            _check_pool(world, rid, sched)
        store = world.stores[rid]
        if store is not None:
            _check_store(world, rid, sched, store)
    _check_placement(world)
    _check_backoff(world)
    _check_penalized(world)


def check_event(world, rid: int, plan) -> None:
    """Per-plan checks (things only visible at schedule time)."""
    from ...serving.paging import STAGE_SLOTS

    for w in plan.work:
        if (w.state.request.repetition_penalty != 1.0
                and w.spec_len > 0):
            raise CheckFailure(
                "H7", f"r{rid}: penalized request "
                      f"{w.state.request.request_id} scheduled with a "
                      f"{w.spec_len}-token spec window"
            )
    if len(plan.stage) > STAGE_SLOTS:
        raise CheckFailure(
            "H2", f"r{rid}: plan stages {len(plan.stage)} promotions "
                  f"(> STAGE_SLOTS={STAGE_SLOTS})"
        )
    budget = world.scenario.token_budget
    if plan.total_tokens > budget:
        raise CheckFailure(
            "INTERNAL_ASSERT",
            f"r{rid}: plan schedules {plan.total_tokens} tokens over "
            f"budget {budget}"
        )
