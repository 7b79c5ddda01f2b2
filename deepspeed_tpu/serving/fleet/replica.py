"""Replica handle: one ServingEngine behind the fleet router.

A thin identity + load wrapper — the engine keeps owning its scheduler,
arena and metrics; the handle adds the fleet-level facts the router
needs (role, load, step timing) without reaching into engine internals
from routing code.
"""

from __future__ import annotations

import time
from typing import List, Tuple

from ..engine import ServingEngine
from ..request import RequestState, RequestStatus

ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
ROLE_MIXED = "mixed"


class ReplicaHandle:
    def __init__(self, replica_id: int, engine: ServingEngine,
                 role: str = ROLE_MIXED):
        if role not in (ROLE_PREFILL, ROLE_DECODE, ROLE_MIXED):
            raise ValueError(f"unknown replica role {role!r}")
        self.replica_id = int(replica_id)
        self.engine = engine
        self.role = role

    # ----------------------------------------------------------- load
    @property
    def queue_depth(self) -> int:
        return len(self.engine.scheduler.queue)

    @property
    def active(self) -> int:
        return self.engine.scheduler.active_count

    @property
    def load(self) -> int:
        """Queued + in-flight: the router's least-loaded ordering key."""
        return self.queue_depth + self.active

    @property
    def has_free_slot(self) -> bool:
        return bool(self.engine.scheduler._free)

    @property
    def has_work(self) -> bool:
        return self.engine.scheduler.has_work

    # ------------------------------------------------------- stepping
    def step(self) -> Tuple[List[RequestState], float]:
        """One engine step; returns (finished, wall_seconds). The wall
        time feeds the bench's parallel-replica virtual clock (replicas
        are data-parallel — a real deployment runs them concurrently, so
        a fleet tick costs max over replicas, not the sum)."""
        t0 = time.perf_counter()
        finished = self.engine.step()
        return finished, time.perf_counter() - t0

    # ------------------------------------------------------- handoff
    def decode_candidates(self) -> List[RequestState]:
        """In-flight requests this PREFILL replica has finished
        prefilling (status DECODE: the final prompt feed sampled their
        first token) that are eligible to move to a decode replica.
        Requests with a repetition penalty stay: their ``seen`` matrix is
        rebuilt from FED tokens only, which a handoff would truncate —
        correctness over placement, the same rule as the prefix-cache and
        spec bypasses."""
        out = []
        for st in self.engine.scheduler.live:
            if st.status is not RequestStatus.DECODE:
                continue
            if st.request.repetition_penalty != 1.0:
                continue
            out.append(st)
        return out

    def __repr__(self) -> str:
        return (f"ReplicaHandle(r{self.replica_id}, {self.role}, "
                f"load={self.load})")
