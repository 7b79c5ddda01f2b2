"""Serving requests: lifecycle, sampling state, deterministic RNG.

Parity: DeepSpeed-MII / FastGen's request objects (the continuous-batching
front door). A :class:`Request` is what a client submits; the scheduler
wraps it in a :class:`RequestState` that tracks the status lifecycle

    QUEUED -> PREFILL -> DECODE -> DONE
        \\______________________-> EVICTED   (timeout / queue overflow)

plus the per-request RNG chain. The RNG is DETERMINISTIC: a request's
sampled tokens depend only on (its key, its prompt, the params) — never
on what else shares the batch — which is what makes the slot engine
oracle-testable against N independent single-request ``generate`` calls.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass, field
from enum import Enum
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np


class RequestStatus(str, Enum):
    QUEUED = "queued"      # admitted, waiting for a slot
    PREFILL = "prefill"    # slot assigned, prompt chunks streaming in
    DECODE = "decode"      # prompt cached, generating tokens
    DONE = "done"          # eos or max_new_tokens reached
    EVICTED = "evicted"    # timed out / rejected; retry after backoff


# legal lifecycle edges (EVICTED is reachable from any live state)
_TRANSITIONS = {
    RequestStatus.QUEUED: {RequestStatus.PREFILL, RequestStatus.EVICTED},
    RequestStatus.PREFILL: {RequestStatus.DECODE, RequestStatus.DONE,
                            RequestStatus.EVICTED},
    RequestStatus.DECODE: {RequestStatus.DONE, RequestStatus.EVICTED},
    RequestStatus.DONE: set(),
    RequestStatus.EVICTED: {RequestStatus.QUEUED},  # resubmission
}


def request_rng(request_id, seed: int = 0) -> jax.Array:
    """Deterministic per-request PRNG key: stable across processes and
    independent of submission order (fold the request id's CRC into a
    base key). Requests that want bit-reproducible sampled parity with a
    single-request ``generate(rng=...)`` call pass an explicit key
    instead."""
    h = zlib.crc32(str(request_id).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(seed), h)


@dataclass
class Request:
    """One generation request (the client surface)."""

    request_id: str
    prompt: np.ndarray  # [S] int token ids
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    eos_token_id: int = -1
    rng: Optional[jax.Array] = None  # default: request_rng(request_id)
    session_id: Optional[str] = None  # fleet session affinity: requests
    #   sharing a session_id route to the same replica (their KV prefix
    #   reuse stays local); None = no stickiness. Single-engine serving
    #   ignores it — determinism never depends on placement.

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError(f"request {self.request_id}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.request_id}: max_new_tokens must be >= 1"
            )

    def rng_key(self) -> jax.Array:
        return self.rng if self.rng is not None else request_rng(
            self.request_id
        )


@dataclass
class RequestState:
    """Scheduler-side view of one request: status, slot, progress,
    timing. All timestamps come from the scheduler's injected clock."""

    request: Request
    status: RequestStatus = RequestStatus.QUEUED
    slot: Optional[int] = None
    arrival_t: float = 0.0
    prefill_start_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    prompt_pos: int = 0          # prompt tokens already fed (chunked prefill)
    tokens: List[int] = field(default_factory=list)  # generated tokens
    attempts: int = 0            # submissions (eviction backoff input)
    retry_after: Optional[float] = None  # set on eviction
    evict_reason: Optional[str] = None
    rng: Optional[jax.Array] = None  # CURRENT key (advances as tokens sample)
    # ---- block-paged KV arena (scheduler-owned; empty on the contiguous
    # arena) ------------------------------------------------------------
    pages: List[int] = field(default_factory=list)  # physical page per
    #   logical page, in order; pages[:owned_from] are SHARED (read-only,
    #   prefix-cache refs) — a write into one triggers copy-on-write
    owned_from: int = 0          # first logical page this request owns
    cached_tokens: int = 0       # prompt tokens skipped via the prefix cache
    # ---- window layers' pool (models with window attention layers) ----
    win_pages: List[int] = field(default_factory=list)  # physical page per
    #   logical page from ``win_lo`` on: the pages behind the window were
    #   given back (no query still to come can see their keys)
    win_lo: int = 0              # logical index of win_pages[0]
    # ---- tiered KV (host spill; empty when serving.host_pages == 0) ----
    host_pages: Dict[int, Tuple[int, bool]] = field(default_factory=dict)
    #   logical page index -> (HostPageStore key, owned). While any entry
    #   exists the matching pages[li] is -1 (NULL sink) and the slot is
    #   unschedulable — the prefetcher promotes <= STAGE_SLOTS per tick
    #   until the map drains. owned=True keys are dropped from the store
    #   after promotion; owned=False keys belong to the prefix cache's
    #   host tier (pinned while referenced here, never dropped by us).
    last_planned: int = 0        # scheduler tick this slot last made
    #   progress (demotion victim ordering: coldest slot spills first)
    # ---- speculative decoding (serving/spec.py) -----------------------
    draft_tail: List[int] = field(default_factory=list)  # the previous
    #   verify window's REJECTED targets: stale-but-plausible verifier
    #   predictions that seed the next n-gram draft's no-match fallback
    #   (never emitted; cleared on eviction rollback)

    def __post_init__(self):
        if self.rng is None:
            self.rng = self.request.rng_key()

    # ----------------------------------------------------------- lifecycle
    def transition(self, new: RequestStatus) -> None:
        if new not in _TRANSITIONS[self.status]:
            raise ValueError(
                f"request {self.request.request_id}: illegal transition "
                f"{self.status.value} -> {new.value}"
            )
        self.status = new

    @property
    def prompt_len(self) -> int:
        return int(self.request.prompt.size)

    @property
    def prompt_remaining(self) -> int:
        return self.prompt_len - self.prompt_pos

    @property
    def finished(self) -> bool:
        return self.status in (RequestStatus.DONE, RequestStatus.EVICTED)

    def output(self) -> np.ndarray:
        """[prompt + max_new_tokens] ids, eos-padded past the last real
        token — the same layout single-request ``generate`` returns."""
        req = self.request
        fill = req.eos_token_id if req.eos_token_id >= 0 else 0
        out = np.full(self.prompt_len + req.max_new_tokens, fill, np.int32)
        out[: self.prompt_len] = req.prompt
        gen = np.asarray(self.tokens, np.int32)
        out[self.prompt_len: self.prompt_len + gen.size] = gen
        return out
