"""Traffic: one general generator per kind of loop, driven by a data file.

A traffic mix is ``benchmarks/traffic/<name>.json``: the ``kind`` of loop
(``train_stream``, ``open_loop``, ``closed_loop``) and its parameters. A new
mix is a new data file; nothing here names a mix.

``--seed`` draws the token ids (and, in ``run.py``, the weights) and
nothing else. Lengths, arrival times and their order come from the mix's own
``schedule_seed``, so every seed does the same work in the same order: a tail
percentile does not depend on where a long prompt happens to land.

The drivers time on the real clock and from the moment a request was *due*,
not from when a stalled loop got round to submitting it. They know the
system under test only as ``submit(spec) -> handle`` and ``step()``; a
handle exposes ``tokens`` (list), ``status`` (str) and ``admitted_t``.
"""

from __future__ import annotations

import contextlib
import math
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for any whole-number seed (the driver's exceed 2**31)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def lognormal_lengths(rng, n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """Heavy-tailed lengths, clipped (the draw ``tools/bench_serve.py``
    makes for prompts, with the clip made explicit)."""
    x = rng.lognormal(math.log(median), sigma, size=n)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


# ------------------------------------------------------------ train_stream
class ZipfTokens:
    """Token ids from a Zipf(s) unigram law over a finite vocabulary
    (rank r has probability ~ r**-s; id = rank - 1 + ``first_id``)."""

    def __init__(self, vocab: int, exponent: float, first_id: int = 0):
        ranks = np.arange(1, vocab - first_id + 1, dtype=np.float64)
        self.cdf = np.cumsum(ranks ** -exponent)
        self.cdf /= self.cdf[-1]
        self.first_id = first_id

    def draw(self, rng, n: int) -> np.ndarray:
        return (np.searchsorted(self.cdf, rng.random(n)) + self.first_id
                ).astype(np.int32)


def train_batches(mix: dict, seed: int, batch: int, seq: int, vocab: int,
                  eos_id: int) -> Iterator[np.ndarray]:
    """Endless [batch, seq] int32 batches: documents of log-normal length,
    Zipf tokens, packed end to end with ``eos_id`` between documents (a
    document may continue in the next row, as packed pretraining data
    does)."""
    rng = rng_for(seed, 1)
    doc = mix["documents"]
    zipf = ZipfTokens(vocab, float(doc["zipf_exponent"]), first_id=eos_id + 1)
    need = batch * seq
    while True:
        out = np.empty(need, np.int32)
        filled = 0
        # draw more lengths than the batch can need, in one call
        lens = lognormal_lengths(
            rng, max(8, 4 * need // int(doc["median_tokens"])),
            doc["median_tokens"], doc["sigma"], doc["min_tokens"],
            doc["max_tokens"])
        for n in lens:
            n = int(min(n, need - filled))
            out[filled:filled + n] = zipf.draw(rng, n)
            filled += n
            if filled < need:
                out[filled] = eos_id
                filled += 1
            if filled >= need:
                break
        yield out.reshape(batch, seq)


class Feeder:
    """The input pipeline's stand-in: a thread that keeps ``depth`` batches
    ready. ``next()`` returns (batch, seconds the caller waited)."""

    def __init__(self, batches: Iterator[np.ndarray], depth: int = 2):
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._batches = batches
        self._t = threading.Thread(target=self._run, name="bench-feeder",
                                   daemon=True)
        self._t.start()

    def _run(self) -> None:
        for b in self._batches:
            while not self._stop.is_set():
                try:
                    self._q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if self._stop.is_set():
                return

    def next(self):
        t0 = time.monotonic()
        b = self._q.get()
        return b, time.monotonic() - t0

    def close(self) -> None:
        self._stop.set()
        self._t.join()


# ------------------------------------------------------------------ requests
@dataclass
class RequestSpec:
    rid: str
    due_s: float            # seconds after the window opens (open loop)
    prompt: np.ndarray      # int32 token ids
    new_tokens: int


@dataclass
class Track:
    """One request as the driver saw it; all times on the driver's clock,
    relative to the opening of the window."""

    spec: RequestSpec
    handle: object
    due_t: float
    submit_t: float
    token_t: List[float] = field(default_factory=list)
    done: bool = False
    failed: bool = False

    @property
    def ttft(self) -> Optional[float]:
        return self.token_t[0] - self.due_t if self.token_t else None


def _length_pairs(mix: dict, n: int):
    """The mix's fixed sequence of (prompt, answer) lengths."""
    rng = rng_for(int(mix["schedule_seed"]), 2)
    p, a = mix["prompt"], mix["answer"]
    return np.stack([
        lognormal_lengths(rng, n, p["median"], p["sigma"], p["min"], p["max"]),
        lognormal_lengths(rng, n, a["median"], a["sigma"], a["min"], a["max"]),
    ], axis=1)


def _specs(pairs, dues, seed: int, vocab: int) -> List[RequestSpec]:
    rng = rng_for(seed, 4)
    return [
        RequestSpec(f"r{i}", float(due),
                    rng.integers(0, vocab, size=int(p), dtype=np.int32),
                    int(a))
        for i, ((p, a), due) in enumerate(zip(pairs, dues))
    ]


def open_loop_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                       rate: Optional[float] = None) -> List[RequestSpec]:
    """Poisson arrivals at the mix's fixed ``rate_per_s`` over ``seconds``.
    Arrival times depend on ``schedule_seed`` and the rate alone."""
    rate = float(rate if rate is not None else mix["rate_per_s"])
    rng = rng_for(int(mix["schedule_seed"]), 5)
    n = int(rate * seconds * 1.5) + 16
    dues = np.cumsum(rng.exponential(1.0 / rate, size=n))
    dues = dues[dues < seconds]
    return _specs(_length_pairs(mix, len(dues)), dues, seed, vocab)


def replay_set(mix: dict, seed: int, vocab: int) -> List[RequestSpec]:
    """The closed loop's fixed replay set (``replay_requests`` long; the
    driver wraps round if the window outlasts it)."""
    n = int(mix["replay_requests"])
    return _specs(_length_pairs(mix, n), np.zeros(n), seed, vocab)


# ------------------------------------------------------------------- drivers
@dataclass
class LoopResult:
    tracks: List[Track]
    window_s: float
    t0: float = 0.0     # the driver's clock when the window opened
    steps: int = 0
    step_wall_s: List[float] = field(default_factory=list)
    late_s: List[float] = field(default_factory=list)  # submit - due

    def attempted(self) -> int:
        return len(self.tracks)

    def failed(self) -> int:
        return sum(1 for t in self.tracks if t.failed)


_FAILED = ("evicted",)


def _stamp(tracks: List[Track], now: float) -> None:
    """Give every token that appeared during the last step the step's end
    time; close requests that finished or were evicted."""
    for t in tracks:
        if t.done:
            continue
        n = len(t.handle.tokens)
        if n > len(t.token_t):
            t.token_t.extend([now] * (n - len(t.token_t)))
        status = str(getattr(t.handle.status, "value", t.handle.status))
        if status in _FAILED:
            t.done = t.failed = True
        elif n >= t.spec.new_tokens or status == "done":
            t.done = True


def _null(_name):
    return contextlib.nullcontext()


def _step_and_stamp(step, clock, annotate, res: "LoopResult",
                    live: List[Track]) -> None:
    """One step of the system under the ``bench/engine.step`` span; steps
    begun inside the window are counted, their tokens stamped."""
    with annotate("bench/engine.step"):
        a = clock()
        step()
        b = clock()
    if a - res.t0 < res.window_s:
        res.steps += 1
        res.step_wall_s.append(b - a)
    _stamp(live, b - res.t0)


def run_open_loop(submit: Callable, step: Callable, schedule: List[RequestSpec],
                  seconds: float, grace_s: float, clock=time.monotonic,
                  sleep=time.sleep, annotate=_null,
                  on_tick: Optional[Callable[[float], None]] = None
                  ) -> LoopResult:
    """Send each request when it is due, whatever the system is doing;
    step the system whenever it has work. Requests due inside the window
    are attempted; after it the loop drains them for at most ``grace_s``
    and what is unfinished then has failed."""
    t0 = clock()
    res = LoopResult([], seconds, t0)
    live: List[Track] = []
    i = 0
    while True:
        now = clock() - t0
        if on_tick is not None:
            on_tick(now)
        while i < len(schedule) and schedule[i].due_s <= now:
            spec = schedule[i]
            with annotate("bench/submit"):
                h = submit(spec)
            tr = Track(spec, h, spec.due_s, now)
            res.tracks.append(tr)
            res.late_s.append(now - spec.due_s)
            live.append(tr)
            i += 1
        live = [t for t in live if not t.done]
        if now >= seconds and i >= len(schedule) and not live:
            break
        if now >= seconds + grace_s:
            for t in live:
                t.done = t.failed = True
            break
        if not live:
            nxt = schedule[i].due_s if i < len(schedule) else seconds
            sleep(max(0.0, min(nxt - now, 0.005)))
            continue
        _step_and_stamp(step, clock, annotate, res, live)
    return res


def run_closed_loop(submit: Callable, step: Callable, replay: List[RequestSpec],
                    clients: int, vocab: int, seconds: float, grace_s: float,
                    clock=time.monotonic, annotate=_null,
                    on_tick: Optional[Callable[[float], None]] = None
                    ) -> LoopResult:
    """``clients`` callers, each sending its next request from the replay
    set the moment its last one completes. No new request after the window
    closes; those in flight then get ``grace_s`` to finish."""
    t0 = clock()
    res = LoopResult([], seconds, t0)
    live: List[Track] = []
    nxt = 0
    while True:
        now = clock() - t0
        if on_tick is not None:
            on_tick(now)
        live = [t for t in live if not t.done]
        while now < seconds and len(live) < clients:
            src, lap = replay[nxt % len(replay)], nxt // len(replay)
            # a later lap sends the same lengths with other token ids, so a
            # prefix cache never turns the replay into a different workload
            prompt = src.prompt if lap == 0 else (src.prompt + lap) % vocab
            spec = RequestSpec(f"{src.rid}.{lap}", now, prompt,
                               src.new_tokens)
            nxt += 1
            with annotate("bench/submit"):
                h = submit(spec)
            tr = Track(spec, h, now, now)
            res.tracks.append(tr)
            live.append(tr)
        if not live:
            break
        if now >= seconds + grace_s:
            for t in live:
                t.done = t.failed = True
            break
        _step_and_stamp(step, clock, annotate, res, live)
    return res


# ------------------------------------------------------------------- metrics
def percentile(values, p: float) -> float:
    """Linear-interpolated percentile (numpy's default), NaN if empty."""
    return float(np.percentile(np.asarray(values, np.float64), p)) \
        if len(values) else float("nan")


def ttft_values(res: LoopResult, grace_s: float) -> List[float]:
    """Seconds from due to first token for every attempted request; one
    that failed or never produced a token counts as the worst possible:
    the whole of the time it could have waited."""
    worst_t = res.window_s + grace_s
    return [t.ttft if (t.ttft is not None and not t.failed)
            else worst_t - t.due_t for t in res.tracks]


def itl_values(res: LoopResult) -> List[float]:
    """Gaps between consecutive output tokens of one request, every gap
    whose later token fell inside the window."""
    gaps = []
    for t in res.tracks:
        tt = t.token_t
        gaps.extend(b - a for a, b in zip(tt, tt[1:]) if b <= res.window_s)
    return gaps
