"""loadgen.py: determinism in --seed, the same work in the same order for
every seed, and latency taken from the instant a request was due."""

import json
import os

import numpy as np
import pytest

from benchmarks import loadgen as L

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mix(name):
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


BIG = 3_000_000_019  # the driver's seeds exceed 2**31


def test_train_batches_are_deterministic_in_the_seed():
    m = mix("pretrain-2k")
    a = L.train_batches(m, BIG, 4, 256, 1000, eos_id=2)
    b = L.train_batches(m, BIG, 4, 256, 1000, eos_id=2)
    c = L.train_batches(m, BIG + 1, 4, 256, 1000, eos_id=2)
    first = next(a)
    assert first.shape == (4, 256) and first.dtype == np.int32
    np.testing.assert_array_equal(first, next(b))
    np.testing.assert_array_equal(next(a), next(b))
    assert not np.array_equal(first, next(c))
    assert first.min() >= 2 and first.max() < 1000
    assert (first == 2).any()          # documents end in an EOS
    # Zipf: the most frequent token id is the first after the EOS
    ids, counts = np.unique(first[first != 2], return_counts=True)
    assert ids[counts.argmax()] == 3


def test_every_seed_sends_the_same_lengths_at_the_same_times_in_one_order():
    # --seed draws the token ids (and the weights) only: a tail percentile
    # does not depend on where a long prompt happens to land
    m = mix("chat")
    a = L.open_loop_schedule(m, BIG, 30.0, 32000)
    b = L.open_loop_schedule(m, BIG, 30.0, 32000)
    c = L.open_loop_schedule(m, 7, 30.0, 32000)
    work = lambda sch: [(s.due_s, len(s.prompt), s.new_tokens) for s in sch]
    assert work(a) == work(b) == work(c)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    n = len(a)
    assert abs(n - m["rate_per_s"] * 30) < 4 * (m["rate_per_s"] * 30) ** 0.5
    p = m["prompt"]
    assert all(p["min"] <= len(s.prompt) <= p["max"] for s in a)
    assert all(0 <= s.prompt.min() and s.prompt.max() < 32000 for s in a)
    # a longer window sends the same requests first
    longer = L.open_loop_schedule(m, BIG, 40.0, 32000)
    assert [s.due_s for s in longer[:n]] == [s.due_s for s in a]


class FakeEngine:
    """One token for every live request per step; steps take ``step_s`` on a
    fake clock, the ``stall_at``-th one takes ``stall_s``."""

    class Handle:
        def __init__(self, spec, now):
            self.spec, self.tokens, self.status = spec, [], "queued"
            self.prefill_start_t = now

    def __init__(self, step_s=0.01, stall_at=None, stall_s=0.0):
        self.now, self.step_s = 0.0, step_s
        self.stall_at, self.stall_s, self.n = stall_at, stall_s, 0
        self.live = []

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += max(s, 1e-4)

    def submit(self, spec):
        h = self.Handle(spec, self.now)
        self.live.append(h)
        return h

    def step(self):
        self.now += self.stall_s if self.n == self.stall_at else self.step_s
        self.n += 1
        for h in self.live:
            h.tokens.append(0)
            if len(h.tokens) >= h.spec.new_tokens:
                h.status = "done"
        self.live = [h for h in self.live if h.status != "done"]


def specs(dues, new_tokens=3):
    return [L.RequestSpec(f"r{i}", d, np.zeros(4, np.int32), new_tokens)
            for i, d in enumerate(dues)]


def test_open_loop_ttft_counts_from_the_due_time_through_a_stall():
    # r0 arrives at 0; its second step stalls 2 s. r1 was due at 0.5 s, in
    # the middle of the stall: the loop submits it 1.5 s late, and its TTFT
    # has to show the stall, not the 10 ms its own first step took.
    eng = FakeEngine(step_s=0.01, stall_at=1, stall_s=2.0)
    res = L.run_open_loop(eng.submit, eng.step, specs([0.0, 0.5]), 5.0, 1.0,
                          clock=eng.clock, sleep=eng.sleep)
    r0, r1 = res.tracks
    assert r0.ttft == pytest.approx(0.01)
    assert r1.submit_t == pytest.approx(2.01)          # when the loop got to it
    assert res.late_s[1] == pytest.approx(1.51)
    assert r1.ttft == pytest.approx(2.02 - 0.5)        # from when it was DUE
    assert r1.handle.prefill_start_t - r1.due_t == pytest.approx(1.51)
    ttft = L.ttft_values(res, grace_s=1.0)
    assert max(ttft) == pytest.approx(1.52)
    # the stalled gap is r0's second token
    assert max(L.itl_values(res)) == pytest.approx(2.0)
    assert res.attempted() == 2 and res.failed() == 0


def test_open_loop_unfinished_and_evicted_requests_fail_as_the_worst():
    eng = FakeEngine(step_s=0.5)
    sch = specs([0.0, 0.2], new_tokens=100)            # cannot finish
    res = L.run_open_loop(eng.submit, eng.step, sch, 2.0, 1.0,
                          clock=eng.clock, sleep=eng.sleep)
    assert res.attempted() == 2 and res.failed() == 2
    assert L.ttft_values(res, 1.0) == [pytest.approx(3.0), pytest.approx(2.8)]
    eng = FakeEngine()
    res = L.run_open_loop(eng.submit, eng.step, specs([0.0]), 1.0, 1.0,
                          clock=eng.clock, sleep=eng.sleep)
    assert res.failed() == 0
    h = L.Track(specs([0.0])[0], FakeEngine.Handle(None, 0), 0.0, 0.0)
    h.handle.status = "evicted"
    L._stamp([h], 0.1)
    assert h.done and h.failed


def test_requests_due_after_the_window_are_not_sent():
    eng = FakeEngine()
    sch = L.open_loop_schedule(mix("chat"), 1, 3.0, 100)
    assert all(s.due_s < 3.0 for s in sch)
    short = [L.RequestSpec(s.rid, s.due_s, s.prompt, 2) for s in sch]
    res = L.run_open_loop(eng.submit, eng.step, short, 3.0, 1.0,
                          clock=eng.clock, sleep=eng.sleep)
    assert res.attempted() == len(sch) and res.failed() == 0


def test_closed_loop_keeps_clients_in_flight_and_varies_later_laps():
    eng = FakeEngine(step_s=0.01)
    replay = [L.RequestSpec(f"q{i}", 0.0, np.full(4, i, np.int32), 5)
              for i in range(3)]
    seen = []

    def submit(spec):
        assert len(eng.live) < 2
        seen.append(spec)
        return eng.submit(spec)

    res = L.run_closed_loop(submit, eng.step, replay, 2, 50, 1.0, 1.0,
                            clock=eng.clock)
    # 2 clients x 5 steps a request x 10 ms: 40 requests in the 1 s window
    assert res.attempted() == pytest.approx(40, abs=2) and res.failed() == 0
    assert [s.rid for s in seen[:4]] == ["q0.0", "q1.0", "q2.0", "q0.1"]
    assert not np.array_equal(seen[0].prompt, seen[3].prompt)  # no replayed prefix
    assert len(seen[0].prompt) == len(seen[3].prompt)


@pytest.mark.parametrize("name", ["chat", "longdoc"])
def test_a_mix_has_no_key_the_generator_does_not_read(name):
    read = {"kind", "why", "rate_per_s", "knee_per_s", "schedule_seed",
            "clients", "replay_requests", "prompt", "answer", "grace_s",
            "trace_seconds", "correctness", "rehearse"}
    assert set(mix(name)) <= read


def test_replay_set_is_fixed_work_in_a_fixed_order():
    m = mix("longdoc")
    a, b = L.replay_set(m, BIG, 32000), L.replay_set(m, 5, 32000)
    assert len(a) == m["replay_requests"]
    assert [(len(s.prompt), s.new_tokens) for s in a] == \
        [(len(s.prompt), s.new_tokens) for s in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert all(m["prompt"]["min"] <= len(s.prompt) <= m["prompt"]["max"] for s in a)
    assert all(m["answer"]["min"] <= s.new_tokens <= m["answer"]["max"] for s in a)


def test_feeder_hands_out_the_stream_in_order_and_stops():
    f = L.Feeder(iter(np.arange(5)), depth=2)
    got = [f.next()[0] for _ in range(5)]
    f.close()
    assert got == [0, 1, 2, 3, 4]
    assert L.percentile([1, 2, 3, 4], 50) == 2.5
    assert np.isnan(L.percentile([], 95))
