"""One span site, two sinks (ISSUE 39): the engines' phase spans reach the
profiler's host plane in every profiled run, and the registry as well when
one is configured; with neither, nothing is recorded and no registry
exists.

The profile is read back with ``jax.profiler.ProfileData`` (nothing of the
benchmark is imported here): a ``TraceAnnotation`` is an event of the
``/host:CPU`` plane whose stats are its arguments.
"""

import collections
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import llama
from deepspeed_tpu.profiling import steptrace
from deepspeed_tpu.serving import Request, ServingEngine

# what tools/trace_report.py --validate holds the registry to
COVERAGE_TOL, COVERAGE_ABS_NS = 0.10, 300e3

SERVE_CHILDREN = ("serve/plan", "serve/dispatch", "serve/device",
                  "serve/complete")
TRAIN_CHILDREN = ("train/batch_prep", "train/dispatch", "train/commit")


@pytest.fixture(autouse=True)
def _fresh_registry():
    steptrace.reset()
    yield
    steptrace.reset()


class profiled:
    """``with profiled(tmp) as p: ...`` then ``p.events`` holds the
    program's spans as (name, start ns, end ns, args), in time order."""

    def __init__(self, path):
        self.dir = str(path)
        self.events = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only
        # a collection between a child's close and its step's (0.1 s in a
        # worker that ran the whole file) is no hole in the cover
        gc.collect()
        gc.disable()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        gc.enable()
        pb = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True))[-1]
        for plane in jax.profiler.ProfileData.from_file(pb).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("serve/", "train/")):
                        self.events.append((
                            e.name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns),
                            dict(e.stats)))
        self.events.sort(key=lambda e: (e[1], -e[2]))
        return False

    def named(self, name):
        return [e for e in self.events if e[0] == name]

    def inside(self, parent, names):
        return [e for e in self.events
                if e[0] in names and parent[1] <= e[1] and e[2] <= parent[2]]


def assert_children_cover(prof, step_name, children):
    steps = prof.named(step_name)
    assert steps
    in_some_step = 0
    for s in steps:
        kids = prof.inside(s, children)
        assert kids, f"{step_name} {s[3]} holds no child"
        in_some_step += len(kids)
        covered = sum(k[2] - k[1] for k in kids)
        assert abs(covered - (s[2] - s[1])) <= (
            COVERAGE_TOL * (s[2] - s[1]) + COVERAGE_ABS_NS), (s, kids)
    # every child lies inside a step: none is an orphan
    assert in_some_step == sum(len(prof.named(c)) for c in children)


def tiny_server(steptrace_section=None, order=None):
    model = llama("llama-tiny", vocab_size=128, max_seq_len=64,
                  hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
                  intermediate_size=64)
    eng = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, max_tokens=64, rng=jax.random.PRNGKey(1))
    srv = ServingEngine(engine=eng, serving={
        "max_slots": 2, "token_budget": 8, "max_tokens": 64,
    }, steptrace=steptrace_section)
    if order is not None:
        srv.step_order = order  # the tests' oracle (test_serving_overlap)
    return srv


def replay(srv, n=2, tag="r"):
    r = np.random.RandomState(0)
    for i in range(n):
        srv.submit(Request(request_id=f"{tag}{i}",
                           prompt=r.randint(0, 128, size=(9,)),
                           max_new_tokens=3))
    srv.run_until_idle()


def tiny_trainer(tmp_path, devices8, traced):
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.models import gpt2

    comm.destroy_process_group()
    config = {"train_batch_size": 8,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
    if traced:
        config["steptrace"] = {"enabled": True}
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=64, max_seq_len=16), config=config)
    data = {"input_ids": np.random.RandomState(0).randint(0, 64,
                                                          size=(8, 16))}
    return engine, data


# ---------------------------------------------------------------------------
# the entry itself
# ---------------------------------------------------------------------------
def test_phase_records_into_a_registry_with_late_arguments():
    reg = steptrace.MetricsRegistry()
    with steptrace.Phase(reg, "serve/step", "serve", step=3) as step:
        sp = steptrace.Phase(reg, "serve/dispatch", "serve", step=3)
        sp.annotate(traced=0)
        sp.end()
        assert sp.t0 is not None and sp.t1 >= sp.t0
        steptrace.Phase(reg, "serve/plan", "serve").cancel()
        step.annotate(scheduled_tokens=8)
    names = [s["name"] for s in reg.spans]
    assert names == ["serve/dispatch", "serve/step"]  # the cancelled one is gone
    assert reg.spans[0]["args"] == {"step": 3, "traced": 0}
    assert reg.spans[1]["args"] == {"step": 3, "scheduled_tokens": 8}
    assert reg.spans[1]["cat"] == "serve"
    d, s = reg.spans
    assert s["t0"] <= d["t0"] and d["t1"] <= s["t1"]


# ---------------------------------------------------------------------------
# serving: the turn on the profiler's clock, no registry
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("order", ["overlapped", "serial"])
def test_serving_turn_reaches_the_profile_without_a_registry(tmp_path, order):
    srv = tiny_server(order=order)
    assert srv.step_order == order and srv.tracer is None
    replay(srv, n=1, tag="warm")  # compile outside the profile
    before = srv.metrics.steps
    with profiled(tmp_path) as prof:
        assert srv.step() == []  # an idle tick, told before serve/step opens
        replay(srv)
        srv.step()               # and another
    ran = srv.metrics.steps - before
    assert ran >= 4
    assert steptrace.get_registry() is None

    assert_children_cover(prof, "serve/step", SERVE_CHILDREN)
    steps = prof.named("serve/step")
    # a turn dispatched or folded (or both): an idle tick wrote nothing
    assert all(s[3]["dispatched"] or s[3]["folded"] for s in steps)
    assert len(steps) == ran + (order == "overlapped")
    for name in SERVE_CHILDREN[1:]:
        numbers = [e[3]["step"] for e in prof.named(name)]
        assert numbers == list(range(before + 1, before + ran + 1)), name
    assert len(prof.named("serve/device_step")) == ran
    # serve/step says which step it dispatched and which it folded, and
    # its children carry those numbers
    turn_of = {}
    for i, s in enumerate(steps):
        a = s[3]
        for kid in prof.inside(s, ("serve/dispatch",)):
            assert kid[3]["step"] == a["dispatched"]
            turn_of["dispatch", kid[3]["step"]] = i
        for kid in prof.inside(s, ("serve/device", "serve/complete")):
            assert kid[3]["step"] == a["folded"]
            turn_of[kid[0], kid[3]["step"]] = i
        if a["dispatched"]:
            assert a["scheduled_tokens"] > 0
    # pairing a step's dispatch with its fold: one turn later in the
    # overlapped order, in the same turn in the serial one
    lag = int(order == "overlapped")
    for n in range(before + 1, before + ran + 1):
        assert turn_of["serve/complete", n] == turn_of["dispatch", n] + lag
        assert turn_of["serve/device", n] == turn_of["serve/complete", n]
    if order == "overlapped":
        assert steps[0][3]["overlapped"] == 0 and steps[0][3]["folded"] == 0
        assert all(s[3]["overlapped"] == 1 for s in steps[1:-1])
        assert steps[-1][3]["dispatched"] == 0  # the last turn only folds
    else:
        assert all(s[3]["overlapped"] == 0 for s in steps)


def test_serving_both_sinks_hold_the_same_spans(tmp_path):
    srv = tiny_server({"enabled": True})
    reg = srv.tracer
    assert reg is steptrace.get_registry() and reg is not None
    replay(srv, n=1, tag="warm")
    mark = len(reg.spans)
    with profiled(tmp_path) as prof:
        srv.step()
        replay(srv)
    in_registry = collections.Counter(
        s["name"] for s in reg.spans[mark:] if s["cat"] == "serve")
    in_profile = collections.Counter(
        e[0] for e in prof.events if e[0] != "serve/device_step")
    assert in_registry == in_profile and in_registry["serve/step"] >= 4
    # and the same arguments, span for span
    for name in ("serve/step", *SERVE_CHILDREN[1:]):
        assert [s["args"] for s in reg.spans[mark:] if s["name"] == name] \
            == [e[3] for e in prof.named(name)], name


HELD = ("scheduled_tokens", "prompt_rows", "prompt_slots", "decode_slots",
        "context_tokens")


@pytest.mark.parametrize("order", ["overlapped", "serial"])
def test_a_steps_dispatch_and_fold_say_what_it_held_in_both_sinks(
        tmp_path, order):
    """ISSUE 54: prompts longer than the budget, answers longer than one
    step. ``serve/dispatch(n)`` and ``serve/device(n)`` carry the same
    counts of step n, in the profile and in the registry, and the counters
    booked at the fold add up to them."""
    srv = tiny_server({"enabled": True}, order=order)
    reg, m = srv.tracer, srv.metrics
    replay(srv, n=1, tag="warm")
    mark, before = len(reg.spans), m.snapshot()
    lengths, news = [13, 20, 9], [4, 3, 5]
    r = np.random.RandomState(3)
    with profiled(tmp_path) as prof:
        for i, (n, new) in enumerate(zip(lengths, news)):
            srv.submit(Request(request_id=f"h{i}",
                               prompt=r.randint(0, 128, size=(n,)),
                               max_new_tokens=new))
        srv.run_until_idle()
    after = m.snapshot()
    ran = after["steps"] - before["steps"]
    first = before["steps"] + 1

    def held(events):
        return {e[3]["step"]: {k: e[3][k] for k in HELD} for e in events}

    dispatched = held(prof.named("serve/dispatch"))
    folded = held(prof.named("serve/device"))
    assert dispatched == folded
    assert sorted(folded) == list(range(first, first + ran))
    for name, mine in (("serve/dispatch", dispatched), ("serve/device", folded)):
        assert mine == {s["args"]["step"]: {k: s["args"][k] for k in HELD}
                        for s in reg.spans[mark:] if s["name"] == name}
    for s in prof.named("serve/step"):
        if s[3]["dispatched"]:  # serve/step keeps its own name for the size
            assert s[3]["scheduled_tokens"] == (
                dispatched[s[3]["dispatched"]]["scheduled_tokens"])
    for n, h in folded.items():
        # no spec here: a decode slot feeds one row
        assert h["prompt_rows"] + h["decode_slots"] == h["scheduled_tokens"]
        assert 0 < h["scheduled_tokens"] <= srv.token_budget
        assert (h["prompt_rows"] > 0) == (h["prompt_slots"] > 0)
        assert 0 < h["prompt_slots"] + h["decode_slots"] <= srv.max_slots
        assert h["context_tokens"] >= h["scheduled_tokens"]
    # the first step is h0's first chunk and nothing else: the whole budget
    assert folded[first] == dict(scheduled_tokens=8, prompt_rows=8,
                                 prompt_slots=1, decode_slots=0,
                                 context_tokens=8)
    # the last is the longest answer's last decode row, alone, with the
    # prompt and all its tokens but the one it samples behind it
    assert folded[first + ran - 1] == dict(
        scheduled_tokens=1, prompt_rows=0, prompt_slots=0, decode_slots=1,
        context_tokens=9 + 5 - 1)

    def grew(key):
        return after[key] - before[key]

    assert grew("prompt_tokens") == sum(lengths) == sum(
        h["prompt_rows"] for h in folded.values())
    # a request's first token comes out of its final chunk: one fed decode
    # row for each further one
    assert grew("decode_tokens") == sum(n - 1 for n in news) == sum(
        h["decode_slots"] for h in folded.values())
    assert grew("scheduled_tokens") == (
        grew("prompt_tokens") + grew("decode_tokens"))
    assert grew("chunk_steps") == sum(
        h["prompt_rows"] > 0 for h in folded.values())
    assert 0 < grew("chunk_steps") < ran and after["discarded_rows"] == 0


def test_a_turn_that_could_plan_nothing_is_no_step_in_either_sink(
        tmp_path, monkeypatch):
    """The scheduler holds a request and plans none of it, with nothing in
    flight: known only after ``plan()``, inside the annotation. The
    registry drops the turn; the profile keeps a ``serve/step`` that says
    it dispatched and folded nothing. (Admission is eager and a starved
    pool evicts until something runs, so the scheduler is made to say so.)"""
    srv = tiny_server({"enabled": True})
    srv.submit(Request(request_id="held", prompt=np.arange(9),
                       max_new_tokens=2))
    assert srv.scheduler.has_work
    monkeypatch.setattr(srv.scheduler, "plan", lambda ahead_of=None: None)
    with profiled(tmp_path) as prof:
        assert srv.step() == []
    assert srv.metrics.steps == 0 and srv._dispatched == 0
    assert [s["name"] for s in srv.tracer.spans
            if s["name"] in ("serve/step", *SERVE_CHILDREN)] == []
    (step,) = prof.named("serve/step")
    assert step[3]["dispatched"] == 0 and step[3]["folded"] == 0
    assert [e[0] for e in prof.inside(step, SERVE_CHILDREN)] == ["serve/plan"]


def test_a_dispatch_that_raises_closes_its_spans_and_takes_no_number(
        tmp_path):
    srv = tiny_server({"enabled": True})
    replay(srv, n=1, tag="warm")
    taken, mark = srv._dispatched, len(srv.tracer.spans)

    def refuses(*args):
        raise RuntimeError("out of memory")

    srv._step_exec = refuses
    srv.submit(Request(request_id="x", prompt=np.arange(9),
                       max_new_tokens=3))
    with profiled(tmp_path) as prof:
        with pytest.raises(RuntimeError, match="out of memory"):
            srv.step()
        # the thread's annotations are balanced: one opened now is no
        # child of a span left open
        with steptrace.Phase(None, "serve/after"):
            pass
    assert srv._dispatched == taken
    for sink in ([s["name"] for s in srv.tracer.spans[mark:]
                  if s["name"] in ("serve/step", *SERVE_CHILDREN)],
                 [e[0] for e in prof.events
                  if e[0] in ("serve/step", *SERVE_CHILDREN)]):
        assert sorted(sink) == ["serve/dispatch", "serve/plan", "serve/step"]
    (step,), (after,) = prof.named("serve/step"), prof.named("serve/after")
    assert step[3]["dispatched"] == taken + 1
    assert len(prof.inside(step, SERVE_CHILDREN)) == 2
    assert after[1] >= step[2]


def test_with_neither_sink_a_replay_and_a_step_record_nothing(
        tmp_path, devices8):
    srv = tiny_server()
    replay(srv)
    assert srv.tracer is None and srv.metrics.tracer is None
    engine, data = tiny_trainer(tmp_path, devices8, traced=False)
    engine.train_batch(batch=data)
    assert engine.tracer is None
    assert steptrace.get_registry() is None


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def test_training_steps_reach_the_profile_without_a_registry(
        tmp_path, devices8):
    engine, data = tiny_trainer(tmp_path, devices8, traced=False)
    engine.train_batch(batch=data)  # compile outside the profile
    with profiled(tmp_path) as prof:
        for _ in range(2):
            engine.train_batch(data_iter=iter([data]))
    assert steptrace.get_registry() is None
    assert_children_cover(prof, "train/step", TRAIN_CHILDREN)
    assert [s[3]["step"] for s in prof.named("train/step")] == [2, 3]
    for name in TRAIN_CHILDREN:
        assert len(prof.named(name)) == 2, name
    # the iterator's pull is a span of its own, before the step opens
    waits = prof.named("train/input_wait")
    assert len(waits) == 2
    assert all(w[2] <= s[1] for w, s in zip(waits, prof.named("train/step")))
    # a fence changes the run: the span that fences exists only with the
    # registry (and then in both sinks: the test below)
    assert prof.named("train/device") == []
    assert all(e[3]["traced"] == 0 for e in prof.named("train/dispatch"))


def test_training_both_sinks_hold_the_same_spans(tmp_path, devices8):
    engine, data = tiny_trainer(tmp_path, devices8, traced=True)
    reg = engine.tracer
    engine.train_batch(batch=data)
    mark = len(reg.spans)
    with profiled(tmp_path) as prof:
        for _ in range(2):
            engine.train_batch(batch=data)
    in_registry = collections.Counter(
        s["name"] for s in reg.spans[mark:] if s["cat"] == "train")
    in_profile = collections.Counter(e[0] for e in prof.events)
    assert in_registry == in_profile
    assert in_registry == {"train/step": 2, "train/batch_prep": 2,
                           "train/dispatch": 2, "train/device": 2,
                           "train/commit": 2}
    assert_children_cover(prof, "train/step",
                          (*TRAIN_CHILDREN, "train/device"))


def test_a_training_dispatch_that_raises_closes_its_spans(tmp_path, devices8):
    engine, data = tiny_trainer(tmp_path, devices8, traced=True)
    engine.train_batch(batch=data)
    mark, steps = len(engine.tracer.spans), engine.global_steps

    def refuses(*args):
        raise RuntimeError("failed to compile")

    engine._jit_train = refuses
    with profiled(tmp_path) as prof:
        with pytest.raises(RuntimeError, match="failed to compile"):
            engine.train_batch(batch=data)
    assert engine.global_steps == steps
    want = ["train/batch_prep", "train/dispatch", "train/step"]
    assert sorted(s["name"] for s in engine.tracer.spans[mark:]
                  if s["cat"] == "train") == want
    assert sorted(e[0] for e in prof.events) == want
    (step,) = prof.named("train/step")
    assert len(prof.inside(step, TRAIN_CHILDREN)) == 2
