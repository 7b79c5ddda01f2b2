"""What the traced serve steps held (``benchmarks/step_kinds.py`` and the six
readers under ``layer_metrics/`` over it, ISSUE 54) on a hand-built trace
whose counts and times are known by construction: a decode-only run, a run
with chunks, one step that starts from idle and one fold whose dispatch lies
before the trace began; on a program without the arguments (the parent); on
the manifest; and on the rehearsal's own trace of the open-loop cell."""

import copy
import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import span_trace, step_kinds
from benchmarks import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LAYER = "serve step: the one [max_slots, token_budget] program"
CLOSED = ("mixtral8x7b-longdoc", "mellum2-12b-mixedlen", "deepseekv32-longctx",
          "minicpm-sala-longctx128k", "ling3flash-reason16",
          "brumby14b-reason16", "glm53flash-longreason8")
OPEN = ("mixtral8x7b-chat",)
# reader -> (unit, better, hand-worked value on the fixture, has .latency)
READERS = {
    "step_ms": ("ms", "lower", 24.0, True),
    "tokens_per_step": ("tokens/step", "higher", 9.0, True),
    "chunk_steps_pct": ("%", "higher", 50.0, True),
    "computed_rows_real_pct": ("%", "higher", 56.25, True),
    "context_tokens_per_slot": ("tokens/slot", "higher", 83.0, False),
    "first_traced_step": ("step", "higher", 5.0, False),
}
LATENCY = [name + ".latency" for name, r in READERS.items() if r[3]]


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def ctx_of(trace):
    return SimpleNamespace(reduced=T.reduce_trace(trace), full_trace=trace,
                           root=ROOT)


def edited(trace, keep=lambda ev: True, stats=lambda ev: ev.stats):
    """The trace with some host events dropped or their arguments changed."""
    out = copy.deepcopy(trace)
    for name, events in out[T.HOST_PLANE].items():
        out[T.HOST_PLANE][name] = [
            T.Event(e.name, e.start, e.dur, dict(stats(e)))
            for e in events if keep(e)]
    return out


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "step_kinds.textproto")) as f:
        return T.load_text_proto(f.read())


@pytest.mark.parametrize("name", list(READERS))
def test_a_reader_returns_the_number_built_in(trace, name):
    assert read(name, ctx_of(trace)) == pytest.approx(READERS[name][2])


def test_every_fold_is_a_step_and_only_a_queued_one_is_timed(trace):
    found = step_kinds.steps(ctx_of(trace))
    assert [s.n for s in found] == list(range(5, 13))  # 13 was never folded
    # the fold whose dispatch lies before the trace counts from its own
    # arguments, and has no step before it to be timed from
    assert (found[0].scheduled_tokens, found[0].decode_slots,
            found[0].context_tokens, found[0].ms) == (4, 4, 400, None)
    assert all(s.dense_rows == 16 for s in found)
    assert [s.ms for s in found[1:]] == pytest.approx(
        [20, 20, 22, 30, 32, 92, 24])
    # 6: the turn that dispatched it was not traced; 11 starts from idle
    assert [s.n for s in found if s.queued] == [7, 8, 9, 10, 12]
    assert step_kinds.times_ms(found) == pytest.approx([20, 22, 30, 32, 24])
    kinds = step_kinds.by_kind(found)
    assert kinds["decode-only"] == dict(
        steps=4, timed=2, median_ms=pytest.approx(21.0),
        longest_ms=pytest.approx(22.0), tokens_per_step=3.75,
        context_per_slot=pytest.approx(1521 / 15))
    assert kinds["with a chunk"] == dict(
        steps=4, timed=3, median_ms=pytest.approx(30.0),
        longest_ms=pytest.approx(32.0), tokens_per_step=14.25,
        context_per_slot=pytest.approx(60.0))


def test_a_wait_that_did_not_block_says_the_device_was_not_handed_on(trace):
    """Turn C's ``serve/device(7)`` returned at once (0.05 ms): step 7 had
    ended before the host looked, so step 8 did not follow it at once."""
    short = edited(trace)
    for e in short[T.HOST_PLANE]["python"]:
        if e.name == "serve/device" and e.stats["step"] == 7:
            e.start, e.dur = e.end - 0.05e6, 0.05e6
    found = step_kinds.steps(ctx_of(short))
    assert [s.n for s in found if s.queued] == [7, 9, 10, 12]


def test_a_tail_without_a_chunk_reads_zero_and_not_nothing(trace):
    ctx = ctx_of(edited(trace, keep=lambda e: e.end <= 80e6))
    assert [s.n for s in step_kinds.steps(ctx)] == [5, 6, 7, 8]
    assert read("chunk_steps_pct", ctx) == 0.0
    assert read("step_ms", ctx) == pytest.approx(21.0)
    assert read("computed_rows_real_pct", ctx) == pytest.approx(
        100.0 * 15 / 64)
    assert read("first_traced_step", ctx) == 5


def test_a_serial_engine_or_an_open_loop_that_never_queues_is_timed_fold_to_fold(
        trace):
    """No turn says ``overlapped=1``: no step qualifies, and the times are
    every interval of consecutive folds, the one across the idle stretch
    among them (the median stands it)."""
    serial = edited(trace, stats=lambda e: {**e.stats, "overlapped": 0}
                    if e.name == "serve/step" else e.stats)
    found = step_kinds.steps(ctx_of(serial))
    assert not any(s.queued for s in found)
    assert step_kinds.times_ms(found) == pytest.approx(
        [20, 20, 22, 30, 32, 92, 24])
    assert read("step_ms", ctx_of(serial)) == pytest.approx(24.0)
    # one fold alone: counts, and no time
    one = ctx_of(edited(trace, keep=lambda e: e.end <= 11e6))
    assert read("tokens_per_step", one) == 4.0
    assert read("step_ms", one) is None
    assert read("computed_rows_real_pct", one) is None  # no device_step


def test_a_program_without_the_arguments_reads_nothing_and_does_not_raise(
        trace):
    """The parent of PR 54 under the benchmark as PR 54 leaves it: the same
    spans, ``step=`` on them and ``dense_rows=`` on the device step, and
    nothing that says what a step held."""
    parent = edited(trace, stats=lambda e: {
        k: v for k, v in e.stats.items() if k not in step_kinds.COUNTS[1:]
        and (k != "scheduled_tokens" or e.name == "serve/step")})
    ctx = ctx_of(parent)
    assert span_trace.named(span_trace.program_spans(ctx), ["serve/device"])
    assert step_kinds.steps(ctx) == []
    for name in READERS:
        assert read(name, ctx) is None, name
    assert "no traced serve/device" in step_kinds.describe(parent)
    # a trace with no span of the program at all, and no trace
    bare = ctx_of({T.HOST_PLANE: {"python": [
        T.Event("bench/engine.step", 0, 100e3, {})]}})
    untraced = SimpleNamespace(reduced=None, root=ROOT)
    for name in READERS:
        assert read(name, bare) is None, name
        assert read(name, untraced) is None, name


def test_the_module_prints_the_range_and_the_two_kinds_apart(trace):
    text = step_kinds.describe(trace)
    assert "steps 5..12: 8 traced, 5 queued" in text and "dense_rows 16" in text
    rows = {line.split("  ")[1].strip(): line.split()
            for line in text.splitlines()[2:4]}
    assert rows["decode-only"][-6:] == ["4", "2", "21.000", "22.000", "3.75",
                                        "101.4"]
    assert rows["with a chunk"][-6:] == ["4", "3", "30.000", "32.000",
                                         "14.25", "60.0"]
    # beside the benchmark's own span round the turn, and the tail's rate:
    # 68 tokens folded after the first fold's end, over 240 ms
    assert ("step_ms 24.000 (mean 25.600), median bench/engine.step 24.500 ms "
            "over 8") in text
    assert "tokens/s 283.3 (folds 6..12 over 0.240 s)" in text
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.step_kinds",
         os.path.join(HERE, "no_such_trace")],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "no *.xplane.pb" in p.stderr


def test_the_manifest_lists_the_ten_entries_appended_with_no_tput_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    from benchmarks import run as bench_run

    names = [m["name"] for m in manifest["per_layer"]]
    mine = [m for m in manifest["per_layer"]
            if m["name"].split(".")[0] in READERS]
    assert len(mine) == 10 and len(names) == len(set(names))
    # appended: nothing of an earlier PR's comes after the first of them (a
    # later PR appends after these: no LAST position is asserted)
    at = names.index(mine[0]["name"])
    assert at >= 54 and [m["name"] for m in manifest["per_layer"][at:at + 10]
                         ] == [m["name"] for m in mine]
    assert not any(m["name"].endswith(".tput") for m in mine)
    assert sum(n.endswith(".tput") for n in names) == 7
    e2e = {x["name"]: x for x in manifest["end_to_end"]}
    assert tuple(e2e["serve_tokens_per_s"]["workloads"][:7]) == CLOSED
    by_name = {m["name"]: m for m in mine}
    assert sorted(by_name) == sorted([*READERS, *LATENCY])
    for name, m in by_name.items():
        stem = name.split(".")[0]
        unit, better, _, _ = READERS[stem]
        assert os.path.basename(bench_run.reader_path(name)) == stem + ".py"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            unit, better, "program_span", LAYER)
        if name.endswith(".latency"):
            assert m["moves"] == "itl_p95_ms"
            assert tuple(m["workloads"]) == OPEN
        else:
            assert m["moves"] == "serve_tokens_per_s"
            # in the order serve_tokens_per_s' own list has
            assert tuple(m["workloads"][:7]) == CLOSED
            assert m["workloads"] == e2e["serve_tokens_per_s"]["workloads"][
                :len(m["workloads"])]
    assert len(json.dumps(manifest)) < 64 * 1024


def test_the_rehearsal_of_the_open_loop_cell_reads_the_four_latency_names(
        tmp_path):
    """The readers need no device: the rehearsal's own profile of the tiny
    run holds the spans with their arguments (names, never numbers). Run
    from a checkout of links of its own: ``run.py`` keeps its trace under
    the checkout it lies in (``.bench_out/trace``, removed at every start),
    and the traced rehearsals of other test files, which other workers run
    at the same time, share the repo's."""
    for name in ("benchmarks", "deepspeed_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", "mixtral8x7b-chat", "--seed", "3000000019",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert os.path.isdir(tmp_path / ".bench_out" / "trace")
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed"
    assert set(LATENCY) <= set(last["metric_names"])
    assert not any(n.split(".")[0] in READERS and "." not in n
                   for n in last["metric_names"])  # the bare ones are not its
