"""The readers of the program's phase spans (``benchmarks/span_trace.py`` and
the four files under ``layer_metrics/`` that use it) on a hand-built trace
whose idle shares are known by construction, on a trace without the spans
(the parent of PR 39), and on the rehearsal's own trace of a serving and a
training cell."""

import importlib.util
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import span_trace
from benchmarks import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ("host_turn_ms_per_step", "idle_in_host_work_pct",
       "idle_in_device_wait_pct", "train_host_ms_per_step")


def read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def ctx_of(trace):
    return SimpleNamespace(reduced=T.reduce_trace(trace), full_trace=trace,
                           root=ROOT)


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "span_trace.textproto")) as f:
        return T.load_text_proto(f.read())


def test_the_window_is_the_reductions_own(trace):
    r = T.reduce_trace(trace)
    assert r.window == (0.0, 1000e3) and r.devices == 1
    assert r.idle_pct() == pytest.approx(31.0)


def test_the_four_readers_return_the_numbers_built_in(trace):
    ctx = ctx_of(trace)
    # turns of 140 and 100 us; the turn that planned nothing is no step
    assert read("host_turn_ms_per_step", ctx) == pytest.approx(0.120)
    # steps of 30 + 40 and 20 + 40 us
    assert read("train_host_ms_per_step", ctx) == pytest.approx(0.065)
    assert read("idle_in_host_work_pct", ctx) == pytest.approx(18.0)
    assert read("idle_in_device_wait_pct", ctx) == pytest.approx(4.5)


def test_the_idle_shares_are_parts_of_device_idle_pct(trace):
    ctx = ctx_of(trace)
    work = read("idle_in_host_work_pct", ctx)
    wait = read("idle_in_device_wait_pct", ctx)
    # the rest: idle outside any span of the program, 85 us of 1000
    both = span_trace.idle_inside_pct(
        ctx, span_trace.SERVE_HOST_WORK + span_trace.TRAIN_HOST_WORK
        + span_trace.SERVE_DEVICE_WAIT)
    assert both == pytest.approx(work + wait)  # no instant is counted twice
    assert work + wait + 8.5 == pytest.approx(ctx.reduced.idle_pct())
    assert read("device_idle_pct", ctx) == pytest.approx(work + wait + 8.5)


def test_a_gap_goes_to_the_innermost_span_that_covers_half_of_it(trace):
    places = span_trace.gap_places(trace, min_s=25e-6)
    assert [(round(1e6 * at), round(1e6 * dur), place)
            for at, dur, place, _ in places] == [
        (100, 30, "serve/device"), (400, 60, "serve/device"),
        # no child holds half of these two: the step (shorter than the
        # benchmark's span round it) is the place
        (500, 100, "train/step"), (900, 100, "train/step")]
    assert places[1][3] == {"step": 2}  # the step whose results were awaited
    assert places[3][3] == {"step": 8}
    assert "gap of 0.0001 s at 0.0009 s: inside train/step" in (
        span_trace.describe(trace, 25e-6))


def test_spans_carry_their_arguments_and_pair_by_step_number(trace):
    lines = span_trace.spans_of(trace)
    assert list(lines) == ["python"]
    dispatched = {e.stats["step"]: e
                  for e in span_trace.named(lines, ["serve/dispatch"])}
    folded = {e.stats["step"]: e
              for e in span_trace.named(lines, ["serve/complete"])}
    # step 2 was dispatched in the first turn and folded in the second
    assert dispatched[2].end <= folded[2].start
    steps = span_trace.named(lines, [span_trace.SERVE_STEP])
    assert [span_trace.is_a_step(s) for s in steps] == [True, True, False]
    assert steps[1].stats == {"dispatched": 3, "folded": 2}


def test_a_program_without_the_spans_reads_nothing_and_does_not_raise():
    """The parent of PR 39 under the benchmark as PR 39 leaves it: device
    operations, the benchmark's spans and ``serve/device_step`` alone."""
    def event(name, start, dur, **stats):
        return T.Event(name, start, dur, stats)

    parent = {
        "/device:TPU:0": {T.OPS_LINE: [event("op.A", 0, 80e3)]},
        T.HOST_PLANE: {"python": [
            event("bench/engine.step", 0, 100e3),
            event("serve/device_step", 10e3, 20e3, rows=128)]}}
    ctx = ctx_of(parent)
    assert read("device_idle_pct", ctx) == pytest.approx(20.0)
    for name in NEW:
        assert read(name, ctx) is None, name
    # and a run that was not traced at all
    untraced = SimpleNamespace(reduced=None, root=ROOT)
    for name in NEW:
        assert read(name, untraced) is None, name


def test_the_manifest_lists_the_eight_new_metrics_with_readers_and_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    from benchmarks import run as bench_run

    cells = {w["name"]: w for w in manifest["workloads"]}
    mine = [m for m in manifest["per_layer"]
            if m["name"].split(".")[0] in NEW]
    assert [m["name"] for m in mine] == [
        "host_turn_ms_per_step.latency", "host_turn_ms_per_step.tput",
        "idle_in_host_work_pct.latency", "idle_in_host_work_pct.tput",
        "idle_in_host_work_pct.train", "idle_in_device_wait_pct.latency",
        "idle_in_device_wait_pct.tput", "train_host_ms_per_step"]
    # no position is asserted: a later PR appends after these (the check
    # that an entry sits LAST in its list refuses every later addition)
    by_layer = {m["layer"] for m in manifest["per_layer"] if m not in mine}
    for m in mine:
        stem = m["name"].split(".")[0]
        assert os.path.basename(bench_run.reader_path(m["name"])) == stem + ".py"
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        assert m["layer"] in by_layer  # a layer the benchmark already names
        assert m["better"] == "lower"
        assert m["source"] == ("program_span" if m["unit"] == "ms"
                               else "device_trace")
        e2e = {x["name"]: x for x in manifest["end_to_end"]}[m["moves"]]
        assert set(m["workloads"]) <= set(e2e["workloads"])


# ``test_bench_glm.py``'s manifest test (PR 36) asserts that GLM's four
# metrics are the LAST four of ``per_layer``; new entries go at the end of
# their lists (one put in the middle reads as a change to what was there),
# so that assert is red with any later metric and the checks behind it no
# longer run there. They run here, a case each, until a ``benchmark`` PR
# takes the position out of that test.
GLM_CELL = "glm47flash-pretrain-4k"
GLM_READERS = ("expert_train_ms_per_step", "expert_train_roofline_pct",
               "latent_flash_ms_per_step", "latent_flash_roofline_pct")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", GLM_READERS)
def test_the_glm_readers_entries_are_what_pr_36_left(name):
    metrics = {x["name"]: x for x in load_json("BENCHMARK.json")["per_layer"]}
    assert metrics[name]["workloads"] == [GLM_CELL]
    assert metrics[name]["moves"] == "train_tokens_per_s_per_chip"
    assert metrics[name]["source"] == "device_trace"
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"))


def test_the_glm_readers_stay_together_and_this_pr_only_appended():
    names = [x["name"] for x in load_json("BENCHMARK.json")["per_layer"]]
    at = names.index(GLM_READERS[0])
    assert names[at:at + 4] == list(GLM_READERS)
    # what PR 39 added follows them, and nothing came between or before
    assert [n.split(".")[0] in NEW for n in names[at + 4:at + 12]] == [True] * 8
    assert not any(n.split(".")[0] in NEW for n in names[:at + 4])


def test_the_glm_cells_traffic_is_what_pr_36_left():
    mix = load_json("benchmarks", "traffic", "pretrain-4k.json")
    assert mix["kind"] == "train_stream"
    assert mix["documents"] == load_json(
        "benchmarks", "traffic", "pretrain-2k.json")["documents"]
    assert 0 < mix["correctness"]["loss_rtol"] <= 0.002


def rehearse(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,metric,others", [
    ("mixtral8x7b-longdoc", "host_turn_ms_per_step.tput",
     ("real_rows_pct.tput",)),
    ("bloom560m-pretrain-2k", "train_host_ms_per_step",
     ("data_wait_ms_per_step",)),
])
def test_the_rehearsal_reads_the_program_spans_on_the_cpu(cell, metric, others):
    """The ``program_span`` readers need no device: the rehearsal's own
    profile of the tiny run holds the spans, and its last line names the
    metric (a name, never a number)."""
    last = rehearse(cell)
    assert last["rehearsal"] == "passed" and last["workload"] == cell
    assert metric in last["metric_names"]
    assert set(others) <= set(last["metric_names"])
    # the device's shares are a chip's to read
    assert not any(n.startswith("idle_in_") for n in last["metric_names"])
