"""run.py end to end on the CPU: the rehearsal says rehearsal and carries no
metric; a measurement without a TPU fails and prints no result; the serving
comparison passes what was served and refuses the reference broken on
purpose (--check-seeds with --inject, the control, at the rehearsal's size)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the rehearsal sets its own device count
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell,trace", [
    ("bloom560m-pretrain-2k", "1"),
    ("mixtral8x7b-chat", "0"),
])
def test_rehearsal_ends_in_a_line_that_says_so(cell, trace):
    p = run("--workload", cell, "--seed", "3000000011", "--seconds", "3",
            "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed" and last["workload"] == cell
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    # no number of any kind under a metric's name
    assert "metrics" not in last and "correct" not in last
    assert all(isinstance(n, str) for n in last["metric_names"])
    assert "compilations inside the window: 0" in p.stdout
    if cell == "mixtral8x7b-chat":  # each part of the rule, count and limit
        assert "share rule: 8 within 0.05" in p.stdout
        assert "outlier rule: 8 clear of a near-tie" in p.stdout
        assert "(gap, margin) per served token: (" in p.stdout
        # the precision sample, after the window: 4 requests of 4 tokens
        assert "16 served tokens" in p.stdout
        assert "exact rule: at least 75.0% = 12" in p.stdout
        assert p.stdout.index("compilations inside the window") < (
            p.stdout.index("exact rule"))


def test_the_check_alone_passes_what_was_served_and_refuses_the_faults():
    # the control: the reference broken on purpose has to read incorrect
    p = run("--workload", "mixtral8x7b-longdoc", "--rehearse", "--check-seeds",
            "3000000041,7", "--inject", "gqa_mispaired,token_clear,token_tied")
    checks = json.loads(p.stdout.strip().splitlines()[-1])["checks"]
    assert [(c["seed"], c["inject"]) for c in checks] == [
        (seed, inject) for seed in (3000000041, 7)
        for inject in (None, "gqa_mispaired", "token_clear", "token_tied")]
    for c in checks:
        # the rehearsal calls no position near-tied, so token_tied finds none
        assert c["correct"] == (c["inject"] in (None, "token_tied")), c
        assert c["tokens"] == 8
        # the precision sample is judged by the exact rule alone: a swapped
        # token of the first sample is no business of it
        assert c["precision"]["tokens"] == 16
        assert (c["precision"]["argmax"] >= 12) == (
            c["inject"] != "gqa_mispaired"), c
    assert p.returncode == 1  # something read incorrect
    assert "metrics" not in p.stdout and "window" not in p.stdout


@pytest.mark.parametrize("args,why", [
    (("--inject", "gqa_mispaired"), "--check-seeds only"),
    (("--inject", "token_clear", "--seed", "5"), "--check-seeds only"),
    (("--check-seeds", "5", "--inject", "nope", "--rehearse"), "no fault"),
    (("--check-seeds", "5", "--rehearse", "--workload",
      "bloom560m-pretrain-2k"), "serving cells"),
])
def test_a_measured_run_is_never_an_injected_one(args, why):
    p = run("--workload", "mixtral8x7b-chat", *args, timeout=120)
    assert p.returncode == 2 and why in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_without_a_tpu_nothing_runs_and_no_result_is_printed():
    p = run("--workload", "bloom560m-pretrain-2k", "--seed", "1",
            "--seconds", "1", "--trace", "0", timeout=120)
    assert p.returncode == 3
    assert "no TPU here" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_an_unknown_workload_is_refused():
    p = run("--workload", "nope", "--seed", "1", "--seconds", "1",
            "--trace", "0", timeout=60)
    assert p.returncode == 2 and "no workload" in p.stderr


@pytest.mark.parametrize("name,stem", [
    ("mfu_pct", "mfu_pct"),
    ("device_idle_pct.train", "device_idle_pct"),
    ("device_idle_pct.some-later-split", "device_idle_pct"),
    ("real_rows_pct.tput", "real_rows_pct"),
])
def test_a_split_metric_is_read_by_the_reader_of_its_quantity(name, stem):
    from benchmarks import run as bench_run

    assert os.path.basename(bench_run.reader_path(name)) == stem + ".py"


def test_a_metric_without_a_reader_is_refused():
    from benchmarks import run as bench_run

    with pytest.raises(SystemExit):
        bench_run.reader_path("no_such_metric.train")

