"""BENCHMARK.json against the contract it is held to, and against the files
it names."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics(m):
    return m["end_to_end"] + m["per_layer"]


def cells_of(metric, m):
    return metric.get("workloads", [w["name"] for w in m["workloads"]])


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24  # a full check with the full 24 cells
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")


def test_every_name_and_unit_is_within_the_allowed_characters(manifest):
    names = []
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        names += [w["name"], w["config"], w["traffic"]]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names += [c["name"], *c["reduced"]]
    for x in metrics(manifest):
        names.append(x["name"])
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for n in names:
        assert NAME.match(n), n
    for key in ("workloads", "configs"):
        got = [x["name"] for x in manifest[key]]
        assert len(got) == len(set(got))
    got = [x["name"] for x in metrics(manifest)]
    assert len(got) == len(set(got))
    for x in manifest["workloads"] + manifest["configs"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_file_the_manifest_names_exists(manifest):
    for c in manifest["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert os.path.isfile(path), c["file"]
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(path) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert "assumed" in body and "deployment" in body
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        t = os.path.join(ROOT, "benchmarks", "traffic", w["traffic"] + ".json")
        assert os.path.isfile(t), t
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            fam = json.load(f)["family"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "families", fam + ".py")), fam
    readers = {r[:-3] for r in os.listdir(os.path.join(
        ROOT, "benchmarks", "layer_metrics")) if r.endswith(".py")}
    named = set()
    for x in manifest["per_layer"]:
        # a split name (x.train, x.latency) is read by the reader of x
        mine = {x["name"], x["name"].split(".")[0]} & readers
        assert mine, x["name"]
        named |= mine
    assert readers == named  # no reader that no metric reads
    for rel in ("run.py", "loadgen.py", "flops.py", "trace_reduce.py",
                "reference.py", "peaks.json"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", rel))


def test_end_to_end_metrics_and_bounds(manifest):
    e2e = {x["name"]: x for x in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for x in manifest["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for w in manifest["workloads"]:
        mine = [x["name"] for x in manifest["end_to_end"]
                if w["name"] in cells_of(x, manifest)]
        assert "setup_s" in mine and len(mine) >= 2, w["name"]


def test_per_layer_metrics_move_a_metric_their_cells_report(manifest):
    e2e = {x["name"]: x for x in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    layers = {}
    for x in manifest["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert x["moves"] in e2e and x["moves"] != "setup_s"
        assert 1 <= len(x["layer"]) <= 200 and "\n" not in x["layer"]
        mine = set(cells_of(x, manifest))
        assert mine <= cells
        assert mine <= set(cells_of(e2e[x["moves"]], manifest)), x["name"]
        layers.setdefault(x["layer"].split(":")[0], set()).add(x["layer"])
    assert all(len(v) == 1 for v in layers.values())  # letter for letter
    for w in manifest["workloads"]:
        assert any(w["name"] in cells_of(x, manifest)
                   for x in manifest["per_layer"]), w["name"]


def test_peaks_table_names_its_source():
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e and "cpu" not in peaks
