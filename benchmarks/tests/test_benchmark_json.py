"""BENCHMARK.json against the contract's limits, and against the files
it names."""

import json
import os
import re

import pytest

from benchmarks.lib.common import reader_path

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_size(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32
    cells = len(bench["workloads"])
    assert 1 <= cells <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, cells // 4)


def test_names_units_and_keys(bench):
    for group, keys, extra in (
            ("configs", {"name", "source", "file", "reduced"}, {"why"}),
            ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
            ("end_to_end", {"name", "unit", "better", "bound", "source"},
             {"workloads"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"}, {"workloads"})):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        for e in bench[group]:
            assert keys <= set(e) <= keys | extra, e
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])


def test_every_cell_reports_what_the_contract_asks(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in bench["end_to_end"]}
    assert e2e["setup_s"] == cells
    for cell in cells:
        assert sum(cell in ws for n, ws in e2e.items() if n != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        # the metric it moves is reported wherever it is
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
    layers = {m["layer"] for m in bench["per_layer"]}
    assert layers <= {"Wire", "Scheduler", "Serving step", "Train step",
                      "Parallelism", "Kernels", "Device"}


def test_files_exist_and_are_data(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        ref = cfg["reference"]
        assert os.path.exists(os.path.join(ROOT, ref["file"]))
        assert ref["compares"] and ref["tolerance"]["why"]
        for key in c["reduced"]:        # widths are never cut
            assert not key.endswith(("_dim", "_rank", "_size")), key
    for w in bench["workloads"]:
        path = os.path.join(ROOT, "benchmarks", "traffic", w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "lib", "drivers", mix["driver"] + ".py"))
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for m in bench[group]:
            assert os.path.exists(reader_path(folder, m["name"])), m["name"]
    # a quantity split by cells is read by one file
    assert reader_path("layer_metrics", "some-cells.train_mfu") == \
        reader_path("layer_metrics", "train_mfu")


def test_harness_has_no_branch_on_a_name(bench):
    names = [w["name"] for w in bench["workloads"]] \
        + [c["name"] for c in bench["configs"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]] \
        + [w["traffic"] for w in bench["workloads"]]
    lib = os.path.join(ROOT, "benchmarks", "lib")
    sources = [os.path.join(ROOT, "benchmarks", "run.py")]
    for d, _, fs in os.walk(lib):
        sources += [os.path.join(d, f) for f in fs if f.endswith(".py")]
    for path in sources:
        with open(path) as f:
            code = "\n".join(line.split("#")[0] for line in f
                             if not line.lstrip().startswith(('"', "'")))
        for n in names:
            assert f'"{n}"' not in code and f"'{n}'" not in code, (path, n)
