"""BENCHMARK.json against the contract it is checked by, and against the
files it names: a later PR adds a cell, a configuration or a metric by
adding files and entries, and this walk is what shows it is enough."""

import json
import os
import re

import pytest

from bench_cells import manifest

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return manifest.load_manifest()


def _metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert 1 <= len(bench["command"]) <= 32
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
        assert ".." not in word.split("/")
    # a full check with the full 24 cells has to fit the driver's day
    runs = 2 + 14 * 24
    assert (runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)


def test_names_units_and_keys(bench):
    names = [m["name"] for m in _metrics(bench)]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in _metrics(bench):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
    for group in (bench["configs"], bench["workloads"]):
        got = [g["name"] for g in group]
        assert len(got) == len(set(got))
        for g in group:
            assert NAME.match(g["name"])
            assert 1 <= len(g["why"]) <= 200 and "\n" not in g["why"]
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_named_file_exists(bench):
    under = tuple(p.rstrip("/") + "/" for p in bench["paths"])
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    files = set()
    for c in bench["configs"]:
        assert c["file"].startswith(under)
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert os.path.isfile(os.path.join(
            manifest.HERE, "reference", body["reference"] + ".py"))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        mix = manifest.load_cell(w["name"]).traffic
        assert os.path.isfile(os.path.join(
            manifest.HERE, "runners", mix["runner"] + ".py"))
    for m in _metrics(bench):
        assert os.path.isfile(manifest.metric_path(m["name"]))
        assert callable(manifest.load_reader(m["name"]))


def test_four_chip_cells_are_at_most_a_quarter(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_reports_and_every_moves_is_reported_there(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def cells_of(metric):
        listed = metric.get("workloads", cells)
        assert set(listed) <= set(cells)
        return set(listed)

    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
    for cell in cells:
        got = [n for n, m in e2e.items() if cell in cells_of(m)]
        assert "setup_s" in got and len(got) >= 2, cell
        assert any(cell in cells_of(m) for m in bench["per_layer"]), cell


def test_layers_are_those_of_perf_md(bench):
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert f"| {layer} |" in perf, layer
