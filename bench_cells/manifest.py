"""``BENCHMARK.json`` and the files it names: everything that belongs to one
configuration, one traffic mix or one metric is a file of its own, found by
its name, so that a later PR adds cells, configurations and metrics by
adding files and entries."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict          # the configuration file
    traffic: dict         # the traffic mix's parameters
    end_to_end: tuple     # metric entries this cell reports
    per_layer: tuple


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", f"{name}.py")


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_manifest(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench_cells: no workload {name!r} in "
                         f"BENCHMARK.json (have: {sorted(cells)})")
    w = cells[name]
    cfg_entry, = (c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        traffic=_read_json(traffic_path(w["traffic"])),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def load_reader(metric_name: str):
    """The metric's own reader: ``read(run) -> number or None``."""
    path = metric_path(metric_name)
    spec = importlib.util.spec_from_file_location(
        "bench_cells_metric_" + metric_name.replace(".", "_").replace(
            "-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str) -> dict:
    table = _read_json(os.path.join(HERE, "reduce", "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise SystemExit(f"bench_cells: no published peaks for device kind "
                         f"{device_kind!r} in reduce/peaks.json")
    return table[device_kind]
