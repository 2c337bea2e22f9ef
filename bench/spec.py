"""Finds the benchmark's data by name: cells, configurations, traffic
mixes, per-layer metric readers and the table of peaks. A later change adds
one of these by adding its file (and its entry in ``BENCHMARK.json``)."""
from __future__ import annotations

import importlib.util
import json
import os
from typing import NamedTuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class Cell(NamedTuple):
    name: str
    config: str
    traffic: str
    chips: int


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> Cell:
    """A cell of ``BENCHMARK.json``, the one list of cells and their chips."""
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return Cell(name, w["config"], w["traffic"], int(w["chips"]))
    raise KeyError(f"no cell named {name!r} in BENCHMARK.json")


def _path(kind: str, name: str, ext: str) -> str:
    return os.path.join(BENCH, kind, name + ext)


def config(name: str) -> dict:
    with open(_path("configs", name, ".json")) as f:
        return json.load(f)


def traffic_path(name: str) -> str:
    return _path("traffic", name, ".json")


def per_layer(cell_name: str) -> list:
    """The per-layer metrics a traced run of this cell reports."""
    b = benchmark()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    reported = {n for n, m in e2e.items()
                if "workloads" not in m or cell_name in m["workloads"]}
    return [m for m in b["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def reader(metric: str):
    """The ``read(ctx)`` function of a per-layer metric's own file."""
    path = _path("metrics", metric, ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
