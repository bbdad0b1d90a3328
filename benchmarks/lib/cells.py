"""Cells, configurations, traffic mixes, verbs and per-layer readers are
found by the names in BENCHMARK.json, in files of their own under the
benchmark's ``paths``. A later PR adds files and entries; it edits none."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Callable, Optional


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict      # configs/<config>.json, as it is run
    traffic: dict     # traffic/<mix>.json
    bench: dict       # the whole BENCHMARK.json
    root: str         # directory that holds BENCHMARK.json

    def metrics(self, group: str) -> list:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [
            m for m in self.bench[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def find_file(root: str, bench: dict, relative: str) -> str:
    """``relative`` under the first of the benchmark's ``paths`` that has it."""
    for base in bench["paths"]:
        path = os.path.join(root, base, relative)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"{relative} is under none of the benchmark's paths {bench['paths']}"
    )


def load_cell(bench_file: str, workload: str) -> Cell:
    root = os.path.dirname(os.path.abspath(bench_file))
    bench = _read_json(bench_file)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {workload!r} in {bench_file}: {names}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        traffic=_read_json(
            find_file(root, bench, f"traffic/{entry['traffic']}.json")
        ),
        bench=bench,
        root=root,
    )


def load_module(path: str, name: str):
    """The module at ``path``; loaded once for each path."""
    have = sys.modules.get(name)
    if have is not None and getattr(have, "__file__", None) == path:
        return have
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_verb(cell: Cell):
    verb = cell.traffic["verb"]
    if "verbs_common" not in sys.modules:
        load_module(find_file(cell.root, cell.bench, "verbs/_common.py"), "verbs_common")
    return load_module(
        find_file(cell.root, cell.bench, f"verbs/{verb}.py"),
        f"bench_verb_{verb}",
    )


def load_reader(cell: Cell, metric: str) -> Optional[Callable]:
    """``layer_metrics/<metric>.py``'s ``read(obs)``."""
    path = find_file(cell.root, cell.bench, f"layer_metrics/{metric}.py")
    return load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def load_peaks(cell: Cell, device_kind: str) -> dict:
    """The published peaks of this device. A device that is not in the
    table is an error, not a default."""
    table = _read_json(find_file(cell.root, cell.bench, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"peaks.json has no device_kind {device_kind!r}; add it with its "
            "source rather than guess"
        )
    return table["devices"][device_kind]
