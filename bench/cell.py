"""Find a benchmark cell's pieces by name.

``BENCHMARK.json`` at the root names each cell (workload) with its
configuration and traffic mix; each lives in a file of its own, found by
name, so a new cell needs new files and no edit to an existing one:

* ``bench/configs/<config>.json`` — the deployment (laws, scale, source);
* ``bench/traffic/<traffic>.json`` — the mix of calls and the limits of
  the comparison that decides ``correct``;
* ``bench/metrics/<metric>.py`` — one per-layer metric's reader, a
  ``read(ctx)`` function returning a number or ``None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    with open(path) as f:
        return json.load(f)


def config_path(name: str) -> Path:
    return BENCH / "configs" / f"{name}.json"


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def load_cell(workload: str, spec: dict | None = None) -> Cell:
    spec = load_spec() if spec is None else spec
    entries = [w for w in spec["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        known = ", ".join(w["name"] for w in spec["workloads"])
        raise KeyError(f"unknown workload {workload!r} (known: {known})")
    w = entries[0]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = _read_json(ROOT / cfg_entry["file"])
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_read_json(traffic_path(w["traffic"])),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def load_reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = metric_path(metric)
    mod_name = "bench_metric_" + metric.replace(".", "_").replace("-", "_")
    s = importlib.util.spec_from_file_location(mod_name, path)
    if s is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read
