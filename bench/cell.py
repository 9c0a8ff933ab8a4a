"""Find a benchmark cell's pieces by name.

``BENCHMARK.json`` at the root names each cell (workload) with its
configuration and traffic mix.  Every piece lives in a file of its own,
found by name, so a new cell, and a new deployment, needs new files and
no edit to an existing one:

* ``bench/configs/<config>.json`` — the deployment (laws, scale, source),
  naming its ``generator``;
* ``bench/traffic/<traffic>.json`` — the mix of calls, naming its
  ``driver``, and the limits of the comparison that decides ``correct``.

Code is found in four directories, one module a file, by the name its
entry gives.  A generator or driver name that is a built-in
(``bench.generate.GENERATORS``, ``bench.drivers.DRIVERS``) is used as it
is; any other name is a file:

* ``bench/metrics/<metric>.py`` — one per-layer metric's reader,
  ``read(ctx)`` returning a number or ``None``;
* ``bench/generators/<generator>.py`` — ``requests(cfg, traffic, seed)``
  returning the cell's requests as a dict of numpy arrays, from the seed
  alone;
* ``bench/callers/<driver>.py`` — a class ``Driver``, a subclass of
  ``bench.drivers.Segments``, that hands the requests to the program's
  entry points.  It may set ``fields``, the answer fields it pulls and
  compares, and ``reference``, the name of its plain reference;
* ``bench/references/<reference>.py`` — a plain reference:
  ``run_job(job)`` returning one lane's answer fields, and ``gaps(got,
  ref, n_requests)`` returning ``(counter_gap, latency_gap)``.  It imports
  numpy and nothing of the program.  The built-in reference is the module
  ``bench.reference``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BUILTIN_REFERENCE = "bench.reference"

# where each kind of code is found by name
PLUGIN_DIRS = {"metric": BENCH / "metrics",
               "generator": BENCH / "generators",
               "driver": BENCH / "callers",
               "reference": BENCH / "references"}


class UnknownName(FileNotFoundError, ValueError):
    """A name that no built-in and no file answers: the file is missing,
    and the name in the configuration or traffic mix is unknown."""


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


def plugin_path(kind: str, name: str) -> Path:
    return PLUGIN_DIRS[kind] / f"{name}.py"


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


def _import_file(kind: str, path: Path):
    """The module in ``path``, executed afresh."""
    mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", path.stem)
    s = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[mod_name] = mod
    s.loader.exec_module(mod)
    return mod


def _find(kind: str, name: str, builtins) -> Path:
    path = plugin_path(kind, name)
    if not path.is_file():
        found = sorted(p.stem for p in PLUGIN_DIRS[kind].glob("*.py"))
        raise UnknownName(f"unknown {kind} {name!r}: no built-in "
                          f"{sorted(builtins)} and no file {path} "
                          f"(files found: {found})")
    return path


def load_plugin(kind: str, name: str, builtins=()):
    """The module of ``PLUGIN_DIRS[kind]/<name>.py``.  A missing file
    raises :class:`UnknownName`, listing ``builtins`` and the files
    found."""
    return _import_file(kind, _find(kind, name, builtins))


def load_reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return load_plugin("metric", metric).read


def reference_source(name: str) -> str:
    """What a process imports for the reference ``name``: the built-in
    module's import name, or the path of the reference's file, so that a
    worker process needs nothing but this string."""
    if name == BUILTIN_REFERENCE:
        return name
    return str(_find("reference", name, [BUILTIN_REFERENCE]))


def load_reference(source: str):
    """The reference module a :func:`reference_source` string names."""
    if source.endswith(".py"):
        return _import_file("reference", Path(source))
    return importlib.import_module(source)


def run_reference_job(source: str, job: dict) -> dict:
    """One job of the reference ``source``; at module level, so that a
    spawn pool can run it."""
    return load_reference(source).run_job(job)
