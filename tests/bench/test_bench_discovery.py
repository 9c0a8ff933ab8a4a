"""BENCHMARK.json and the files the harness finds by name."""
import json
import re

import pytest

from bench import cell as C
from bench import drivers as D
from bench import generate as G

SPEC = C.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3"
    for word in SPEC["command"][1:]:
        assert (C.ROOT / word).is_file()
        assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_finds_its_files_by_name(workload):
    cell = C.load_cell(workload)
    entry = next(w for w in SPEC["workloads"] if w["name"] == workload)
    assert C.traffic_path(entry["traffic"]).is_file()
    assert cell.traffic["driver"] in ("replay", "sweep")
    assert set(cell.traffic["limits"]) == {"counter_gap", "latency_gap"}
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    # the traffic names the rate it reports, and the cell lists it
    assert cell.traffic["rate_metric"] in e2e
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(C.load_reader(m["name"]))
        # a per-layer metric moves an end-to-end metric its cell reports
        assert m["moves"] in e2e
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200


def _driver(workload):
    cell = C.load_cell(workload)
    return D.build(cell.config, cell.traffic, 1)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves_generator_driver_and_reference(workload):
    """Each cell's generator, driver and reference are a built-in or a file
    of their directory, and the reference has what a run calls."""
    cell = C.load_cell(workload)
    gen = cell.config["generator"]
    assert gen in G.GENERATORS or C.plugin_path("generator", gen).is_file()
    kind = cell.traffic["driver"]
    assert kind in D.DRIVERS or C.plugin_path("driver", kind).is_file()
    drv = _driver(workload)
    assert isinstance(drv, D.Segments) and drv.fields
    ref = C.load_reference(C.reference_source(drv.reference))
    assert callable(ref.run_job) and callable(ref.gaps)


REF_ALONE = """
import sys
sys.path.insert(0, {root!r})
from bench.cell import load_reference
load_reference({source!r})
print(sorted(m for m in sys.modules
             if m.split(".")[0] in ("repro", "jax", "jaxlib")))
"""


@pytest.mark.parametrize("name", sorted({_driver(w).reference
                                         for w in WORKLOADS}))
def test_reference_imports_nothing_of_the_program(name):
    """Loaded alone in a fresh process, a reference brings in nothing of the
    program under test, nor JAX."""
    import subprocess
    import sys
    code = REF_ALONE.format(root=str(C.ROOT),
                            source=C.reference_source(name))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=C.ROOT, check=True)
    assert out.stdout.strip() == "[]"


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_configurations(entry):
    cfg = json.load(open(C.ROOT / entry["file"]))
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in cfg
        assert not re.search(r"(_dim|_rank|size|width)$", key)
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])
    assert cfg["control"] and cfg["precision"]
    assert 1 <= len(entry["source"]) <= 200 and "\n" not in entry["source"]


def test_four_chip_cells_are_at_most_half():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


def test_a_new_cell_needs_only_new_entries(tmp_path, monkeypatch):
    """A cell is found from names alone: an added workload entry that pairs
    existing files needs no code, and a new configuration brings its
    generator as a file of ``bench/generators``."""
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(
        name="fig2_roster_again", config="paper_fig2",
        traffic="roster_pareto", chips=1, why="a pairing of existing files"))
    cell = C.load_cell("fig2_roster_again", spec)
    assert cell.traffic == C.load_cell("fig2_roster_pareto").traffic
    # host-clock set-up metrics apply to every cell, later ones too
    assert [m["name"] for m in cell.per_layer] == ["ingest_s", "warmup_s"]
    with pytest.raises(KeyError):
        C.load_cell("no_such_cell")

    gen_dir = tmp_path / "generators"
    gen_dir.mkdir()
    (gen_dir / "ones.py").write_text(
        "import numpy as np\n\n\ndef requests(cfg, traffic, seed):\n"
        "    return {'objs': np.full(cfg['n_requests'], seed)}\n")
    monkeypatch.setitem(C.PLUGIN_DIRS, "generator", gen_dir)
    (tmp_path / "ones.json").write_text(json.dumps(
        dict(name="ones", generator="ones", n_requests=4)))
    spec["configs"].append(dict(name="ones", file=str(tmp_path / "ones.json"),
                                reduced=[], source="a test", why="a test"))
    spec["workloads"].append(dict(
        name="ones_replay", config="ones", traffic="replay_poisson",
        chips=1, why="a new configuration with its own generator"))
    cell = C.load_cell("ones_replay", spec)
    assert cell.config["generator"] not in G.GENERATORS
    assert G.requests(cell.config, cell.traffic, 7)["objs"].tolist() == [7] * 4


def test_missing_reader_is_an_error():
    with pytest.raises(FileNotFoundError):
        C.load_reader("no_such_metric")


def test_without_a_chip_no_result():
    """Off a TPU the harness exits non-zero and prints nothing on stdout:
    it never falls back to the CPU."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(C.ROOT / "bench" / "run.py"), "--workload",
         "fig2_roster_pareto", "--seed", "3000000017", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300, cwd=C.ROOT)
    assert out.returncode == 3
    assert out.stdout == ""
    assert "TPU" in out.stderr
