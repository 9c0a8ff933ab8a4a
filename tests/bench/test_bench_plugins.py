"""A new deployment needs only new files: a generator, a driver and a
plain reference, each found by name (``bench/cell.py``).

A toy deployment (reads and updates over a few keys, counted by a jitted
program) is written into ``tmp_path``, the lookups are pointed there, and
whole runs of its cell go through ``bench.run.run`` on the CPU: correct
with the reference in this process and in the spawn pool, not correct
with the reference broken by one counter.
"""
import textwrap

import jax
import pytest

from bench import cell as C
from bench import drivers as D
from bench import generate as G

GENERATOR = """
import numpy as np


def requests(cfg, traffic, seed):
    rng = np.random.default_rng(seed)
    n, n_keys = int(cfg["n_requests"]), int(cfg["n_keys"])
    return dict(keys=rng.integers(0, n_keys, n).astype(np.int32),
                ops=(rng.random(n) < traffic["update_share"]).astype(np.int32),
                sizes=np.ones(n_keys, np.float32))
"""

DRIVER = """
import numpy as np

from bench.drivers import Segments


class Driver(Segments):
    fields = ("n_reads", "n_updates", "n_keys_touched")
    reference = "toy_count"

    def __init__(self, config, traffic, seed):
        super().__init__(config, traffic, seed)
        self.lanes = [("toy", 0.0, 0.0)]
        self.work_per_call = self.seg_len

    def ingest(self):
        import jax
        import jax.numpy as jnp
        n_keys = self.n_objects

        @jax.jit
        def count(keys, ops):
            touched = jnp.zeros(n_keys, jnp.int32).at[keys].set(1)
            return dict(n_reads=jnp.sum(ops == 0), n_updates=jnp.sum(ops),
                        n_keys_touched=jnp.sum(touched))
        self.count = count
        self.segments = [(jnp.asarray(self.ref_in["keys"][sl]),
                          jnp.asarray(self.ref_in["ops"][sl]))
                         for sl in map(self._slice, range(self.n_segments))]

    def warm(self):
        self.call(0)

    def release(self):
        self.segments = None

    def call(self, k):
        import jax
        out = jax.device_get(self.count(*self.segments[k % self.n_segments]))
        return {f: np.asarray(out[f], np.float64).reshape(-1)
                for f in self.fields}

    def jobs(self, segment, control=None):
        sl = self._slice(segment)
        return [dict(keys=self.ref_in["keys"][sl], ops=self.ref_in["ops"][sl],
                     **(control or {}))]
"""

REFERENCE = """
import numpy as np

OFF = {off}


def run_job(job):
    keys, ops = np.asarray(job["keys"]), np.asarray(job["ops"])
    return dict(n_reads=float(np.sum(ops == 0)),
                n_updates=float(np.sum(ops == 1)) + OFF,
                n_keys_touched=float(np.unique(keys).size))


def gaps(got, ref, n_requests):
    return max(abs(got[f] - ref[f]) for f in ref) / n_requests, 0.0
"""


def write_plugins(root, monkeypatch, off=0):
    """The toy's three files under ``root``, and the lookups pointed
    there."""
    for kind, sub, name, src in (
            ("generator", "generators", "toy_ops", GENERATOR),
            ("driver", "callers", "toy_calls", DRIVER),
            ("reference", "references", "toy_count",
             REFERENCE.format(off=off))):
        d = root / sub
        d.mkdir(parents=True, exist_ok=True)
        (d / f"{name}.py").write_text(textwrap.dedent(src))
        monkeypatch.setitem(C.PLUGIN_DIRS, kind, d)


def toy_cell():
    return C.Cell(
        name="toy_cell", chips=1,
        config=dict(name="toy", generator="toy_ops", n_requests=256,
                    n_keys=16),
        traffic=dict(driver="toy_calls", segment_requests=128,
                     update_share=0.5, rate_metric="toy_req_per_s",
                     limits={"counter_gap": 0.0, "latency_gap": 0.0}),
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "toy_req_per_s", "unit": "req/s"}],
        per_layer=[])


def run_toy(workers):
    from bench.run import run
    return run(toy_cell(), 11, seconds=0.0, trace=False,
               devices=jax.devices()[:1], workers=workers)


def test_toy_is_no_built_in_and_no_file_of_the_repo():
    assert "toy_ops" not in G.GENERATORS
    assert "toy_calls" not in D.DRIVERS
    for kind, name in (("generator", "toy_ops"), ("driver", "toy_calls"),
                       ("reference", "toy_count")):
        assert not C.plugin_path(kind, name).exists()


@pytest.mark.parametrize("workers", [1, 2], ids=["in_process", "spawn_pool"])
def test_new_deployment_from_files_is_correct(workers, tmp_path,
                                              monkeypatch):
    write_plugins(tmp_path, monkeypatch)
    out = run_toy(workers)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "toy_req_per_s"}


@pytest.mark.parametrize("workers", [1, 2], ids=["in_process", "spawn_pool"])
def test_broken_reference_is_not_correct(workers, tmp_path, monkeypatch):
    write_plugins(tmp_path, monkeypatch, off=1)
    out = run_toy(workers)
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]
    assert out["checks"]["counter_gap"]["value"] == 1 / 128


def test_plugin_driver_sets_its_fields_and_reference(tmp_path, monkeypatch):
    write_plugins(tmp_path, monkeypatch)
    cell = toy_cell()
    drv = D.build(cell.config, cell.traffic, 3)
    assert isinstance(drv, D.Segments)
    assert drv.fields == ("n_reads", "n_updates", "n_keys_touched")
    source = C.reference_source(drv.reference)
    assert source == str(tmp_path / "references" / "toy_count.py")
    drv.generate()
    (job,) = drv.jobs(0)
    assert C.run_reference_job(source, job)["n_reads"] == float(
        (job["ops"] == 0).sum())


def test_unknown_names_raise(tmp_path, monkeypatch):
    write_plugins(tmp_path, monkeypatch)
    cfg = dict(toy_cell().config, generator="no_such_generator")
    with pytest.raises(ValueError, match="synthetic.*toy_ops"):
        G.requests(cfg, toy_cell().traffic, 1)
    traffic = dict(toy_cell().traffic, driver="no_such_driver")
    with pytest.raises(ValueError, match="replay.*sweep.*toy_calls"):
        D.build(toy_cell().config, traffic, 1)
    with pytest.raises(FileNotFoundError, match="bench.reference.*toy_count"):
        C.reference_source("no_such_reference")
