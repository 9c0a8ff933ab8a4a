"""Mesh builders (repro.launch.mesh): axis names, axis types, and the
import-side-effect-free contract.

``make_production_mesh`` needs 256+ devices, so its axis wiring is checked
against a capturing stand-in for ``jax.make_mesh`` rather than by building
the mesh.  The import-purity contract — importing the launch modules never
queries jax devices, so ``XLA_FLAGS``-forced host device counts set *after*
import but *before* first device use still take effect — is a subprocess
regression test, since an in-process jax is already initialized.
"""
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.launch import mesh as mesh_mod

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture
def capture_make_mesh(monkeypatch):
    calls = []

    def fake(shape, axes, **kw):
        calls.append((tuple(shape), tuple(axes), dict(kw)))
        return "mesh"

    monkeypatch.setattr(jax, "make_mesh", fake)
    return calls


def test_production_mesh_axis_names(capture_make_mesh):
    mesh_mod.make_production_mesh()
    mesh_mod.make_production_mesh(multi_pod=True)
    (s1, a1, _), (s2, a2, _) = capture_make_mesh
    assert (s1, a1) == ((16, 16), ("data", "model"))
    assert (s2, a2) == ((2, 16, 16), ("pod", "data", "model"))


def test_axis_type_forwarded_new_jax(capture_make_mesh):
    """Every mesh axis is built as an Auto axis (sharding left to XLA)."""
    mesh_mod.make_production_mesh(multi_pod=True)
    _, axes, kw = capture_make_mesh[0]
    assert kw == {"axis_types": (AxisType.Auto,) * len(axes)}


def test_local_mesh_builds_on_one_device():
    m = mesh_mod.make_local_mesh()
    assert m.axis_names == ("data", "model")
    assert dict(m.shape) == {"data": 1, "model": 1}


def test_data_mesh():
    m = mesh_mod.make_data_mesh()
    assert m.axis_names == ("data",)
    assert int(m.shape["data"]) == jax.device_count()
    assert mesh_mod.make_data_mesh(1).devices.size == 1
    # explicit device order is preserved verbatim (the fabric parity suite
    # builds permuted meshes from this)
    devs = list(jax.devices())
    mp = mesh_mod.make_data_mesh(devices=devs)
    assert list(mp.devices.flat) == devs


def test_data_mesh_rejects_bad_counts():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        mesh_mod.make_data_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError):
        mesh_mod.make_data_mesh(0)


def test_import_performs_no_device_query():
    """Importing repro.launch.{mesh,fabric} must not initialize jax's
    backend: XLA_FLAGS set after the imports still forces the device
    count (the module docstrings' contract)."""
    child = (
        "import sys, os; sys.path.insert(0, sys.argv[1])\n"
        "import repro.launch.mesh, repro.launch.fabric\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        "import jax\n"
        "assert jax.device_count() == 4, jax.device_count()\n"
        "print('DEVICES', jax.device_count())\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)     # the child inherits the CPU platform
    proc = subprocess.run([sys.executable, "-c", child, SRC],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DEVICES 4" in proc.stdout
