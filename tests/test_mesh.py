"""The data mesh builder (repro.launch.mesh): axis name, axis type, device
order, and the import-side-effect-free contract.

The axis type is checked against a capturing stand-in for
``jax.sharding.Mesh``.  The import-purity contract — importing the launch
modules never queries jax devices, so ``XLA_FLAGS``-forced host device counts
set *after* import but *before* first device use still take effect — is a
subprocess regression test, since an in-process jax is already initialized.
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
def capture_mesh(monkeypatch):
    calls = []

    def fake(devices, axes, **kw):
        calls.append((devices, tuple(axes), dict(kw)))
        return "mesh"

    monkeypatch.setattr(jax.sharding, "Mesh", fake)
    return calls


def test_axis_type_forwarded_new_jax(capture_mesh):
    """The data axis is built as an Auto axis (sharding left to XLA)."""
    mesh_mod.make_data_mesh()
    _, axes, kw = capture_mesh[0]
    assert axes == ("data",)
    assert kw == {"axis_types": (AxisType.Auto,)}


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
