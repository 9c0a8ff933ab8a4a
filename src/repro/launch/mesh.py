"""The sweep fabric's 1-D ``data`` device mesh.

Built by a function (never a module-level constant) so importing this module
never touches jax device state: ``XLA_FLAGS`` that force a host device count
must be set before the first device query.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_data_mesh(n_devices: int | None = None, devices=None):
    """1-D ``data`` mesh over ``n_devices`` local devices (default: all).

    The sweep fabric's lane-sharding axis (:mod:`repro.launch.fabric`,
    DESIGN.md §13).  ``devices`` pins an explicit device *order* — the
    fabric's lane->device assignment follows mesh order, and the parity
    suite (tests/test_fabric.py) builds permuted meshes to prove the
    assignment is invisible in results; ``jax.make_mesh`` may reorder
    devices for locality, so this builder constructs the ``Mesh``
    directly from the given sequence."""
    import numpy as np

    devs = list(jax.devices()) if devices is None else list(devices)
    if n_devices is not None:
        if n_devices < 1 or n_devices > len(devs):
            raise ValueError(
                f"n_devices={n_devices} but {len(devs)} device(s) are "
                f"available; on CPU, fake host devices must be forced with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
                f"jax initializes (the subprocess pattern of "
                f"benchmarks/probe_memory.py)")
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.array(devs), ("data",),
                             axis_types=(AxisType.Auto,))
