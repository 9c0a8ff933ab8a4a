"""Multi-device sweep fabric: shard the sweep engine's lane axis (DESIGN.md §13).

The batched sweep engine (:mod:`repro.core.sweep`) flattens a whole
policies x params x capacities x seeds grid into one *lane* axis and vmaps
the simulation body over it — every lane is an independent ``lax.scan``
with no cross-lane communication.  That makes the lane axis embarrassingly
parallel, and this module is the (only) place that exploits it: the
flattened lane arrays are sharded over a 1-D ``data`` device mesh with
``shard_map``, each device runs the *identical* vmapped body on its lane
shard (the stacked trace rides along replicated), and the results gather
back into the exact ``[T, G, ...]`` layout of the single-device dispatch.

Parity contract (pinned by tests/test_fabric.py): device count and
lane->device assignment are **bitwise invisible** in ``SimResult``s.  This
holds by construction — per-lane arithmetic never leaves its device, the
only "communication" is the output gather, and lane padding (to a multiple
of the device count) reuses the sweep engine's dead-lane mechanism
(repeats of lane 0, sliced off before reshape) so pad lanes never interact
with real ones.

Callers do not use this module directly: ``sweep_grid(..., devices=d)`` /
``sweep_hier_grid(..., mesh=m)`` route here (``devices=1`` with no mesh
lowers to exactly the single-device graph, bypassing this module
entirely).  Importing this module never touches jax device state — the
same contract as :mod:`repro.launch.mesh` — so ``XLA_FLAGS``-forced host
device counts (the trick ``benchmarks/probe_memory.py`` and the
fabric parity tests use) keep working as long as they are set
before jax initializes.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec

__all__ = ["resolve_fabric", "fabric_lane_multiple", "fabric_sweep_single",
           "fabric_sweep_multi", "fabric_hier_single", "fabric_hier_multi"]


def resolve_fabric(devices=None, mesh=None):
    """Map the user-facing ``devices=`` / ``mesh=`` knobs onto a mesh.

    Returns ``None`` (caller keeps today's single-device graph, untouched)
    for ``devices in (None, 1)`` with no mesh.  An explicit ``mesh`` must
    carry a ``data`` axis — the lane-sharding axis — and always routes
    through the fabric, even with one device (the in-process parity tests
    use a 1-device mesh to exercise the shard_map machinery).
    ``devices=d`` builds a 1-D data mesh over the first ``d`` local
    devices (:func:`repro.launch.mesh.make_data_mesh`).
    """
    if mesh is not None:
        if devices is not None:
            raise ValueError("pass either devices= or mesh=, not both")
        if "data" not in mesh.axis_names:
            raise ValueError(
                f"fabric mesh needs a 'data' axis (the lane-sharding "
                f"axis); got axes {mesh.axis_names}")
        return mesh
    if devices is None:
        return None
    d = int(devices)
    if d < 1:
        raise ValueError(f"devices={devices} must be >= 1")
    if d == 1:
        return None
    n = jax.device_count()
    if d > n:
        raise ValueError(
            f"devices={d} but only {n} jax device(s) are visible; on CPU, "
            f"fake host devices must be forced with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"jax initializes (the subprocess pattern of "
            f"benchmarks/probe_memory.py)")
    from .mesh import make_data_mesh
    return make_data_mesh(d)


def fabric_lane_multiple(mesh) -> int:
    """Lane counts must divide into ``mesh``'s data axis: the sweep engine
    pads the flattened grid up to this multiple (dead lanes, DESIGN.md §13)."""
    return 1 if mesh is None else int(mesh.shape["data"])


def _specs(mesh):
    """(in_specs, out_specs): lane pytree sharded on axis 0 over ``data``,
    broadcast pytree replicated, results sharded on the lane axis (axis 1 —
    the sweep bodies put the stacked-trace axis first)."""
    lane = PartitionSpec("data")
    return (lane, PartitionSpec()), PartitionSpec(None, "data")


def _mk_shard_map(body, mesh):
    in_specs, out_specs = _specs(mesh)
    # per-lane scans never communicate and outputs are genuinely
    # lane-sharded, so varying-manual-axes checking has nothing to verify —
    # and with it on, the commit lax.cond's branches (one touches the
    # device-varying lane state, one passes constants through) fail its
    # equal-output-type rule
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# One compiled callable per (mesh, entry point, static config): the cache
# mirrors jax.jit's own static_argnames behavior — the sweep engine calls
# with whatever statics the grid needs and re-invocations reuse the traced
# graph.  Typed PRNG key arrays cross the shard_map boundary as raw
# uint32 key data (wrap_key_data inside the body is bitwise lossless);
# jax's extended-dtype sharding support is not relied on.
@functools.lru_cache(maxsize=None)
def _fabric_call(mesh, kind: str, statics: tuple):
    from repro.core import sweep as _sweep

    if kind == "single":
        policy_name, estimate_z, score_mode, update = statics

        def body(lanes, rest):
            caps, kd, pstack = lanes
            (tstack,) = rest
            return _sweep._sweep_single_impl(
                tstack, caps, jax.random.wrap_key_data(kd), pstack,
                policy_name, estimate_z, score_mode, update)
    elif kind == "multi":
        policy_names, estimate_z, update = statics

        def body(lanes, rest):
            caps, kd, lidx, pstack = lanes
            (tstack,) = rest
            return _sweep._sweep_multi_impl(
                tstack, caps, jax.random.wrap_key_data(kd), lidx, pstack,
                policy_names, estimate_z, update)
    elif kind == "hier_single":
        policy_name, l2_policy, estimate_z, n_shards = statics

        def body(lanes, rest):
            c1s, c2s, kd, pstack = lanes
            tstack, p2 = rest
            return _sweep._sweep_hier_single_impl(
                tstack, c1s, c2s, jax.random.wrap_key_data(kd), pstack, p2,
                policy_name, l2_policy, estimate_z, n_shards)
    elif kind == "hier_multi":
        policy_names, l2_policy, estimate_z, n_shards = statics

        def body(lanes, rest):
            c1s, c2s, kd, lidx, pstack = lanes
            tstack, p2 = rest
            return _sweep._sweep_hier_multi_impl(
                tstack, c1s, c2s, jax.random.wrap_key_data(kd), lidx,
                pstack, p2, policy_names, l2_policy, estimate_z, n_shards)
    else:
        raise ValueError(f"unknown fabric kind {kind!r}")
    return jax.jit(_mk_shard_map(body, mesh))


def _key_data(keys):
    return jax.random.key_data(keys)


def fabric_sweep_single(mesh, tstack, caps, keys, pstack, policy_name,
                        estimate_z, score_mode, update):
    call = _fabric_call(mesh, "single",
                        (policy_name, estimate_z, score_mode, update))
    return call((caps, _key_data(keys), pstack), (tstack,))


def fabric_sweep_multi(mesh, tstack, caps, keys, lidx, pstack, policy_names,
                       estimate_z, update):
    call = _fabric_call(mesh, "multi", (policy_names, estimate_z, update))
    return call((caps, _key_data(keys), lidx, pstack), (tstack,))


def fabric_hier_single(mesh, tstack, c1s, c2s, keys, pstack, p2, policy_name,
                       l2_policy, estimate_z, n_shards):
    call = _fabric_call(mesh, "hier_single",
                        (policy_name, l2_policy, estimate_z, n_shards))
    return call((c1s, c2s, _key_data(keys), pstack), (tstack, p2))


def fabric_hier_multi(mesh, tstack, c1s, c2s, keys, lidx, pstack, p2,
                      policy_names, l2_policy, estimate_z, n_shards):
    call = _fabric_call(mesh, "hier_multi",
                        (policy_names, l2_policy, estimate_z, n_shards))
    return call((c1s, c2s, _key_data(keys), lidx, pstack), (tstack, p2))
