"""Compile rehearsals for a TPU v5e that is described, not attached.

Interpret-mode parity (tests/test_kernels.py) cannot see what the TPU
compiler refuses: block shapes outside the 8x128 tiling rule, scalar
stores into vector memory, kernels that do not fit fast memory.  These
tests lower and compile the hot path's Pallas kernels, and the streamed
replay's chunk steps (dense, and the slot table's) with the scoring kernel
inside their commit loops, for one chip of a described ``v5e:2x2`` topology at real sizes.  Nothing runs;
a compile that passes says nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and under several test
workers only the worker that is given this file should.  The persistent
compile cache is off around these compiles (an entry written for a chip
cannot be read back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import PolicyParams
from repro.core.simulator import _chunk_step_jit, _slot_chunk_step_jit
from repro.core.state import init_slot_state, init_state
from repro.kernels.lane_scatter import lane_scatter_add, lane_scatter_set
from repro.kernels.ranking_score import ranking_scores, ranking_victim_order


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip; the kernel must be a real Mosaic
    custom call, not the Pallas interpreter's HLO."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _block_copies_in_loops(text: str, block: str) -> list[str]:
    """Copies of the whole per-object state block (HLO shape ``block``)
    outside the entry computation, i.e. inside the scan and commit loops:
    a read of the block still live after a column write makes the
    compiler copy all of it, once a request."""
    copies, entry = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            entry = line.startswith("ENTRY")
        elif (not entry and re.search(r" copy(-start)?\(", line)
              and block + "{" in line):
            copies.append(line.strip()[:160])
    return copies


@pytest.mark.parametrize("n", [4096, 200_000])
def test_ranking_victim_order_compiles(one_chip, n):
    streams = [_spec(one_chip, (n,), jnp.float32)] * 4
    cached = _spec(one_chip, (n,), jnp.bool_)
    _compile(lambda *a: ranking_victim_order(*a, omega=1.0, top=8),
             *streams, cached)


@pytest.mark.parametrize("n,steps", [(4096, 1), (131_072, 8)])
def test_ranking_scores_compiles(one_chip, n, steps):
    """At the default block the custom call writes one (8, 128) candidate
    tile a grid step: 8 steps over the slot engine's 131 072 slots."""
    streams = [_spec(one_chip, (n,), jnp.float32)] * 4
    cached = _spec(one_chip, (n,), jnp.bool_)
    compiled = _compile(lambda *a: ranking_scores(*a, omega=1.0),
                        *streams, cached)
    call = re.search(r"= \(f32\[\d+,128\][^ ]* f32\[(\d+),128\].*"
                     r"custom-call", compiled.as_text())
    assert call and int(call.group(1)) == 8 * steps


@pytest.mark.parametrize("scatter", [lane_scatter_set, lane_scatter_add],
                         ids=["set", "add"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bool_],
                         ids=["f32", "bool"])
def test_lane_scatter_compiles(one_chip, scatter, dtype):
    lanes, n = 11, 3000
    _compile(scatter, _spec(one_chip, (lanes, n), dtype),
             _spec(one_chip, (lanes,), jnp.int32),
             _spec(one_chip, (lanes,), dtype))


def test_stream_chunk_step_with_kernel_compiles(one_chip):
    """One 131072-request chunk over 4096 objects, eq.-16 scoring through
    the compiled victim-order kernel inside the commit loop's lax.cond."""
    n, chunk = 4096, 131_072
    spec = lambda s: _spec(one_chip, s.shape, s.dtype)
    state = jax.tree.map(spec, jax.eval_shape(
        lambda: init_state(n, jnp.float32(100.0), jax.random.key(0),
                           jnp.zeros((n,), jnp.float32))))
    params = jax.tree.map(lambda x: _spec(one_chip, jnp.shape(x),
                                          jnp.result_type(x)),
                          PolicyParams(omega=1.0))
    f32 = lambda *s: _spec(one_chip, s, jnp.float32)
    compiled = _compile(
        lambda st, t, o, z, d, sz, p: _chunk_step_jit(
            st, t, o, z, None, d, sz, p, "stoch_vacdh", True, "kernel"),
        state, f32(chunk), _spec(one_chip, (chunk,), jnp.int32), f32(chunk),
        f32(), f32(n), params)
    assert compiled.memory_analysis() is not None
    assert not _block_copies_in_loops(compiled.as_text(), "f32[14,32,128]")


@pytest.mark.parametrize("policy", ["stoch_vacdh", "lru"])
def test_paper_scale_chunk_step_keeps_one_state_block(one_chip, policy):
    """The Fig. 2 replay's chunk step (100 objects, 25000 requests, a flat
    [14, 100] block): every column write updates the block in place."""
    n, chunk = 100, 25_000
    spec = lambda s: _spec(one_chip, s.shape, s.dtype)
    state = jax.tree.map(spec, jax.eval_shape(
        lambda: init_state(n, jnp.float32(500.0), jax.random.key(0),
                           jnp.zeros((n,), jnp.float32))))
    params = jax.tree.map(lambda x: _spec(one_chip, jnp.shape(x),
                                          jnp.result_type(x)),
                          PolicyParams(omega=1.0))
    f32 = lambda *s: _spec(one_chip, s, jnp.float32)
    mode = "kernel" if policy == "stoch_vacdh" else "rank"
    compiled = jax.jit(
        lambda st, t, o, z, d, sz, p: _chunk_step_jit(
            st, t, o, z, None, d, sz, p, policy, True, mode)).lower(
        state, f32(chunk), _spec(one_chip, (chunk,), jnp.int32), f32(chunk),
        f32(), f32(n), params).compile()
    assert not _block_copies_in_loops(compiled.as_text(), "f32[14,100]")


def test_slot_chunk_step_with_kernel_compiles(one_chip):
    """The exact CDN replay's chunk step: 25000 requests over a 131072-slot
    table and a 200000-key universe, the table's probe and insert, and
    eq.-16 scoring through the compiled ``ranking_scores`` kernel."""
    slots, chunk, universe = 131_072, 25_000, 200_000
    spec = lambda s: _spec(one_chip, s.shape, s.dtype)
    state = jax.tree.map(spec, jax.eval_shape(
        lambda: init_slot_state(slots, jnp.float32(939.5),
                                jax.random.key(0))))
    params = jax.tree.map(lambda x: _spec(one_chip, jnp.shape(x),
                                          jnp.result_type(x)),
                          PolicyParams(omega=1.0))
    f32 = lambda *s: _spec(one_chip, s, jnp.float32)
    compiled = _compile(
        lambda st, t, o, z, d, sz, zp, p: _slot_chunk_step_jit(
            st, t, o, z, None, d, sz, zp, p, "stoch_vacdh", True, "kernel"),
        state, f32(chunk), _spec(one_chip, (chunk,), jnp.int32), f32(chunk),
        f32(), f32(universe), f32(universe), params)
    assert "ranking_scores" in compiled.as_text()
    assert not _block_copies_in_loops(compiled.as_text(), "f32[14,1024,128]")
