"""Late-base shift invariance across the roster.

The CDN deployment replays streams whose timestamps start at 1.7e9 s (the
epoch-like ``start_time`` of ``RealWorldSpec`` in repro.data.traces).  A
rebased stream (``simulate_stream``'s default) converts each chunk to
float32 offsets from the chunk's own start, so the same request pattern
must give bitwise the same results at base 0 and at base 1.7e9 — for every
policy, in the dense and the slot-table engine, and with the kernel's
oracle scoring the slot table at any chunking.  The gaps are multiples of
2^-10 s, so both streams hold exactly the same float64 offsets.
"""
import numpy as np
import pytest

from repro.core import simulate_stream
from repro.core.ranking import POLICIES
from repro.core.trace import RequestStream

ALL_POLICIES = sorted(POLICIES)
LATE_BASE = 1.7e9


def _gap_pattern_stream(base_time: float, seed=3, T=500, N=24):
    """A stream with exactly-representable gaps placed at ``base_time``."""
    rng = np.random.default_rng(seed)
    gaps = rng.integers(1, 2000, T) * 2.0 ** -10
    objs = rng.integers(0, N, T).astype(np.int32)
    sizes = rng.integers(1, 8, N).astype(np.float32)
    z_mean = np.full(N, 0.05, np.float32)
    z_draw = (z_mean[objs] * rng.exponential(1.0, T)).astype(np.float32)
    return RequestStream(base_time + np.cumsum(gaps), objs, sizes, z_mean,
                         z_draw)


def _assert_same(a, b, msg=""):
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                      err_msg=msg)


def _early_and_late(policy, **kw):
    early = simulate_stream(_gap_pattern_stream(0.0), 30.0, policy,
                            estimate_z=True, **kw)
    late = simulate_stream(_gap_pattern_stream(LATE_BASE), 30.0, policy,
                           estimate_z=True, **kw)
    return early, late


@pytest.mark.parametrize("state_mode", ["dense", "slots"])
@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_late_base_is_bitwise_invisible(policy, state_mode):
    early, late = _early_and_late(policy, chunk_size=97,
                                  state_mode=state_mode)
    _assert_same(early, late, f"{policy}/{state_mode}")
    assert int(early.n_evictions) > 0


@pytest.mark.parametrize("chunk_size", [7, 97, 500])
def test_late_base_slot_kernel_scoring(chunk_size):
    early, late = _early_and_late("stoch_vacdh", chunk_size=chunk_size,
                                  state_mode="slots", use_kernel="ref")
    _assert_same(early, late, f"chunk={chunk_size}")
    assert int(early.n_evictions) > 0
