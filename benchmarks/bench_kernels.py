"""Kernel micro-benchmarks: wall-clock of the ranking kernel's jnp oracles
(score, and score + top-E victim order) on an 8192-object table.  Pallas
kernels run in interpret mode on CPU, so the timed path is the XLA oracle;
the compiled kernels are timed on the chip by the benchmark cells."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref

from .common import emit


def _time(fn, *args, iters=5):
    """(mean_us, min_us) over ``iters`` timed calls after a compile call.

    ``perf_counter`` (monotonic, highest available resolution — ``time.time``
    is wall-clock and jitters with NTP slews) around *each* call; the min
    is the least-perturbed sample and the number to trend, the mean shows
    scheduler noise on a loaded host."""
    fn(*args)  # compile
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree.map(lambda a: a.block_until_ready(), out)
        samples.append((time.perf_counter() - t0) * 1e6)
    return sum(samples) / iters, min(samples)


def run() -> list[dict]:
    rows = []
    ks = jax.random.split(jax.random.key(0), 5)

    # eviction ranking oracle (paper eq. 16 scores)
    n = 8192
    lam = jax.random.uniform(ks[0], (n,), minval=0.01, maxval=10)
    z = jax.random.uniform(ks[1], (n,), minval=0.001, maxval=1)
    r = jax.random.uniform(ks[2], (n,), minval=0.01, maxval=10)
    sz = jax.random.uniform(ks[3], (n,), minval=1, maxval=100)
    c = jnp.ones((n,), bool)
    fr = jax.jit(lambda *a: ref.ranking_scores_ref(*a, 1.0)[0])
    us, us_min = _time(fr, lam, z, r, sz, c)
    rows.append(dict(name="ranking_ref_8k", us_per_call=round(us, 1),
                     us_min=round(us_min, 1), derived=f"{n/us:.1f}obj/us"))

    # fused rank-and-select oracle (score + masked top-E victim order) —
    # the evict-until-fit loop's precomputed diet (DESIGN.md §10)
    fs = jax.jit(lambda *a: ref.victim_order_ref(
        ref.ranking_scores_ref(*a, 1.0)[0], a[4], 8))
    us, us_min = _time(fs, lam, z, r, sz, c)
    rows.append(dict(name="rank_select_ref_8k", us_per_call=round(us, 1),
                     us_min=round(us_min, 1), derived=f"top8"))
    return rows


def main():
    emit(run(), "bench_kernels")


if __name__ == "__main__":
    main()
