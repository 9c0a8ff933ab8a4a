"""Benchmark driver: one function per paper table/figure.
Prints ``name,us_per_call,derived``-style CSV per benchmark and writes
benchmarks/results/*.csv.  --full reproduces the paper-scale settings.
The ``realworld`` and ``sweep`` jobs additionally write machine-readable
perf-trajectory snapshots (``BENCH_stream.json`` / ``BENCH_sweep.json``)
at the repo root so future PRs can diff req/s, wall-clock, and peak RSS
without re-reading EXPERIMENTS prose.

XLA's persistent compilation cache is enabled
(:func:`repro.launch.compile_cache.enable_compile_cache`: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache/`` at the repo
root) so repeat invocations skip graph compiles — the sweep engine's
unified graphs (one per figure) make the cache small and stable across
runs (EXPERIMENTS.md §Perf records cold vs warm-cache)."""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path


def _run_memory_probe() -> None:
    import subprocess

    # two probes, both subprocess-isolated: the SimState RSS scaling rows
    # (sparse slots vs dense at N in {1e4,1e5,1e6} — each cell is its own
    # child so ru_maxrss is per-configuration) and the model-stack HLO
    # forensics (must set XLA_FLAGS for 512 host devices before jax
    # initializes, which cannot happen in this process)
    for extra in (["--simstate"], ["--layers", "2"]):
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.probe_memory", *extra],
            cwd=Path(__file__).parent.parent)
        if proc.returncode != 0:
            raise RuntimeError(f"probe_memory {extra[0]} exited "
                               f"{proc.returncode}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slower)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,fig3,fig4,fig5,fig6,realworld,"
                         "kernels,sweep,serving,memory (memory runs only "
                         "when explicitly selected)")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="disable the persistent XLA compilation cache")
    args = ap.parse_args()
    if not args.no_compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    want = set(args.only.split(",")) if args.only else None

    from . import (bench_kernels, bench_serving, bench_sweep, fig2_synthetic,
                   fig3_trace_stats, fig4_sensitivity, fig5_real_traces,
                   fig6_hierarchy, fig_realworld)
    from .common import emit

    jobs = [
        # memory probes (probe_memory.py): SimState RSS scaling rows
        # (slots vs dense) + model-stack HLO forensics, both as
        # subprocesses (see _run_memory_probe).  First in line: on a chip
        # host the children need the chip, which this process holds from
        # its first jax computation on.  Opt-in only (--only memory): the
        # cells compile and the dense million-object replay is out of the
        # cache-benchmark jobs' wall-clock budget.
        ("memory", _run_memory_probe),
        ("fig3", lambda: emit(fig3_trace_stats.run(), "fig3_trace_stats")),
        ("fig2", lambda: emit(fig2_synthetic.run(full=args.full),
                              "fig2_synthetic")),
        ("fig4", lambda: emit(fig4_sensitivity.run(full=args.full),
                              "fig4_sensitivity")),
        ("fig5", lambda: emit(fig5_real_traces.run(full=args.full),
                              "fig5_real_traces")),
        ("fig6", lambda: emit(fig6_hierarchy.run(full=args.full),
                              "fig6_hierarchy")),
        ("realworld", lambda: emit(fig_realworld.run(full=args.full),
                                   "fig_realworld")),
        ("kernels", lambda: emit(bench_kernels.run(), "bench_kernels")),
        # realworld/sweep also refresh the BENCH_stream.json /
        # BENCH_sweep.json perf-trajectory snapshots at the repo root
        ("sweep", lambda: emit(bench_sweep.run(full=args.full),
                               "bench_sweep")),
        # closed-loop serving tails: appends BENCH_serving.json history
        ("serving", lambda: emit(bench_serving.run(full=args.full),
                                 "bench_serving")),
    ]
    for name, fn in jobs:
        if want is None and name == "memory":
            continue
        if want and name not in want:
            continue
        print(f"\n=== {name} ===")
        t0 = time.time()
        fn()
        print(f"[{name}] done in {time.time()-t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
