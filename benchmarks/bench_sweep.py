"""Sweep-engine dispatch benchmark: unified multi-policy graph vs
sequential per-policy dispatch, and the PR-1 omega-sweep target, re-measured
on the overhauled hot path (shared-substrate scoring — DESIGN.md §10).

Two questions, answered with warm-graph wall-clock (compile excluded and
reported separately, since the persistent XLA cache makes it a one-time
cost):

* **roster**: is ONE unified multi-policy call still slower than a python
  loop of statically specialized per-policy calls on this hardware?  This
  was EXPERIMENTS §Perf's "lockstep union penalty" — the unified graph used
  to stack all P rank functions per commit; with the substrate/epilogue
  split it computes one estimator pass + P cheap epilogues.
* **omega**: batched omega-grid sweep vs a sequential per-point loop
  (PR 1's ≥5× target workload).

A third question since the multi-device fabric (DESIGN.md §13) landed:
does sharding the lane axis over D devices pay on this hardware?  Real
meshes need ``XLA_FLAGS=--xla_force_host_platform_device_count`` before
jax initializes, so the device-scaling section spawns itself as
``--scaling-child D`` subprocesses (one forced-device jax per count) and
collates their rows; ``--devices D`` instead routes *this* process's
sweeps through the fabric (CI's multi-device-smoke row sets the flag in
the job env and runs ``--devices 4 --no-scaling``).

Writes ``BENCH_sweep.json`` at the repo root (machine-readable perf
trajectory) plus the usual CSV row dump.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time

import jax

from repro.core import PolicyParams, simulate, sweep_grid
from repro.data.traces import SyntheticSpec, synthetic_trace

from .common import (POLICY_SET, REPO_ROOT, block_until_ready_tree, emit,
                     forced_device_env, write_bench_json)

ITERS = 3
SCALING_COUNTS = (1, 2, 4)


def _scaling_workload(full: bool):
    """A lane-rich omega x capacity grid (24 lanes, divisible by every
    SCALING_COUNTS entry) — wide enough that sharding has lanes to win."""
    n_req = 30_000 if full else 10_000
    spec = SyntheticSpec(n_objects=100, n_requests=n_req, rate=2000.0,
                         latency_base=0.02, latency_per_mb=5e-4,
                         stochastic=True)
    trace = synthetic_trace(jax.random.key(5), spec)
    plist = [PolicyParams(omega=o)
             for o in (0.0, 0.25, 0.5, 0.75, 1.0, 1.5, 2.0, 3.0)]
    caps = [300.0, 500.0, 800.0]
    return trace, caps, plist, n_req


def scaling_child(d: int, full: bool) -> dict:
    """Measure one device count in THIS process (on the CPU the parent
    forced the fake-device flag into our env before jax initialized)."""
    trace, caps, plist, n_req = _scaling_workload(full)

    def grid():
        return sweep_grid(trace, caps, "stoch_vacdh", plist,
                          devices=d).result

    first, warm, wmin = _timed(grid)
    sims = len(plist) * len(caps) * n_req
    dev = jax.devices()[0]
    return dict(name=f"fabric_d{d}", mode=f"lane axis over {d} device(s)",
                n_lanes=len(plist) * len(caps), devices=d,
                platform=dev.platform, device_kind=dev.device_kind,
                first_call_s=round(first, 3), warm_s=round(warm, 3),
                warm_min_s=round(wmin, 3), req_per_s=int(sims / warm))


def run_scaling(full: bool) -> list[dict]:
    """Device-scaling rows, one per count.  On a chip host they run in this
    process over the real devices (the chip belongs to this process; a
    count above the host's devices fails with the fabric's message).  On
    the CPU each count runs in a subprocess with max(SCALING_COUNTS) fake
    host devices forced in its env."""
    if jax.default_backend() != "cpu":
        return [scaling_child(d, full) for d in SCALING_COUNTS]
    rows = []
    for d in SCALING_COUNTS:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_sweep",
             "--scaling-child", str(d)] + (["--full"] if full else []),
            capture_output=True, text=True, timeout=1200, cwd=REPO_ROOT,
            env=forced_device_env(max(SCALING_COUNTS)))
        if proc.returncode != 0:
            raise RuntimeError(
                f"scaling child d={d} failed:\n{proc.stderr[-4000:]}")
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("SCALING_ROW ")][-1]
        rows.append(json.loads(line[len("SCALING_ROW "):]))
    return rows


def _timed(fn, iters: int = ITERS):
    """(first_call_s, warm_mean_s, warm_min_s) — first call pays compile."""
    t0 = time.perf_counter()
    block_until_ready_tree(fn())
    first = time.perf_counter() - t0
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        block_until_ready_tree(fn())
        samples.append(time.perf_counter() - t0)
    return first, sum(samples) / iters, min(samples)


def run(full: bool = False, devices: int | None = None,
        scaling: bool = True, out: str | None = None,
        smoke: bool = False) -> list[dict]:
    n_req = 30_000 if full else (4_000 if smoke else 10_000)
    spec = SyntheticSpec(n_objects=100, n_requests=n_req, rate=2000.0,
                         latency_base=0.02, latency_per_mb=5e-4,
                         stochastic=True)
    trace = synthetic_trace(jax.random.key(5), spec)
    cap = 500.0
    params = PolicyParams(omega=1.0)
    rows = []

    # --- full-roster: unified one-call vs sequential per-policy ----------
    names = list(POLICY_SET)

    def unified():
        return sweep_grid(trace, cap, names, [params],
                          devices=devices).result

    def sequential():
        return [sweep_grid(trace, cap, pol, [params],
                           devices=devices).result
                for pol in names]

    u_first, u_warm, u_min = _timed(unified)
    s_first, s_warm, s_min = _timed(sequential)
    sims = len(names) * n_req
    rows += [
        dict(name="roster_unified", mode="one multi-policy call",
             n_policies=len(names), first_call_s=round(u_first, 3),
             warm_s=round(u_warm, 3), warm_min_s=round(u_min, 3),
             req_per_s=int(sims / u_warm)),
        dict(name="roster_sequential", mode="per-policy loop",
             n_policies=len(names), first_call_s=round(s_first, 3),
             warm_s=round(s_warm, 3), warm_min_s=round(s_min, 3),
             req_per_s=int(sims / s_warm)),
    ]

    # --- large-N roster: the fig2/fig5 regime ----------------------------
    # the substrate split removed the rank-stack term of the lockstep
    # penalty and the lane-scatter lowering the serve-write term; what
    # remains is the lockstep-union commit scoring (DESIGN.md §11) — this
    # section keeps that regime honest in the trajectory (the N=3000
    # canary row).  Skipped in --smoke (CI's bounded multi-device run):
    # the N=3000 graphs dominate the wall-clock
    if not smoke:
        nspec = SyntheticSpec(n_objects=3000, n_requests=n_req, rate=2000.0,
                              latency_base=0.02, latency_per_mb=5e-4,
                              stochastic=True)
        ntrace = synthetic_trace(jax.random.key(5), nspec)

        def unified_n():
            return sweep_grid(ntrace, 1500.0, names, [params],
                              devices=devices).result

        def sequential_n():
            return [sweep_grid(ntrace, 1500.0, pol, [params],
                               devices=devices).result
                    for pol in names]

        # 2 warm iters (not the default 3): the N=3000 graphs are the
        # slowest rows, and warm_min_s is what the summary/canary reads —
        # one sample was measured ±30% noisy on the 2-vCPU container
        un_first, un_warm, un_min = _timed(unified_n, iters=2)
        sn_first, sn_warm, sn_min = _timed(sequential_n, iters=2)
        sims = len(names) * n_req
        rows += [
            dict(name="roster3000_unified", mode="one multi-policy call",
                 n_policies=len(names), first_call_s=round(un_first, 3),
                 warm_s=round(un_warm, 3), warm_min_s=round(un_min, 3),
                 req_per_s=int(sims / un_warm)),
            dict(name="roster3000_sequential", mode="per-policy loop",
                 n_policies=len(names), first_call_s=round(sn_first, 3),
                 warm_s=round(sn_warm, 3), warm_min_s=round(sn_min, 3),
                 req_per_s=int(sims / sn_warm)),
        ]

    # --- omega sweep: batched grid vs sequential per-point ---------------
    omegas = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0)
    plist = [PolicyParams(omega=o) for o in omegas]

    def batched():
        return sweep_grid(trace, cap, "stoch_vacdh", plist,
                          devices=devices).result

    def per_point():
        return [simulate(trace, cap, "stoch_vacdh", p) for p in plist]

    b_first, b_warm, b_min = _timed(batched)
    p_first, p_warm, p_min = _timed(per_point)
    sims = len(omegas) * n_req
    rows += [
        dict(name="omega_batched", mode="one batched grid",
             n_points=len(omegas), first_call_s=round(b_first, 3),
             warm_s=round(b_warm, 3), warm_min_s=round(b_min, 3),
             req_per_s=int(sims / b_warm)),
        dict(name="omega_sequential", mode="per-point loop",
             n_points=len(omegas), first_call_s=round(p_first, 3),
             warm_s=round(p_warm, 3), warm_min_s=round(p_min, 3),
             req_per_s=int(sims / p_warm)),
    ]

    by = {r["name"]: r for r in rows}

    def _ratio(num, den):
        return round(by[num]["warm_s"] / max(by[den]["warm_s"], 1e-9), 3)

    summary = dict(
        roster_unified_over_sequential=_ratio("roster_sequential",
                                              "roster_unified"),
        omega_batched_over_sequential=_ratio("omega_sequential",
                                             "omega_batched"))
    if "roster3000_unified" in by:
        summary["roster3000_unified_over_sequential"] = _ratio(
            "roster3000_sequential", "roster3000_unified")

    # --- device scaling: fabric lane-sharding vs single device ----------
    # fake host devices on 2 vCPU oversubscribe the cores, so >1 here is a
    # real win and <1 an honest negative — both belong in the trajectory
    if scaling:
        srows = run_scaling(full)
        rows += srows
        warm = {r["devices"]: r["warm_s"] for r in srows}
        summary["fabric_d4_speedup_over_d1"] = round(
            warm[1] / max(warm[4], 1e-9), 3)

    headline = dict(summary)
    if "roster3000_unified" in by:
        headline["roster3000_unified_req_per_s"] = \
            by["roster3000_unified"]["req_per_s"]
    write_bench_json("BENCH_sweep.json", dict(
        benchmark="bench_sweep",
        workload=dict(n_objects=spec.n_objects,
                      n_objects_large=None if smoke else 3000,
                      n_requests=n_req, capacity=cap, roster=names,
                      omegas=list(omegas), devices=devices,
                      scaling_counts=list(SCALING_COUNTS) if scaling
                      else None),
        rows=rows,
        summary=summary,
    ), path=out, headline=headline)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--devices", type=int, default=None,
                    help="route this process's sweeps through the fabric "
                         "(needs XLA_FLAGS-forced devices already in env)")
    ap.add_argument("--no-scaling", action="store_true",
                    help="skip the subprocess device-scaling section")
    ap.add_argument("--out", default=None,
                    help="write the JSON snapshot here instead of the "
                         "repo-root BENCH_sweep.json (CI smoke keeps the "
                         "checkout clean)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: 4k requests, no N=3000 section")
    ap.add_argument("--scaling-child", type=int, default=None,
                    metavar="D", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.scaling_child is not None:
        row = scaling_child(args.scaling_child, full=args.full)
        print("SCALING_ROW " + json.dumps(row))
        return
    emit(run(full=args.full, devices=args.devices,
             scaling=not args.no_scaling, out=args.out, smoke=args.smoke),
         "bench_sweep")


if __name__ == "__main__":
    main()
