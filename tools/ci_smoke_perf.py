"""CI long-trace smoke throughput recorder + floor check + bench-JSON lint.

Runs a 100k-request generated-realistic trace through the streaming chunked
engine (the same workload as the ``slow``-marked smoke test), writes the
measured wall-clock / req/s / peak RSS to a JSON artifact, and exits
non-zero if throughput falls below a *generous* floor — a hot-path
regression canary, not a benchmark: shared CI runners are noisy, so the
floor is set >=10x below the 2-vCPU dev-container measurement
(EXPERIMENTS.md §Perf iteration 6: ~87k req/s streamed on the dev
container — hence the 5k default, raised from the historical 2k, which
the container now clears by ~17x).
Override the floor / output path via ``--floor`` / ``--out``
(``--floor 0`` records without asserting).

``--check-bench`` instead lints the repo-root perf-trajectory snapshots
(``BENCH_stream.json`` / ``BENCH_sweep.json`` / ``BENCH_serving.json``):
schema keys present, history entries well-formed (sha + date + at least
one numeric headline), and the canary rows that future PRs diff against
(the N=3000 roster pair, the streamed-vs-device stoch_vacdh pair, the
serving benchmark's scenario x hedging tail grid with its SLO-search and
hierarchy rows) actually exist — so a benchmark refactor cannot silently
stop recording the trajectory.  It additionally gates the
``roster3000_unified_over_sequential`` canary *trend*: the latest summary
value must be numeric and must not fall below the best value the history
has ever recorded by more than ``TREND_TOLERANCE`` (the ISSUE-9 grouped
commit dispatch flipped this ratio past 1.0; a silent slide back to the
lockstep-union 0.54x regime is exactly what this catches).

The default smoke also runs a bounded million-object slot-table replay in
a child process (probe_memory's subprocess pattern: ``ru_maxrss`` is a
process-lifetime high-water mark, so the cell needs its own process) and
fails if its peak RSS exceeds ``--rss-ceiling-mb`` — the scale claim of
DESIGN.md §14 stated as a CI invariant.

Usage: PYTHONPATH=src python tools/ci_smoke_perf.py [--floor REQ_S]
       PYTHONPATH=src python tools/ci_smoke_perf.py --check-bench
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

DEFAULT_FLOOR = 5_000        # req/s; dev-container measures ~87k
N_REQUESTS = 100_000
CHUNK_SIZE = 16_384

# canary-trend gate: the latest roster3000_unified_over_sequential may sit
# at most this fraction below the best history value (shared runners are
# noisy; a real regression to the lockstep-union regime is a ~2.5x drop)
TREND_TOLERANCE = 0.25

# bounded million-object slot-mode smoke (child process); the dev
# container measures ~233 MB peak — the ceiling is ~4x that, generous for
# runner noise but far below the dense engine's multi-GB footprint at 1M
SLOTS_SMOKE_KEYS = 1_000_000
SLOTS_SMOKE_REQUESTS = 30_000
DEFAULT_RSS_CEILING_MB = 1_024


def _fail(msg: str) -> None:
    raise SystemExit(f"BENCH SCHEMA FAIL: {msg}")


def _check_history(payload: dict, name: str) -> None:
    hist = payload.get("history")
    if not isinstance(hist, list) or not hist:
        _fail(f"{name}: missing/empty 'history' (the perf trajectory)")
    for i, entry in enumerate(hist):
        if not isinstance(entry, dict):
            _fail(f"{name}: history[{i}] is not an object")
        for key in ("sha", "date_utc"):
            if not isinstance(entry.get(key), str) or not entry[key]:
                _fail(f"{name}: history[{i}] lacks a non-empty '{key}'")
        nums = [v for k, v in entry.items()
                if k not in ("sha", "date_utc")
                and isinstance(v, (int, float))]
        if not nums:
            _fail(f"{name}: history[{i}] has no numeric headline field")


def _serving_canary(p: dict) -> bool:
    """The serving tail grid: >= 2 scenarios x {hedging on, off} single-tier
    rows with numeric p50/p99, plus hierarchy-mode and SLO-search rows —
    the surface every future SLO/robustness claim is measured on.  Since
    the fault-tolerance layer (DESIGN.md §15) the grid must also carry
    both replica scenarios — degraded_replica and origin_outage rows with
    a numeric shed_rate and n_replicas >= 2 — and the brownout-flip
    headline: a degraded_replica SLO-search row with a numeric
    req/s-at-SLO (the row PR 6 recorded as unattainable single-origin)."""
    rows = p.get("rows", [])
    single = {(r.get("scenario"), r.get("hedging")) for r in rows
              if r.get("mode") == "single"
              and isinstance(r.get("p50_ms"), (int, float))
              and isinstance(r.get("p99_ms"), (int, float))
              and isinstance(r.get("p999_ms"), (int, float))}
    scenarios = {s for s, _ in single}
    both_hedge = {s for s in scenarios
                  if (s, True) in single and (s, False) in single}
    replica_ok = all(any(
        r.get("mode") == "single" and r.get("scenario") == s
        and isinstance(r.get("shed_rate"), (int, float))
        and isinstance(r.get("fail_rate"), (int, float))
        and isinstance(r.get("n_replicas"), int) and r["n_replicas"] >= 2
        for r in rows) for s in ("degraded_replica", "origin_outage"))
    flip_ok = any(r.get("mode") == "slo_search"
                  and r.get("scenario") == "degraded_replica"
                  and isinstance(r.get("req_s_at_slo"), (int, float))
                  for r in rows)
    return (len(both_hedge) >= 2
            and any(r.get("mode") == "hier" for r in rows)
            and any(r.get("mode") == "slo_search"
                    and isinstance(r.get("req_s_at_slo"), (int, float))
                    for r in rows)
            and replica_ok and flip_ok
            and isinstance(p.get("depth_hists"), dict)
            and len(p["depth_hists"]) > 0)


def _sweep_canary(p: dict) -> bool:
    """The N=3000 lockstep-union rows (the carried-miss baseline) plus the
    multi-device fabric's device-scaling rows (DESIGN.md §13): every
    SCALING_COUNTS device count with a numeric warm wall-clock, and the
    d4-vs-d1 speedup in the summary so the trajectory records whether
    lane-sharding pays (or honestly doesn't) on each machine."""
    rows = p.get("rows", [])
    fabric = {r.get("devices") for r in rows
              if str(r.get("name", "")).startswith("fabric_d")
              and isinstance(r.get("warm_s"), (int, float))}
    return ({r.get("name") for r in rows}
            >= {"roster3000_unified", "roster3000_sequential"}
            and fabric >= {1, 2, 4}
            and isinstance(p.get("summary", {})
                           .get("fabric_d4_speedup_over_d1"), (int, float)))


def _check_sweep_trend(payload: dict, tol: float = TREND_TOLERANCE) -> None:
    """Gate the unified-vs-sequential canary's *trajectory*, not just its
    presence: the latest ``roster3000_unified_over_sequential`` must be
    numeric and must not regress below the best value history has ever
    recorded by more than ``tol`` (relative).  History entries predating
    the canary (or non-numeric ones) are skipped, so the gate tightens
    itself as better measurements land — recording an improvement raises
    the bar for every later PR."""
    key = "roster3000_unified_over_sequential"
    cur = payload.get("summary", {}).get(key)
    if not isinstance(cur, (int, float)):
        _fail(f"BENCH_sweep.json: summary lacks a numeric '{key}'")
    recorded = [e[key] for e in payload.get("history", [])
                if isinstance(e.get(key), (int, float))]
    if not recorded:
        _fail(f"BENCH_sweep.json: no history entry records '{key}' — "
              f"the canary trend has no baseline")
    best = max(recorded)
    floor = best * (1.0 - tol)
    if cur < floor:
        _fail(f"BENCH_sweep.json: {key}={cur:.3f} regressed below "
              f"{floor:.3f} (best recorded {best:.3f} minus {tol:.0%} "
              f"tolerance) — the commit-dispatch canary is sliding back "
              f"toward the lockstep-union regime")
    print(f"OK: {key}={cur:.3f} within {tol:.0%} of best recorded "
          f"({best:.3f})")


def check_bench_schemas(root: Path = REPO_ROOT) -> None:
    """Validate the repo-root BENCH_*.json trajectory files (see module
    docstring).  Raises SystemExit with a message on the first violation."""
    for fname, canary in (
        ("BENCH_stream.json",
         lambda p: {r.get("policy") for r in p.get("rows", [])}
         >= {"lru", "stoch_vacdh"} and p.get("device_mode")),
        ("BENCH_sweep.json", _sweep_canary),
        ("BENCH_serving.json", _serving_canary),
    ):
        path = root / fname
        if not path.exists():
            _fail(f"{fname} missing at repo root")
        try:
            payload = json.loads(path.read_text())
        except ValueError as e:
            _fail(f"{fname}: not valid JSON ({e})")
        for key in ("benchmark", "rows", "generated_utc", "backend"):
            if key not in payload:
                _fail(f"{fname}: missing top-level key '{key}'")
        if not canary(payload):
            _fail(f"{fname}: canary rows absent — the trajectory would "
                  f"silently lose its regression baseline")
        _check_history(payload, fname)
        if fname == "BENCH_sweep.json":
            _check_sweep_trend(payload)
    print("OK: bench JSON schemas valid (canary rows + history present)")


def run_slots_smoke(rss_ceiling_mb: float,
                    timeout_s: float = 900.0) -> dict:
    """Bounded million-object slot-mode streamed replay in a child process;
    returns the child's measurement row and fails hard on an RSS breach."""
    import subprocess
    cmd = [sys.executable, "-m", "benchmarks.probe_memory",
           "--simstate-child", str(SLOTS_SMOKE_KEYS), "slots",
           "--requests", str(SLOTS_SMOKE_REQUESTS)]
    proc = subprocess.run(cmd, cwd=REPO_ROOT, capture_output=True,
                          text=True, timeout=timeout_s)
    marked = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("SIMSTATE ")]
    if proc.returncode != 0 or not marked:
        tail = (proc.stderr or proc.stdout).strip().splitlines()
        raise SystemExit("SLOTS SMOKE FAIL: child exited "
                         f"{proc.returncode}: " + " | ".join(tail[-3:]))
    row = json.loads(marked[-1][len("SIMSTATE "):])
    rss = row["peak_rss_mb"]
    if rss_ceiling_mb and rss > rss_ceiling_mb:
        raise SystemExit(
            f"SLOTS SMOKE FAIL: peak RSS {rss:.0f} MB over the "
            f"{rss_ceiling_mb:.0f} MB ceiling for a "
            f"{SLOTS_SMOKE_KEYS // 10**6}M-key slot-mode replay — the "
            f"bounded-residency claim of DESIGN.md §14 no longer holds")
    print(f"OK: slots smoke ({SLOTS_SMOKE_KEYS // 10**6}M keys, "
          f"{SLOTS_SMOKE_REQUESTS} requests) peak RSS {rss:.0f} MB <= "
          f"{rss_ceiling_mb:.0f} MB ceiling")
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--floor", type=float, default=DEFAULT_FLOOR,
                    help="minimum acceptable req/s (0 disables the assert)")
    ap.add_argument("--out", default="smoke_perf.json",
                    help="JSON artifact path")
    ap.add_argument("--policy", default="stoch_vacdh")
    ap.add_argument("--check-bench", action="store_true",
                    help="lint BENCH_*.json trajectory files and exit")
    ap.add_argument("--rss-ceiling-mb", type=float,
                    default=DEFAULT_RSS_CEILING_MB,
                    help="peak-RSS ceiling for the million-object slots "
                         "smoke (0 records without asserting)")
    ap.add_argument("--no-slots-smoke", action="store_true",
                    help="skip the million-object slot-mode child replay")
    args = ap.parse_args()

    if args.check_bench:
        check_bench_schemas()
        return 0

    from benchmarks.common import write_bench_json
    from repro.core import PolicyParams, simulate_stream
    from repro.data.traces import (RealWorldSpec, compact_requests,
                                   realworld_raw)

    t0 = time.perf_counter()
    raw = realworld_raw(RealWorldSpec(n_requests=N_REQUESTS, n_keys=20_000,
                                      start_time=1.7e9))
    stream, stats = compact_requests(raw, top_k=2000, n_recycle=128)
    gen_s = time.perf_counter() - t0

    # first replay pays compile; the timed replay measures the hot path
    simulate_stream(stream, 500.0, args.policy, PolicyParams(omega=1.0),
                    estimate_z=True, chunk_size=CHUNK_SIZE)
    t0 = time.perf_counter()
    r = simulate_stream(stream, 500.0, args.policy, PolicyParams(omega=1.0),
                        estimate_z=True, chunk_size=CHUNK_SIZE)
    float(r.total_latency)
    wall = time.perf_counter() - t0
    req_s = N_REQUESTS / wall

    # million-object slot-mode replay in a child process: asserts the
    # DESIGN.md §14 bounded-RSS claim and rides along in the artifact
    slots_row = (None if args.no_slots_smoke
                 else run_slots_smoke(args.rss_ceiling_mb))

    # same schema/stamping as the BENCH_*.json trajectory files
    path = write_bench_json("smoke_perf.json", dict(
        benchmark="ci_long_trace_smoke",
        policy=args.policy,
        n_requests=N_REQUESTS,
        n_objects=stats.n_objects,
        chunk_size=CHUNK_SIZE,
        gen_s=round(gen_s, 2),
        sim_wall_s=round(wall, 2),
        req_per_s=int(req_s),
        floor_req_per_s=int(args.floor),
        peak_rss_mb=round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1),
        hit_ratio=round(float(r.hit_ratio), 4),
        slots_smoke=slots_row,
        slots_rss_ceiling_mb=args.rss_ceiling_mb,
    ), path=args.out)
    print(json.dumps(json.loads(path.read_text()), indent=2))

    if args.floor and req_s < args.floor:
        print(f"FAIL: {req_s:.0f} req/s below the {args.floor:.0f} req/s "
              f"floor — hot-path regression (or an unusually starved "
              f"runner; re-run to confirm)", file=sys.stderr)
        return 1
    print(f"OK: {req_s:.0f} req/s >= {args.floor:.0f} req/s floor")
    return 0


if __name__ == "__main__":
    sys.exit(main())
