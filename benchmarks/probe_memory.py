"""SimState RSS scaling probe (``--only memory``): sparse slot-table vs
dense streamed-replay peak RSS at nominal universe sizes N in {1e4, 1e5,
1e6} (DESIGN.md §14).  ``ru_maxrss`` is a *process-lifetime* high-water
mark, so each (N, mode) cell runs in its own child process
(``--simstate-child``) — measuring dense then slots in one process would
report dense's peak for both.  The dense engine holds 14 O(N) state
columns and scores an O(N) eviction substrate per commit; the slot
engine's table is sized by *distinct-touched* keys, so its RSS is bounded
by the request budget, not the nominal universe.

Importing this module is side-effect free."""
import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
SIMSTATE_SIZES = (10_000, 100_000, 1_000_000)
SIMSTATE_REQUESTS = 60_000      # bounded: RSS is the headline, not req/s

def _simstate_stream(n_keys: int, n_requests: int, seed: int = 0):
    """Zipf(0.9)-over-the-nominal-universe request stream, pure numpy.

    The hot head re-hits (so the cache and eviction paths are exercised)
    while the cold tail spreads touches across the universe — at bounded
    request counts only a fraction of the nominal N keys is ever touched,
    which is exactly the regime the slot table targets."""
    import numpy as np

    from repro.core.trace import RequestStream
    rng = np.random.default_rng(seed)
    r = np.arange(1, n_keys + 1, dtype=np.float64)
    p = r ** -0.9
    p /= p.sum()
    objs = rng.choice(n_keys, size=n_requests, p=p).astype(np.int32)
    times = np.cumsum(rng.exponential(1.0 / 2000.0, n_requests))
    sizes = np.minimum(rng.lognormal(0.0, 1.2, n_keys), 512.0).astype(
        np.float32)
    z_mean = (0.005 + 2e-4 * sizes).astype(np.float32)
    z_draw = (z_mean[objs] * rng.exponential(1.0, n_requests)).astype(
        np.float32)
    return RequestStream(times=times, objs=objs, sizes=sizes,
                         z_mean=z_mean, z_draw=z_draw)


def simstate_child_row(n_keys: int, mode: str, n_requests: int) -> dict:
    """One (universe size, state_mode) measurement — run in a fresh
    process so ``ru_maxrss`` is this configuration's own peak."""
    import resource
    import time

    import jax
    import numpy as np

    from repro.core import PolicyParams, simulate_stream
    from repro.core.state import slot_table_size

    stream = _simstate_stream(n_keys, n_requests)
    touched = np.unique(stream.objs)
    distinct = int(touched.size)
    # 10% of the TOUCHED footprint (not the nominal universe's), so the
    # cache actually fills and evicts — a nominal-footprint capacity would
    # never evict and the dense scoring substrate would stay unexercised
    capacity = 0.1 * float(stream.sizes[touched].sum())
    t0 = time.perf_counter()
    r = simulate_stream(stream, capacity, "stoch_vacdh",
                        PolicyParams(omega=1.0), estimate_z=True,
                        chunk_size=16_384, state_mode=mode)
    lat = float(r.total_latency)
    wall = time.perf_counter() - t0
    dev = jax.devices()[0]
    return dict(
        n_keys=n_keys, mode=mode, n_requests=n_requests,
        platform=dev.platform, device_kind=dev.device_kind,
        distinct_touched=distinct,
        n_slots=slot_table_size(distinct) if mode == "slots" else "",
        capacity=round(capacity, 1), latency=round(lat, 4),
        hit_ratio=round(float(r.hit_ratio), 4),
        wall_s=round(wall, 1), req_per_s=int(n_requests / wall),
        peak_rss_mb=round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1))


def run_simstate_probe(sizes=SIMSTATE_SIZES, n_requests=SIMSTATE_REQUESTS,
                       timeout_s: float = 1800.0) -> list[dict]:
    """Spawn one ``--simstate-child`` per (N, mode) cell and collect rows.

    A cell that dies or times out becomes a labeled failure row rather
    than aborting the probe — the dense 1e6 cell is expected to be the
    painful one (O(N) per-commit substrate on CPU), and recording *that*
    honestly is part of the point."""
    rows = []
    for n in sizes:
        for mode in ("dense", "slots"):
            cmd = [sys.executable, "-m", "benchmarks.probe_memory",
                   "--simstate-child", str(n), mode,
                   "--requests", str(n_requests)]
            try:    # the child inherits this process's platform; this
                # process never touches jax, so a chip is free for it
                proc = subprocess.run(cmd, cwd=REPO_ROOT,
                                      capture_output=True, text=True,
                                      timeout=timeout_s)
            except subprocess.TimeoutExpired:
                rows.append(dict(n_keys=n, mode=mode,
                                 n_requests=n_requests, status="timeout",
                                 timeout_s=int(timeout_s)))
                print(f"# simstate N={n} {mode}: TIMEOUT after "
                      f"{timeout_s:.0f}s", flush=True)
                continue
            marked = [ln for ln in proc.stdout.splitlines()
                      if ln.startswith("SIMSTATE ")]
            if proc.returncode != 0 or not marked:
                tail = (proc.stderr or proc.stdout).strip().splitlines()
                rows.append(dict(n_keys=n, mode=mode,
                                 n_requests=n_requests,
                                 status=f"exit {proc.returncode}"))
                print(f"# simstate N={n} {mode}: FAILED "
                      f"(exit {proc.returncode}): "
                      + " | ".join(tail[-3:]), flush=True)
                continue
            row = dict(json.loads(marked[-1][len("SIMSTATE "):]),
                       status="ok")
            rows.append(row)
            print(f"# simstate N={n} {mode}: rss={row['peak_rss_mb']}MB "
                  f"wall={row['wall_s']}s ({row['req_per_s']} req/s, "
                  f"{row['distinct_touched']} touched)", flush=True)
    from benchmarks.common import emit
    emit(rows, "probe_memory_simstate")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--simstate-child", nargs=2, metavar=("N", "MODE"),
                    default=None, help=argparse.SUPPRESS)
    ap.add_argument("--requests", type=int, default=SIMSTATE_REQUESTS)
    ap.add_argument("--timeout", type=float, default=1800.0,
                    help="per-cell wall-clock budget")
    args = ap.parse_args(argv)

    if args.simstate_child is not None:
        n, mode = args.simstate_child
        row = simstate_child_row(int(n), mode, args.requests)
        print("SIMSTATE " + json.dumps(row), flush=True)
        return
    run_simstate_probe(n_requests=args.requests, timeout_s=args.timeout)


if __name__ == "__main__":
    main()
