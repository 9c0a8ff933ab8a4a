#!/usr/bin/env python3
"""Bring-up smoke test: drive the simulator, the sweep engine and the
serving engine once on a TPU, through their public entry points, and check
what comes out.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sweep fabric only

Phases on one chip (each prints one line):

* ``stream_replay`` — the 1M-request real-world-scale trace (Zipf 0.9 over
  200k keys, generated from a seed), compacted to 4096 + 512 dense objects
  and replayed through ``simulate_stream`` with the compiled Pallas
  victim-order kernel: eight 131072-request chunks, donated carry,
  double-buffered prefetch.
* ``oracle`` — on a prefix of the same stream, the chip's ``simulate``
  against the numpy event oracle ``simulate_ref`` (counters equal, total
  latency within rtol 2e-4), kernel scoring against the jnp rank path
  (rtol 1e-6, equal hits and evictions), and whether the chip's result is
  bitwise the CPU backend's (printed, not asserted).
* ``lane_roster`` / ``lane_kernel`` — the 11-policy unified ``sweep_grid``
  at N=3000 (compact commit dispatch), two lanes checked against the
  oracle; and a single-policy omega x capacity grid with the Pallas lane
  scatter, bitwise against the jnp scatter.
* ``serving`` — a seeded degraded-replica scenario through ``ServeEngine``
  with 3 replicas and hedging; every request has exactly one outcome.

With ``--chips 4`` the only phase is ``fabric``: ``sweep_grid(...,
devices=4)`` against ``devices=1`` in this one process, bitwise, with the
lanes checked to land on four devices.

The last line of standard output is one JSON object naming the device;
it says ``"ok": true`` only when every phase passed.  Without a TPU the
script exits non-zero before any phase runs.  JAX's persistent compile
cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CHUNK = 131_072
REPLAY_BUDGET_S = 300.0     # replay fewer whole chunks past this
ORACLE_PREFIX = 20_000
ROSTER = ("lru", "lfu", "lhd", "adaptsize", "lru_mad", "lhd_mad", "lac",
          "cala", "vacdh", "lrb_lite", "stoch_vacdh")


def _ready(tree):
    import jax
    return jax.block_until_ready(tree)


def _timed(fn):
    t0 = time.perf_counter()
    out = _ready(fn())
    return out, time.perf_counter() - t0


def _leaves_equal(a, b) -> bool:
    import jax
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _check_counts(r, n_requests: int, what: str) -> None:
    n = np.asarray(r.n_hits + r.n_delayed + r.n_misses)
    if not np.all(n == n_requests):
        raise AssertionError(f"{what}: hits+delayed+misses = {n}, expected "
                             f"{n_requests}")
    if not np.all(np.isfinite(np.asarray(r.total_latency))):
        raise AssertionError(f"{what}: non-finite total latency")


def _oracle(trace, capacity, policy, params, **kw) -> dict:
    """The numpy event oracle, its few eager jnp calls kept on the host
    CPU (op-by-op dispatch to the chip would only slow it down)."""
    import jax

    from repro.core.refsim import simulate_ref
    with jax.default_device(jax.devices("cpu")[0]):
        return simulate_ref(trace, capacity, policy, params, **kw)


def _check_oracle(got, ref: dict, what: str) -> None:
    """The tier-1 scan-vs-event-oracle contract (tests/test_simulator.py)."""
    for f in ("n_hits", "n_delayed", "n_misses", "n_evictions"):
        if int(getattr(got, f)) != ref[f]:
            raise AssertionError(f"{what}: {f} {int(getattr(got, f))} != "
                                 f"oracle {ref[f]}")
    np.testing.assert_allclose(float(got.total_latency),
                               ref["total_latency"], rtol=2e-4,
                               err_msg=what)


def _realworld():
    from repro.data.traces import (RealWorldSpec, compact_requests,
                                   realworld_raw)
    stream, _ = compact_requests(realworld_raw(RealWorldSpec()),
                                 top_k=4096, n_recycle=512)
    return stream, 0.1 * float(stream.sizes.sum())


def _slice(stream, lo: int, hi: int):
    return stream._replace(times=stream.times[lo:hi],
                           objs=stream.objs[lo:hi],
                           z_draw=stream.z_draw[lo:hi])


def phase_stream_replay(stream, capacity) -> str:
    from repro.core import PolicyParams, simulate_stream

    def replay(s):
        return lambda: simulate_stream(
            s, capacity, "stoch_vacdh", PolicyParams(omega=1.0),
            estimate_z=True, use_kernel=True, chunk_size=CHUNK)

    n = stream.n_requests
    n_chunks = -(-n // CHUNK)
    one = _slice(stream, 0, CHUNK)
    # first calls compile the two chunk graphs the replay uses: whole
    # chunks, and the padded tail
    _, first_whole = _timed(replay(one))
    _, first_tail = _timed(replay(_slice(stream, (n_chunks - 1) * CHUNK, n)))
    _, chunk_s = _timed(replay(one))
    k = min(n_chunks, max(1, int(REPLAY_BUDGET_S // chunk_s)))
    part = stream if k == n_chunks else _slice(stream, 0, k * CHUNK)
    r, wall = _timed(replay(part))
    _check_counts(r, part.n_requests, "stream_replay")
    hit = float(r.hit_ratio)
    if not 0.0 < hit < 1.0:
        raise AssertionError(f"stream_replay: hit ratio {hit}")
    scope = "" if k == n_chunks else (
        f" prefix={k}/{n_chunks} chunks (a full replay would pass the "
        f"{REPLAY_BUDGET_S:.0f} s budget)")
    return (f"stream_replay: requests={part.n_requests} objects="
            f"{stream.n_objects} chunks={k} chunk_size={CHUNK} "
            f"wall_s={wall:.3f} req_per_s={part.n_requests / wall:.0f} "
            f"compile_s={first_whole + first_tail - 2 * chunk_s:.3f} "
            f"hit_ratio={hit:.6f} mean_latency_s="
            f"{float(r.mean_latency):.9f}{scope}")


def phase_oracle(stream, capacity) -> str:
    import jax

    from repro.core import PolicyParams, simulate
    from repro.core.trace import trace_of_stream

    pre = _slice(stream, 0, ORACLE_PREFIX)
    pre = pre._replace(times=pre.times - pre.times[0])
    params = PolicyParams(omega=1.0)

    def sim(trace, use_kernel):
        return lambda: simulate(trace, capacity, "stoch_vacdh", params,
                                estimate_z=True, use_kernel=use_kernel)

    trace = trace_of_stream(pre)
    kern, kern_s = _timed(sim(trace, True))
    rank, rank_s = _timed(sim(trace, False))
    t0 = time.perf_counter()
    ref = _oracle(trace, capacity, "stoch_vacdh", params, estimate_z=True)
    ref_s = time.perf_counter() - t0
    _check_oracle(kern, ref, "oracle (kernel scoring)")
    _check_oracle(rank, ref, "oracle (rank scoring)")
    # kernel vs jnp rank path: tests/test_simulator.py's contract
    np.testing.assert_allclose(float(kern.total_latency),
                               float(rank.total_latency), rtol=1e-6)
    for f in ("n_evictions", "n_hits"):
        if int(getattr(kern, f)) != int(getattr(rank, f)):
            raise AssertionError(f"oracle: kernel {f} != rank {f}")
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = _ready(sim(trace_of_stream(pre), False)())
    return (f"oracle: requests={ORACLE_PREFIX} counters_equal=True "
            f"latency_rel_err={abs(float(kern.total_latency) - ref['total_latency']) / ref['total_latency']:.3e} "
            f"kernel_vs_rank_rel="
            f"{abs(float(kern.total_latency) - float(rank.total_latency)) / float(rank.total_latency):.3e} "
            f"chip_eq_cpu_bitwise={_leaves_equal(rank, cpu)} "
            f"kernel_first_call_s={kern_s:.3f} rank_first_call_s="
            f"{rank_s:.3f} oracle_s={ref_s:.3f}")


def _n3000_trace():
    import jax

    from repro.data.traces import SyntheticSpec, synthetic_trace
    spec = SyntheticSpec(n_objects=3000, n_requests=10_000, rate=2000.0,
                         latency_base=0.02, latency_per_mb=5e-4,
                         stochastic=True)
    return synthetic_trace(jax.random.key(5), spec), spec.n_requests


def phase_lane_roster() -> str:
    from repro.core import PolicyParams, sweep_grid
    from repro.core.simulator import batched_commit_mode

    trace, n_req = _n3000_trace()
    cap, params = 1500.0, PolicyParams(omega=1.0)

    def grid():
        return sweep_grid(trace, cap, list(ROSTER), [params]).result

    r, first = _timed(grid)
    r, warm = _timed(grid)
    if r.total_latency.shape != (1, len(ROSTER), 1, 1, 1):
        raise AssertionError(f"lane_roster: shape {r.total_latency.shape}")
    _check_counts(r, n_req, "lane_roster")
    for pol in ("lru", "stoch_vacdh"):
        i = ROSTER.index(pol)
        lane = type(r)(*(x[0, i, 0, 0, 0] for x in r))
        _check_oracle(lane, _oracle(trace, cap, pol, params),
                      f"lane_roster[{pol}]")
    return (f"lane_roster: policies={len(ROSTER)} objects=3000 "
            f"requests={n_req} commit_mode={batched_commit_mode(3000)} "
            f"first_call_s={first:.3f} warm_s={warm:.3f} "
            f"oracle_lanes=lru,stoch_vacdh")


def phase_lane_kernel() -> str:
    import jax

    from repro.core import PolicyParams, sweep_grid
    from repro.core.state import set_lane_backend

    trace, n_req = _n3000_trace()
    plist = [PolicyParams(omega=o) for o in (0.0, 1.0, 2.0)]
    caps = [1000.0, 1500.0, 2000.0]
    out = {}
    for backend in ("kernel", "scatter"):
        # the lane backend is read at trace time
        jax.clear_caches()
        set_lane_backend(backend)
        try:
            out[backend] = _timed(lambda: sweep_grid(
                trace, caps, "stoch_vacdh", plist, use_kernel=True).result)
        finally:
            set_lane_backend("scatter")
    _check_counts(out["kernel"][0], n_req, "lane_kernel")
    if not _leaves_equal(out["kernel"][0], out["scatter"][0]):
        raise AssertionError("lane_kernel: Pallas lane scatter != jnp "
                             "scatter")
    return (f"lane_kernel: lanes={len(plist) * len(caps)} objects=3000 "
            f"requests={n_req} kernel_eq_scatter_bitwise=True "
            f"kernel_first_call_s={out['kernel'][1]:.3f} "
            f"scatter_first_call_s={out['scatter'][1]:.3f}")


def phase_serving() -> str:
    from repro.data.scenarios import make_scenario
    from repro.serving.engine import LatencyModel, ReplicaSet, ServeEngine
    from repro.serving.faults import DegradePolicy, FaultPlan

    w = make_scenario("degraded_replica", seed=0, n_requests=2_000)
    _, first = np.unique(w.keys, return_index=True)
    footprint = float(w.n_tokens[first].sum(dtype=np.float64))
    lat = LatencyModel(base_s=0.02, per_token_s=2e-5, hedge_quantile=0.85)
    eng = ServeEngine(
        capacity=0.25 * footprint, policy="stoch_vacdh", latency=lat,
        state_size_fn=float, hedging=True, seed=0,
        replicas=ReplicaSet.uniform(w.n_replicas, lat,
                                    scale_fns=list(w.replica_scales), seed=0),
        faults=FaultPlan(seed=0), degrade=DegradePolicy())
    t0 = time.perf_counter()
    outcomes = Counter(
        eng.serve(float(t), f"p{k}", int(n))[0]
        for t, k, n in zip(w.times, w.keys, w.n_tokens))
    wall = time.perf_counter() - t0
    s = eng.stats
    n = w.n_requests
    labels = ("hit", "delayed", "miss", "shed", "failed")
    if sum(outcomes[k] for k in labels) != n or (
            outcomes["hit"], outcomes["shed"], outcomes["failed"]) != (
            s.hits, s.shed, s.failed) or (
            s.hits + s.delayed_hits + s.misses + s.shed != n):
        raise AssertionError(f"serving: outcomes {dict(outcomes)} do not "
                             f"account for {n} requests ({s.as_dict()})")
    if w.n_replicas != 3 or s.hedges == 0:
        raise AssertionError(f"serving: replicas={w.n_replicas} "
                             f"hedges={s.hedges}")
    return (f"serving: requests={n} replicas={w.n_replicas} "
            f"hits={outcomes['hit']} delayed={outcomes['delayed']} "
            f"misses={outcomes['miss']} shed={outcomes['shed']} "
            f"failed={outcomes['failed']} hedges={s.hedges} "
            f"mean_latency_s={s.total_latency / max(n - s.shed, 1):.6f} "
            f"wall_s={wall:.3f}")


def phase_fabric(n_devices: int) -> str:
    import jax

    from repro.core import PolicyParams, sweep_grid
    from repro.launch import fabric

    trace, n_req = _n3000_trace()
    plist = [PolicyParams(omega=o)
             for o in (0.0, 0.25, 0.5, 0.75, 1.0, 1.5, 2.0, 3.0)]
    caps = [1000.0, 1500.0, 2000.0]
    shards = []
    call = fabric.fabric_sweep_single

    def spy(*a, **k):       # record where the sharded lanes were computed
        out = call(*a, **k)
        shards.append({s.device for s in out.total_latency.addressable_shards
                       if s.data.size})
        return out

    fabric.fabric_sweep_single = spy
    try:
        lines = []
        for what, run in (
            ("single", lambda d: sweep_grid(trace, caps, "stoch_vacdh",
                                            plist, devices=d)),
            ("multi", lambda d: sweep_grid(
                trace, 1500.0, ["lru", "lfu", "stoch_vacdh"],
                [PolicyParams(omega=1.0)], seeds=(0, 1),
                commit_mode=None if d > 1 else "lockstep", devices=d)),
        ):
            one, t1 = _timed(lambda: run(1).result)
            many, td = _timed(lambda: run(n_devices).result)
            _check_counts(one, n_req, f"fabric[{what}]")
            if not _leaves_equal(one, many):
                raise AssertionError(f"fabric[{what}]: devices={n_devices} "
                                     f"!= devices=1")
            lines.append(f"{what}_lanes={one.total_latency.size} "
                         f"d1_first_call_s={t1:.3f} "
                         f"d{n_devices}_first_call_s={td:.3f}")
    finally:
        fabric.fabric_sweep_single = call
    if len(shards) != 1 or len(shards[0]) != n_devices:
        raise AssertionError(f"fabric: lanes computed on {shards}")
    return (f"fabric: devices={n_devices} bitwise_equal_d1=True "
            f"lane_devices={sorted(d.id for d in shards[0])} "
            + " ".join(lines))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sweep fabric across four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform!r} devices)",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} cache={cache}",
          flush=True)

    if args.chips == 4:
        phases = [lambda: phase_fabric(4)]
    else:
        t0 = time.perf_counter()
        stream, capacity = _realworld()
        print(f"setup: generate+compact {stream.n_requests} requests "
              f"s={time.perf_counter() - t0:.3f}", flush=True)
        phases = [lambda: phase_stream_replay(stream, capacity),
                  lambda: phase_oracle(stream, capacity),
                  phase_lane_roster, phase_lane_kernel, phase_serving]
    for phase in phases:
        t0 = time.perf_counter()
        line = phase()
        print(f"{line} phase_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
