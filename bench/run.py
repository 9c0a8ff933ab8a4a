#!/usr/bin/env python3
"""Benchmark harness: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix; the
run generates that cell's requests from ``--seed``, hands them to the
program through its entry points, warms every compiled shape up (all of
it counted as set-up), then calls the program in a closed loop for
``--seconds``: each call starts when the previous one has returned and its
answer has been pulled to the host.  After the window, every answer is
compared with the plain reference (``bench/reference.py``, or the one
the cell's driver names) and the run
prints one JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window's first call),
``device``, and last
``checks``, each compared number beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.  JAX's persistent compilation cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the root
of the checkout.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is bench/ itself; put the checkout there so
# no module of bench/ can shadow a standard one
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

TRACE_DIR = ROOT / ".bench_trace"
NO_CHIP = 3


class Spans:
    """The benchmark's own host spans: host seconds per name, and a
    ``TraceAnnotation`` of the same name in a profiler trace."""

    def __init__(self):
        self.total: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        self.total[name] = self.total.get(name, 0.0) + (
            time.perf_counter() - t0)


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations (a compile inside the window is a fault
    of the warm-up)."""

    def __init__(self):
        self.n = 0
        import jax.monitoring as mon

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
        mon.register_event_duration_secs_listener(listener)


def closed_loop(driver, seconds: float, spans: Spans, after_first=None):
    """Calls until ``seconds`` have passed; returns the calls' answers and
    the window's start and end (the last call's return).  ``after_first``
    runs once the first call has returned."""
    calls = []
    t0 = time.perf_counter()
    k = 0
    while True:
        with spans("call"):
            calls.append((k, driver.call(k)))
        if k == 0 and after_first is not None:
            after_first()
        k += 1
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            return calls, t0, t1


def reference_answers(driver, segments, control=None, workers=None):
    """The answer of the driver's plain reference for every lane of
    ``segments``, in a pool of processes that import only numpy."""
    import itertools
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    from bench.cell import reference_source, run_reference_job
    source = reference_source(driver.reference)
    jobs = [(seg, lane, job) for seg in segments
            for lane, job in enumerate(driver.jobs(seg, control))]
    n = workers or max(1, min(len(jobs), (os.cpu_count() or 2) - 1))
    if n == 1:
        outs = [run_reference_job(source, j) for *_, j in jobs]
    else:
        with ProcessPoolExecutor(
                n, mp_context=multiprocessing.get_context("spawn")) as ex:
            outs = list(ex.map(run_reference_job, itertools.repeat(source),
                               [j for *_, j in jobs]))
    return {(seg, lane): out for (seg, lane, _), out in zip(jobs, outs)}


def compare(driver, calls, refs, limits: dict) -> dict:
    """Every call's every lane against the reference: the widest counter
    gap (share of a lane's requests) and latency gap (relative), as the
    driver's reference's ``gaps`` gives them."""
    from bench.cell import load_reference, reference_source
    gaps = load_reference(reference_source(driver.reference)).gaps
    worst = {"counter_gap": 0.0, "latency_gap": 0.0}
    failed = 0
    for k, ans in calls:
        seg = k % driver.n_segments
        bad = False
        for lane in range(len(driver.lanes)):
            got = {f: float(v[lane]) for f, v in ans.items()}
            cg, lg = gaps(got, refs[(seg, lane)], driver.seg_len)
            for name, v in (("counter_gap", cg), ("latency_gap", lg)):
                worst[name] = max(worst[name], v)
                bad |= not v <= limits[name]
        failed += bad
    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in worst.items()}
    return dict(attempted=len(calls), failed=failed, checks=checks,
                correct=failed == 0 and all(
                    c["value"] <= c["limit"] for c in checks.values()))


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def per_layer(cell, ctx) -> dict:
    from bench.cell import load_reader
    out = {}
    for m in cell.per_layer:
        v = load_reader(m["name"])(ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float | None = None, workers: int | None = None) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.
    ``workers`` caps the reference's processes."""
    from bench import drivers, xtrace
    t_start = _T0 if t_start is None else t_start
    spans = Spans()
    drv = drivers.build(cell.config, cell.traffic, seed)
    drv.prepare(spans)
    with spans("warmup"):
        drv.warm()
    setup_s = time.perf_counter() - t_start
    compiles = CompileCounter()
    if trace:
        # the device trace holds a few million op events (each simulated
        # request records dozens): it covers the window's first call
        xtrace.start(TRACE_DIR)
    calls, w0, w1 = closed_loop(drv, seconds, spans,
                                xtrace.stop if trace else None)
    t_trace = time.perf_counter()
    view = xtrace.read(TRACE_DIR, [d.id for d in devices]) if trace \
        else None
    trace_s = time.perf_counter() - t_trace
    memory_peak = _memory_peak(devices)
    work = len(calls) * drv.work_per_call

    metrics, breakdown = {}, None
    if trace:
        ctx = xtrace.Context(view=view, work=drv.work_per_call,
                             timers=dict(spans.total),
                             device_kind=devices[0].device_kind,
                             n_objects=drv.n_objects)
        metrics = per_layer(cell, ctx)
        breakdown = view.breakdown()
    else:
        values = {"setup_s": setup_s, drv.rate_metric: work / (w1 - w0)}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"cell {cell.name} lists {m['name']!r}; its "
                               f"traffic reports {sorted(values)}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    drv.release()       # the program's state, before the reference runs
    t_ref = time.perf_counter()
    segments = sorted({k % drv.n_segments for k, _ in calls})
    refs = reference_answers(drv, segments, workers=workers)
    verdict = compare(drv, calls, refs, cell.traffic["limits"])
    reference_s = time.perf_counter() - t_ref

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "device_kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        device.update(busy_s=view.busy_s(), window_s=view.window_s)
    out = {"correct": verdict["correct"], "attempted": verdict["attempted"],
           "failed": verdict["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["calls"] = len(calls)
    out["compiles_in_window"] = compiles.n
    if trace:
        out["trace_read_s"] = trace_s
        out["trace_complete"] = view.complete
    out["reference_s"] = reference_s
    out["checks"] = verdict["checks"]
    return out


def emit(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.cell import load_cell
    cell = load_cell(args.workload)
    enable_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return NO_CHIP
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              devices[:cell.chips])
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
