"""Shared benchmark utilities: CSV emit + policy sweep runner."""
from __future__ import annotations

import csv
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

RESULTS_DIR = Path(__file__).parent / "results"
REPO_ROOT = Path(__file__).parent.parent

POLICY_SET = ["lru", "lfu", "lhd", "adaptsize", "lru_mad", "lhd_mad",
              "lac", "cala", "vacdh", "lrb_lite", "stoch_vacdh"]


def forced_device_env(n: int) -> dict:
    """Subprocess env with ``n`` fake host CPU devices forced via XLA_FLAGS.

    The multi-device sweep fabric (repro.launch.fabric, DESIGN.md §13) is
    validated on CPU by faking devices, and the flag only works if set
    before jax initializes — so multi-device measurement on the CPU
    happens in a child process (the ``benchmarks/probe_memory.py``
    pattern).  Only a parent that itself runs on the CPU may ask for this:
    the child inherits the parent's platform, and on a chip host the
    parent holds the chip, so chip measurements stay in-process.  Any
    pre-existing device-count flag is replaced outright (a stale count
    surfaces much later as a confusing mesh error); other XLA flags are
    kept."""
    import os
    import re
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"fake host devices apply to the CPU platform only, and this "
            f"process runs on {jax.default_backend()!r}: measure real "
            f"devices in-process")
    env = dict(os.environ)
    prior = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    flag = f"--xla_force_host_platform_device_count={n}"
    env["XLA_FLAGS"] = f"{prior} {flag}".strip()
    return env


def _git_sha() -> str:
    """Short HEAD sha, suffixed '-dirty' when the working tree differs —
    a history entry must never attribute uncommitted code's numbers to a
    clean commit."""
    import subprocess
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
        porcelain = subprocess.run(
            ["git", "status", "--porcelain"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
        return f"{sha}-dirty" if porcelain else sha
    except Exception:
        return "unknown"


def _backfill_headline(old: dict) -> dict:
    """Synthesize a history entry's headline from a pre-history payload, so
    the first history-aware write preserves the prior PR's point instead of
    overwriting it (the PR-4 backfill)."""
    if old.get("benchmark") == "fig_realworld_stream":
        agg = old.get("aggregate", {})
        dev = old.get("device_mode") or [{}]
        return {k: v for k, v in dict(
            mean_req_per_s=agg.get("mean_req_per_s"),
            peak_rss_mb=agg.get("peak_rss_mb"),
            device_req_per_s=dev[0].get("req_per_s")).items()
            if v is not None}
    if old.get("benchmark") == "bench_sweep":
        return dict(old.get("summary", {}))
    return {}


def write_bench_json(filename: str, payload: dict,
                     path: Path | str | None = None,
                     headline: dict | None = None) -> Path:
    """Write a machine-readable perf-trajectory snapshot at the repo root
    (or at ``path`` — CI's smoke artifact reuses the same schema).

    ``BENCH_stream.json`` / ``BENCH_sweep.json`` exist so future PRs can
    diff measured req/s, wall-clock, and peak RSS against this one instead
    of re-reading EXPERIMENTS prose.  The environment fields make cross-PR
    numbers interpretable (a TPU row and a 2-vCPU row are different
    experiments, not a regression) — one stamping function so every
    artifact shares one schema.

    ``headline`` (a small dict of the run's defining numbers) turns the
    snapshot into a *trajectory*: the file's ``history`` list is carried
    forward across writes and the current run is appended as
    ``{sha, date_utc, **headline}`` — so the full-detail ``rows`` always
    describe the latest run while ``history`` accrues one headline per
    measurement across PRs.  A pre-history file on disk contributes a
    backfilled first entry (sha 'pre-history') derived from its own
    payload, so no recorded point is ever dropped."""
    import json
    import os
    import platform
    from datetime import datetime, timezone

    payload = dict(payload)
    payload.setdefault("backend", jax.default_backend())
    payload.setdefault("cpu_count", os.cpu_count())
    payload.setdefault("platform", platform.platform())
    payload.setdefault("jax_version", jax.__version__)
    payload.setdefault(
        "generated_utc",
        datetime.now(timezone.utc).isoformat(timespec="seconds"))
    path = Path(path) if path is not None else REPO_ROOT / filename
    if headline is not None:
        history = []
        try:
            old = json.loads(path.read_text())
            history = list(old.get("history", []))
            if not history:
                back = _backfill_headline(old)
                if back:
                    history.append(dict(
                        sha="pre-history",
                        date_utc=old.get("generated_utc"), **back))
        except (OSError, ValueError):
            pass
        history.append(dict(sha=_git_sha(),
                            date_utc=payload["generated_utc"], **headline))
        payload["history"] = history
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {path}")
    return path


def emit(rows: list[dict], name: str, echo: bool = True) -> Path:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    path = RESULTS_DIR / f"{name}.csv"
    if rows:
        fields = list(dict.fromkeys(k for r in rows for k in r))
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=fields, restval="")
            w.writeheader()
            w.writerows(rows)
    if echo:
        for r in rows:
            print(",".join(str(v) for v in r.values()))
    return path


def improvement_table(trace, capacity, policies=POLICY_SET, params=None,
                      extra: dict | None = None,
                      estimate_z: bool = True,
                      use_kernel=False) -> list[dict]:
    """Latency improvement vs LRU (paper eq. 17) for each policy.
    estimate_z=True: policies see only observed fetch durations (the paper's
    operational setting for stochastic latency)."""
    from repro.core import PolicyParams, simulate
    params = params or PolicyParams()
    base = simulate(trace, capacity, "lru", params, estimate_z=estimate_z)
    lru_lat = float(base.total_latency)
    rows = []
    for pol in policies:
        t0 = time.time()
        r = simulate(trace, capacity, pol, params, estimate_z=estimate_z,
                     use_kernel=use_kernel)
        lat = float(r.total_latency)
        rows.append(dict(
            policy=pol,
            latency=round(lat, 4),
            improvement_vs_lru=round((lru_lat - lat) / lru_lat, 5),
            hit_ratio=round(float(r.hit_ratio), 4),
            delayed_ratio=round(float(r.n_delayed)
                                / max(float(r.n_requests), 1), 4),
            sim_s=round(time.time() - t0, 2),
            **(extra or {})))
    return rows


LANE_BUCKET = 12    # pad sweep grids so differently-sized sweeps share XLA


def _grid_rows(g, policies, names, per_pt, extra, extra_fn) -> list[dict]:
    """Flatten a SweepGrid into improvement_table-schema rows."""
    lru_li = names.index("lru")
    T, _, P, C, S = g.result.total_latency.shape
    rows = []
    for pol in policies:
        li = names.index(pol)
        for ti in range(T):
            for pi in range(P):
                for ci in range(C):
                    for si in range(S):
                        r = g.point(ti, li, pi, ci, si)
                        lat = float(r.total_latency)
                        lb = float(g.result.total_latency[ti, lru_li, pi,
                                                          ci, si])
                        row = dict(
                            policy=pol,
                            latency=round(lat, 4),
                            improvement_vs_lru=round((lb - lat) / lb, 5),
                            hit_ratio=round(float(r.hit_ratio), 4),
                            delayed_ratio=round(
                                float(r.n_delayed)
                                / max(float(r.n_requests), 1), 4),
                            sim_s=round(per_pt, 3),
                            **(extra or {}),
                            **(extra_fn(g.params[pi]) if extra_fn else {}))
                        row["capacity"] = round(float(g.capacities[ci]), 1)
                        if T > 1:
                            row["trace_idx"] = ti
                        if S > 1:
                            row["seed"] = g.seeds[si]
                        rows.append(row)
    return rows


def sweep_improvement_table(traces, capacities, policies, params=None,
                            seeds=(0,), extra: dict | None = None,
                            extra_fn=None, estimate_z: bool = True,
                            graph_policies=None, unified: bool = True,
                            lane_bucket: int | None = LANE_BUCKET
                            ) -> list[dict]:
    """improvement_table over a whole scenario grid via core/sweep.py.

    ``unified=True``: ONE compiled+batched call — the LRU baseline rides as
    a lane of the unified multi-policy graph — covers policies x traces x
    params x capacities x seeds.  Right for small object universes and
    policy subsets (fig4's sensitivity grids), where the whole sweep's
    dispatch-and-compile overhead collapses into one call.

    ``unified=False``: one single-policy (statically specialized) batched
    call per policy plus one for the LRU baseline.  Right for large-N or
    full policy-roster tables (fig2/fig5): evaluating every rank function in
    lockstep would multiply the per-step element work (EXPERIMENTS.md
    §Perf), while per-policy graphs stay lean and — with the traces padded
    to one shape — compile once per policy for the whole figure.

    ``extra_fn(params) -> dict`` labels rows per grid point (e.g. the swept
    omega); ``extra`` labels every row.  ``graph_policies`` optionally names
    a superset policy list to build the unified graph with, so consecutive
    sweeps over different policy subsets reuse one compiled graph (rows are
    only emitted for ``policies``).  ``lane_bucket`` applies to the unified
    path only: per-policy grids within one call already share a shape, and
    padding them would also flip small grids onto a batched update
    lowering (DESIGN.md §11) — a net loss at large N.
    """
    from repro.core import PolicyParams, SimResult, sweep_grid
    from repro.core.trace import Trace

    trace_list = [traces] if isinstance(traces, Trace) else list(traces)
    params_list = (list(params) if isinstance(params, (list, tuple))
                   else [params or PolicyParams()])
    policies = list(policies)

    if unified:
        if graph_policies is not None:
            names = list(graph_policies)
            names += [p for p in policies + ["lru"] if p not in names]
        else:
            names = policies if "lru" in policies else ["lru"] + policies
        t0 = time.time()
        g = sweep_grid(trace_list, capacities, names, params_list, seeds,
                       estimate_z=estimate_z, lane_bucket=lane_bucket)
        block_until_ready_tree(g.result)
        shape = g.result.total_latency.shape
        n_pts = 1
        for s in shape:
            n_pts *= int(s)
        per_pt = (time.time() - t0) / max(n_pts, 1)
        return _grid_rows(g, policies, names, per_pt, extra, extra_fn)

    # per-policy path: one batched call per policy; stitch the per-policy
    # [T, 1, P, C, S] grids into one [T, L, P, C, S] result for row emission
    names = policies if "lru" in policies else ["lru"] + policies
    t0 = time.time()
    grids = [sweep_grid(trace_list, capacities, pol, params_list, seeds,
                        estimate_z=estimate_z, lane_bucket=None)
             for pol in names]
    for g in grids:
        block_until_ready_tree(g.result)
    joined = SimResult(*(jnp.concatenate([g.result[f] for g in grids], axis=1)
                         for f in range(len(grids[0].result))))
    g0 = grids[0]
    g = g0._replace(result=joined, policies=tuple(names))
    n_pts = 1
    for s in joined.total_latency.shape:
        n_pts *= int(s)
    per_pt = (time.time() - t0) / max(n_pts, 1)
    return _grid_rows(g, policies, names, per_pt, extra, extra_fn)


def block_until_ready_tree(x):
    jax.tree.map(lambda a: a.block_until_ready()
                 if hasattr(a, "block_until_ready") else a, x)


def pad_trace_objects(trace, n_objects: int):
    """Pad the object universe with never-requested dummies.

    Traces whose only shape difference is the universe size then share one
    compiled sweep graph (fig5's surrogates).  Dummies are never requested,
    so they are never cached, in flight, or eviction victims — results are
    bitwise unchanged; their rank rows are computed and discarded.
    """
    import jax.numpy as jnp

    from repro.core.trace import Trace
    pad = n_objects - trace.n_objects
    if pad <= 0:
        return trace
    return Trace(trace.times, trace.objs,
                 jnp.concatenate([trace.sizes,
                                  jnp.ones((pad,), jnp.float32)]),
                 jnp.concatenate([trace.z_mean,
                                  jnp.ones((pad,), jnp.float32)]),
                 trace.z_draw)
