"""Plain reference of an exact slot-table replay (numpy only).

A segment of the exact CDN replay touches some 24 000 of a 200 000-key
universe.  The reference relabels the segment's keys to dense ids in
ascending raw-id order, keeps those keys' sizes and fetch means, and
replays with ``bench.reference``.  Relabelling keeps the id order, which
is the order every tie of the replay is broken in (commits and victims,
ties by object id), so the answer is the one over the whole universe,
without ranking 200 000 objects at every eviction.

It adds the slot table's counters as an exact replay states them: every
distinct key is inserted once (``n_inserts``) and none is ever reclaimed
(``n_reclaims`` 0).
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference as R


def run_job(job: dict) -> dict:
    job = dict(job)
    keys, dense = np.unique(np.asarray(job["objs"]), return_inverse=True)
    job.update(objs=dense.astype(np.int32),
               sizes=np.asarray(job["sizes"])[keys],
               z_mean=np.asarray(job["z_mean"])[keys])
    return dict(R.run_job(job), n_inserts=int(keys.size), n_reclaims=0)


def gaps(got: dict, ref: dict, n_requests: int) -> tuple[float, float]:
    """``bench.reference.gaps`` with ``n_inserts`` among the counters; a
    reclaim breaks the exact guarantee and reads ``(inf, inf)``."""
    if float(got["n_reclaims"]) != 0.0:
        return math.inf, math.inf
    cg, lg = R.gaps(got, ref, n_requests)
    ins = abs(float(got["n_inserts"]) - float(ref["n_inserts"]))
    if not math.isfinite(ins):
        return math.inf, math.inf
    return max(cg, ins / max(n_requests, 1)), lg
