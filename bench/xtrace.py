"""Reduce a profiler trace of the measured window to per-layer numbers.

The JAX profiler writes an XSpace (``*.xplane.pb``).  :func:`extract`
keeps what the reduction reads, as plain lists: per chip, the device's
operation events and the compiled programs' (modules') events; and the
host's spans (the benchmark's own ``bench.*`` annotations among them).
:class:`View` then works on those lists alone, so a small recorded trace
stored as JSON checks the reduction without a chip:

* the window is the host's ``bench.call`` spans, first start to last end:
  the whole of a call, the host's work before its first operation and the
  pull of its answer after the last one included;
* the trace is ``complete`` when the chips' recorded ops reach the end of
  the window; a full trace buffer drops the rest, and the window is then
  cut where the recorded ops end (readers of whole calls read nothing);
* a chip's busy time is the union of its operation intervals inside the
  window, and its idle share is one less busy over the window;
* operations are ranked by their summed device time;
* each idle gap is named by the innermost host span around its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.call"
# a complete trace records ops up to the pull of the call's answer: its
# last op ends within this share of the window (and this many ns) of the
# window's end
TAIL_SHARE, TAIL_NS = 0.02, 20_000_000


def start(trace_dir: Path) -> None:
    """Start the profiler: compute ops on the chip, no Python tracer (it
    would slow the host loop it measures)."""
    import shutil

    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_COMPUTE"}
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def _line(events) -> dict:
    """One line's events as arrays: name codes into ``names``, start and
    duration in ns.  An op's name is its HLO instruction's, without the
    shapes and operands the trace spells out after it."""
    names, codes, start, dur = {}, [], [], []
    for name, s, d in events:
        name = name.split(" = ", 1)[0]
        codes.append(names.setdefault(name, len(names)))
        start.append(s)
        dur.append(d)
    return {"names": list(names), "code": np.asarray(codes, np.int64),
            "start": np.asarray(start, np.int64),
            "dur": np.asarray(dur, np.int64)}


def extract(trace_dir: Path, device_ids) -> dict:
    """The events of the newest trace under ``trace_dir``: per chip its
    op and module lines, and every host span."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            out["devices"][m.group(1)] = {
                line.name: _line((e.name, e.start_ns, e.duration_ns)
                                 for e in line.events)
                for line in plane.lines if line.name in (OPS_LINE,
                                                         MODULES_LINE)}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
    # only the host spans that overlap the window can name an idle gap
    calls = [(s, s + d) for n, s, d in out["host"] if n == WINDOW_SPAN]
    if calls:
        lo, hi = min(c[0] for c in calls), max(c[1] for c in calls)
        out["host"] = [h for h in out["host"]
                       if h[1] < hi and h[1] + h[2] > lo]
    return out


def _union(start, end):
    """Merged ``[start, end)`` intervals (touching ones merge)."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    return s[first], np.maximum.reduceat(e, first)


class View:
    """What the per-layer readers see of one traced window."""

    def __init__(self, events: dict):
        calls = [(s, s + d) for n, s, d in events["host"]
                 if n == WINDOW_SPAN]
        if not calls:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        self.host = events["host"]
        self.devices = sorted(events["devices"], key=int)
        self.lines = {d: {name: evs if isinstance(evs, dict) else _line(evs)
                          for name, evs in lines.items()}
                      for d, lines in events["devices"].items()}
        self.lo = min(s for s, _ in calls)
        self.hi = max(e for _, e in calls)
        # the device keeps a bounded number of events and drops the rest:
        # a cut trace's window ends where the chips' recorded ops end, so
        # that ops the trace dropped are not read as idle time
        ends = [int((ln["start"] + ln["dur"]).max())
                for d in self.devices
                for ln in [self._line(d, OPS_LINE)] if ln["start"].size]
        tail = max(TAIL_NS, TAIL_SHARE * (self.hi - self.lo))
        self.complete = bool(ends) and max(ends) >= self.hi - tail
        if ends and not self.complete:
            self.hi = max(self.lo, max(ends))
        self.window_s = (self.hi - self.lo) * 1e-9
        self._busy = {}
        for d in self.devices:
            ln = self._line(d, OPS_LINE)
            keep = self._inside(ln)
            self._busy[d] = _union(
                np.maximum(ln["start"][keep], self.lo),
                np.minimum(ln["start"][keep] + ln["dur"][keep], self.hi))

    def _line(self, d, line):
        return self.lines[d].get(line) or _line([])

    def _inside(self, ln):
        return (ln["start"] < self.hi) & (ln["start"] + ln["dur"] > self.lo)

    def _clipped(self, ln):
        """Each event's nanoseconds inside the window."""
        return np.maximum(0, np.minimum(ln["start"] + ln["dur"], self.hi)
                          - np.maximum(ln["start"], self.lo))

    def busy_s(self, device=None) -> float:
        """Busy seconds of one chip, or the mean over the chips."""
        ds = self.devices if device is None else [device]
        if not ds:
            return 0.0
        return sum(int((self._busy[d][1] - self._busy[d][0]).sum())
                   for d in ds) * 1e-9 / len(ds)

    def idle_share(self, device=None) -> float:
        ds = self.devices if device is None else [device]
        return sum(1.0 - self.busy_s(d) / self.window_s for d in ds) / len(ds)

    def op_time(self, pattern: str, line: str = OPS_LINE):
        """(summed seconds inside the window, count) of the events whose
        name matches ``pattern`` on ``line``, over all chips."""
        rx = re.compile(pattern)
        total, count = 0, 0
        for d in self.devices:
            ln = self._line(d, line)
            hit = np.array([bool(rx.search(n)) for n in ln["names"]] or
                           [False])[ln["code"]] & self._inside(ln)
            total += int(self._clipped(ln)[hit].sum())
            count += int(hit.sum())
        return total * 1e-9, count

    def ranked_ops(self, k: int = 10):
        totals: dict[str, int] = {}
        for d in self.devices:
            ln = self._line(d, OPS_LINE)
            sums = np.bincount(ln["code"], self._clipped(ln),
                               len(ln["names"]))
            for name, ns in zip(ln["names"], sums):
                totals[name] = totals.get(name, 0) + int(ns)
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top if ns > 0]

    def idle_gaps(self, k: int = 10):
        """The ``k`` longest idle gaps of the chips inside the window,
        each named by the innermost host span around its middle."""
        gaps = []
        for d in self.devices:
            s, e = self._busy[d]
            lo = np.concatenate([[self.lo], e])
            hi = np.concatenate([s, [self.hi]])
            for a, b in zip(lo[hi > lo], hi[hi > lo]):
                gaps.append((int(b - a), int(a), int(b)))
        gaps.sort(reverse=True)
        out = []
        for length, a, b in gaps[:k]:
            mid = (a + b) // 2
            around = [(dur, n) for n, s0, dur in self.host
                      if s0 <= mid < s0 + dur]
            out.append([min(around)[1] if around else "(no host span)",
                        length * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.ranked_ops(), "idle_gaps": self.idle_gaps()}


def read(trace_dir: Path, device_ids) -> View:
    """Reduce the stopped profiler's trace; the trace files go."""
    import shutil
    try:
        return View(extract(trace_dir, set(device_ids)))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def save_events(events: dict, path: Path) -> None:
    """Write extracted events as JSON (lines as ``[name, start, dur]``)."""
    def rows(ln):
        return [[ln["names"][c], int(s), int(d)]
                for c, s, d in zip(ln["code"], ln["start"], ln["dur"])]
    plain = {"host": events["host"],
             "devices": {d: {name: rows(ln) for name, ln in lines.items()}
                         for d, lines in events["devices"].items()}}
    with open(path, "w") as f:
        json.dump(plain, f)


def load_events(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Context:
    """Everything a per-layer reader may read."""

    view: View
    work: int                   # requests (or lane-requests) traced
    timers: dict                # host seconds of the benchmark's spans
    device_kind: str
    n_objects: int

    def peak(self) -> dict:
        """This chip's row of ``bench/peaks.json``; a chip not listed there
        is an error, never a default."""
        with open(Path(__file__).resolve().parent / "peaks.json") as f:
            rows = json.load(f)["devices"]
        if self.device_kind not in rows:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in bench/peaks.json")
        return rows[self.device_kind]
