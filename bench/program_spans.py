"""The program's own host spans (``repro.*``, ``repro.core.spans``) on the
device trace's clock: what the chips did while the host was inside them.

The program annotates its call and chunk boundaries with
``jax.profiler.TraceAnnotation``; the spans land on the host plane beside
the benchmark's ``bench.*`` spans.  Worked out from a :class:`View`'s
``host``, ``lines``, ``lo`` and ``hi`` alone:

* a span set is the union of the named spans' intervals, clipped to the
  window;
* a chip's idle time inside it is the set's length less the chip's busy
  time inside it, busy being the union of the chip's op intervals in the
  window, as the view's own idle share counts it; so the idle shares of
  disjoint span sets sum to no more than the view's idle share;
* a launch is a runtime execute event on the host whose start lies inside
  the set.

A trace with none of the named spans (a program that has none) or a cut
trace reads ``None``.
"""
from __future__ import annotations

import numpy as np

from bench.xtrace import OPS_LINE, WINDOW_SPAN, _union

# the runtime's host event for one launch of a compiled program
EXECUTE = "PJRT_LoadedExecutable_Execute"


def _merged(start, end):
    """Disjoint sorted ``[start, end)`` intervals covering the same ticks;
    empty ones (clipped off the window) go."""
    keep = end > start
    return _union(start[keep], end[keep])


def span_set(view, names):
    """The union of the host spans named in ``names``, inside the window."""
    ev = [(s, s + d) for n, s, d in view.host if n in names]
    if not ev:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    s, e = np.asarray(ev, np.int64).T
    return _merged(np.maximum(s, view.lo), np.minimum(e, view.hi))


def _busy(view, device):
    ln = view.lines[device].get(OPS_LINE)
    if ln is None:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    s, e = ln["start"], ln["start"] + ln["dur"]
    keep = (s < view.hi) & (e > view.lo)
    return _merged(np.maximum(s[keep], view.lo), np.minimum(e[keep], view.hi))


def _busy_until(bs, be, t):
    """Busy nanoseconds before each time in ``t`` of disjoint sorted
    intervals ``[bs, be)``."""
    if bs.size == 0:
        return np.zeros_like(t)
    done = np.concatenate([[0], np.cumsum(be - bs)])
    k = np.searchsorted(bs, t, side="right")
    last_end = be[np.maximum(k - 1, 0)]
    return done[k] - np.where(k > 0, np.maximum(last_end - t, 0), 0)


def idle_share(view, names):
    """Percent of the window in which a chip was idle and the host inside
    one of ``names``, averaged over the chips; None where none of the
    spans is in the trace, there is no chip, or the trace is cut."""
    if not view.devices or not view.complete:
        return None
    s, e = span_set(view, names)
    if s.size == 0:
        return None
    idle = 0
    for d in view.devices:
        bs, be = _busy(view, d)
        busy = _busy_until(bs, be, e) - _busy_until(bs, be, s)
        idle += int((e - s - busy).sum())
    return 100.0 * idle / len(view.devices) / (view.hi - view.lo)


def launches_per_call(view, names):
    """Program launches that start inside ``names``' spans, per call in
    the window; None where none of the spans is in the trace or the trace
    is cut."""
    if not view.complete:
        return None
    s, e = span_set(view, names)
    if s.size == 0:
        return None
    t = np.asarray([t0 for n, t0, _ in view.host if n == EXECUTE], np.int64)
    k = np.searchsorted(s, t, side="right") - 1
    inside = (k >= 0) & (t < e[np.maximum(k, 0)])
    calls = sum(n == WINDOW_SPAN for n, _, _ in view.host)
    return int(inside.sum()) / calls
