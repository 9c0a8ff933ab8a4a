"""The program's own names in a profiler trace.

Host spans (:func:`span`) are ``jax.profiler.TraceAnnotation``s: they land
on the profiler's host plane, on the device trace's clock, and cost a
no-op check when no profiler runs.  They sit at call and chunk boundaries
only, never on a per-request path.  Device scopes (:func:`scope`) are
``jax.named_scope``s: they name the ops traced inside them in the compiled
program's ``op_name`` metadata and change nothing else.

Every name is listed here, so a name cannot drift from the readers that
look for it (``bench/metrics/``, PERF.md section 3).
"""
from __future__ import annotations

import jax

PREFIX = "repro."
SPANS = ("stream.init", "stream.prep", "stream.dispatch",
         "sweep.prologue", "sweep.dispatch")
SCOPES = ("serve", "commit", "rank_select", "evict")


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>``; ``name`` must be one of SPANS."""
    if name not in SPANS:
        raise KeyError(f"unknown span {name!r}; known: {SPANS}")
    return jax.profiler.TraceAnnotation(PREFIX + name)


def scope(name: str):
    """The device scope ``repro.<name>``; ``name`` must be one of SCOPES."""
    if name not in SCOPES:
        raise KeyError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(PREFIX + name)
