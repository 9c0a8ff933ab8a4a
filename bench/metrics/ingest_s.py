"""ingest: host seconds to build the cell's requests, the benchmark's own
generation from the seed plus the program's ingest of the arrays
(``compact_requests`` for a stream, ``make_trace`` for a sweep)."""


def read(ctx):
    return ctx.timers.get("generate", 0.0) + ctx.timers.get("ingest", 0.0)
