"""lane axis: the share of one whole sweep call in which no operation ran
on a chip while the host was in ``repro.sweep.prologue``: sweep_grid's
checks, the capacity and seed conversions, the trace stack and the lane
flatten, before the compiled grid is called."""
from bench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx.view, {"repro.sweep.prologue"})
