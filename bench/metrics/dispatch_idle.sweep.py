"""lane axis: the share of one whole sweep call in which no operation ran
on a chip while the host was in ``repro.sweep.dispatch``: the call of the
compiled grid, from its argument handling to its launch."""
from bench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx.view, {"repro.sweep.dispatch"})
