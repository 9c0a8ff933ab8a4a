"""lane axis: compiled programs the host launched while in
``repro.sweep.prologue`` (the eager conversions, stacks and gathers of
sweep_grid before its grid runs), per traced call."""
from bench.program_spans import launches_per_call


def read(ctx):
    return launches_per_call(ctx.view, {"repro.sweep.prologue"})
