"""lane axis: the share of one whole sweep call in which no operation ran
on a chip, averaged over the cell's chips.  The call's span covers the
host's dispatch of sweep_grid before the first operation, any gap inside
the grid and, across chips, the wait for the slowest shard, and the pull
of the answer after the last; the next call starts as this one returns.
A trace cut short by a full buffer reads nothing."""


def read(ctx):
    if not ctx.view.devices or not ctx.view.complete:
        return None
    return 100.0 * ctx.view.idle_share()
