"""fabric: the busiest chip's busy time over the mean across the cell's
chips (1 when the sweep fabric's lane shards take equal device time)."""


def read(ctx):
    ds = ctx.view.devices
    if len(ds) < 2 or not ctx.view.complete:
        return None
    busy = [ctx.view.busy_s(d) for d in ds]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
