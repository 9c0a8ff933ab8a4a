"""lane axis: device busy time, summed over the cell's chips, per
lane-request (one request of one (policy, omega, capacity) lane)."""


def read(ctx):
    if not ctx.view.devices or ctx.work == 0 or not ctx.view.complete:
        return None
    return 1e6 * ctx.view.busy_s() * len(ctx.view.devices) / ctx.work
