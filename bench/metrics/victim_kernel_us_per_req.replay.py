"""victim-order kernel: device time of the fused eq.-16 scoring and
victim-order Pallas kernel (``kernels/ranking_score.py``) per simulated
request."""
KERNEL = r"ranking_victim_order"


def read(ctx):
    seconds, n = ctx.view.op_time(KERNEL)
    if n == 0 or ctx.work == 0 or not ctx.view.complete:
        return None
    return 1e6 * seconds / ctx.work
