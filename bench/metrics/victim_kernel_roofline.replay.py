"""victim-order kernel: the least time of its calls on this chip over the
device time they took.  The least time of a call is the larger of its
bytes (from the call's shapes) over the HBM bandwidth and its operations
over the compute peak (``bench/kernel_cost.py``, ``bench/peaks.json``);
at the replay's 100 objects the bytes bound it."""
from bench.kernel_cost import roofline_time, victim_order_cost

KERNEL = r"ranking_victim_order"


def read(ctx):
    seconds, n = ctx.view.op_time(KERNEL)
    if n == 0 or seconds <= 0.0:
        return None
    least, _bound = roofline_time(*victim_order_cost(ctx.n_objects),
                                  ctx.peak())
    return 100.0 * n * least / seconds
