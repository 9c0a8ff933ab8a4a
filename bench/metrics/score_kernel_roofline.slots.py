"""score kernel over slots: the least time of the eq.-16 scoring kernel's
calls (``ranking_scores``, ``kernels/ranking_score.py``) on this chip over
the device time they took, in a slot-table replay.  ``ranking_scores`` is
the victim-order pass with ``top=1`` over the whole table, so a call's
bytes and operations are ``victim_order_cost(n_slots, top=1)``
(``bench/kernel_cost.py``), ``n_slots`` being the cell's ``n_objects``;
peaks from ``bench/peaks.json``.  At 131 072 slots the bytes bound it."""
from bench.kernel_cost import roofline_time, victim_order_cost

KERNEL = r"ranking_scores"


def read(ctx):
    seconds, n = ctx.view.op_time(KERNEL)
    if n == 0 or seconds <= 0.0:
        return None
    least, _bound = roofline_time(*victim_order_cost(ctx.n_objects, top=1),
                                  ctx.peak())
    return 100.0 * n * least / seconds
