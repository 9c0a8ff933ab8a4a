"""score kernel over slots: device time of the eq.-16 scoring Pallas
kernel (``ranking_scores``, ``kernels/ranking_score.py``), which scores
every slot of the table at each commit that needs room, per simulated
request."""
KERNEL = r"ranking_scores"


def read(ctx):
    seconds, n = ctx.view.op_time(KERNEL)
    if n == 0 or ctx.work == 0 or not ctx.view.complete:
        return None
    return 1e6 * seconds / ctx.work
