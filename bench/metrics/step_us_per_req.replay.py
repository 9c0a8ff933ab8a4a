"""serve step and commit loop: device time of the compiled chunk-step
program (``_chunk_step_jit``: the per-request serve step and the commit
``while_loop``, kernel calls included) per simulated request."""
from bench.xtrace import MODULES_LINE

PROGRAM = r"_chunk_step_jit"


def read(ctx):
    seconds, n = ctx.view.op_time(PROGRAM, MODULES_LINE)
    if n == 0 or ctx.work == 0 or not ctx.view.complete:
        return None
    return 1e6 * seconds / ctx.work
