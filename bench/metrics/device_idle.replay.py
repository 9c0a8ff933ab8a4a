"""chunk dispatch: the share of one whole replay call in which no operation
ran on the chip.  The call's span covers every gap the loop pays: the
host's work before the first chunk is dispatched, the waits between chunks
while simulate_stream's host loop builds and sends the next one, and the
pull of the answer after the last; the next call starts as this one
returns.  A trace cut short by a full buffer reads nothing."""


def read(ctx):
    if not ctx.view.devices or not ctx.view.complete:
        return None
    return 100.0 * ctx.view.idle_share()
