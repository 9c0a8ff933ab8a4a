"""chunk dispatch: the share of one whole replay call in which no
operation ran on the chip while the host was building a chunk
(``repro.stream.prep``: slicing, concatenation, ``device_put``) or
dispatching one (``repro.stream.dispatch``): the first chunk's ramp and
any break in the double buffering."""
from bench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx.view, {"repro.stream.prep",
                                 "repro.stream.dispatch"})
