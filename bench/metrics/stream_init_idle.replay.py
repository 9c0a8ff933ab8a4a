"""chunk dispatch: the share of one whole replay call in which no
operation ran on the chip while the host was in ``repro.stream.init``:
simulate_stream's host conversions, the sizes sent to the device and the
initial state's fills, before the first chunk is built."""
from bench.program_spans import idle_share


def read(ctx):
    return idle_share(ctx.view, {"repro.stream.init"})
