"""compile: host seconds of the warm-up call that compiles (or loads from
the persistent cache) every program the window runs."""


def read(ctx):
    return ctx.timers.get("warmup")
