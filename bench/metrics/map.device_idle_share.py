"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    red = ctx.trace_reduction
    return 100.0 * red.idle_share if red is not None and red.window_s > 0 else None
