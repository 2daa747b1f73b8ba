"""Device time of one decode-step execution: the step program's time in
the trace over its executions."""

STEP = r"serve_step"


def read(ctx):
    n, s = ctx.trace_reduction.modules_matching(STEP)
    return 1e3 * s / n if n else None
