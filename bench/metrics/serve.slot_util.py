"""Share of slot-steps that carried a real token: prompt and fed-back
generated tokens of the traced wave, over decode-step executions in the
trace times slots."""

STEP = r"serve_step"


def read(ctx):
    c, red = ctx.counters, ctx.trace_reduction
    n, _ = red.modules_matching(STEP)
    if n == 0:
        return None
    real = sum(p + s - 1 for p, s in zip(c["prompt_lens"], c["served"]))
    return 100.0 * real / (n * c["slots"])
