"""Device time of the engine's compiled cores per query: every program
execution on the chip in the traced window (the cores are the only
programs a query runs there), over the queries."""


def read(ctx):
    red, c = ctx.trace_reduction, ctx.counters
    if red is None or not c.get("queries"):
        return None
    total = sum(red.module_s.values())
    return 1e3 * total / c["queries"] if total > 0 else None
