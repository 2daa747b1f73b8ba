"""Latent rows the decode step up-projects to per-head keys and values, per
layer over the traced wave's steps, over the cached rows those steps
attend: step ``t`` of a wave attends ``slots x (t + 1)`` rows. It reads
the program's record of each traced MLA decode call
(``repro.models.layers.mla_record``); a decode that attends in the latent
space records no rows and reads 0. None where the program keeps no
record."""

STEP = r"serve_step"


def upproj_rows_per_call(slots):
    """Rows the program records for one decode call of ``slots``
    single-token queries, or None."""
    try:
        from repro.models.layers import mla_record
    except ImportError:
        return None
    rows = [n["upproj_rows"] for call, n in mla_record().items()
            if call.batch == slots and call.q_seq == 1]
    return max(rows) if rows else None


def read(ctx):
    red, slots = ctx.trace_reduction, ctx.counters.get("slots")
    if red is None or not slots:
        return None
    n, _ = red.modules_matching(STEP)
    rows = upproj_rows_per_call(slots) if n else None
    if rows is None:
        return None
    return rows * n / (slots * n * (n + 1) / 2)
