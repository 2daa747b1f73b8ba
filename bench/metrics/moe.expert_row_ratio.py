"""Rows the held routed experts multiply per decode call, over the rows
routing sends them on average (``tokens x k x held / E``): near 1 for
buffers sized to what arrives, E for buffers of ``tokens x k`` rows per
held expert, which drop nothing whatever the routing. It reads the
program's record of each traced expert-layer call
(``repro.models.moe.moe_record``) at the cell's slot count. None where the
program keeps no record."""


def read(ctx):
    slots = ctx.counters.get("slots")
    try:
        from repro.models.moe import moe_record
    except ImportError:
        return None
    ratios = [n["buffer_rows"] / (call.tokens * call.top_k * call.experts_held
                                  / call.n_routed_experts)
              for call, n in moe_record().items() if call.tokens == slots]
    return max(ratios) if ratios else None
