"""Roofline share of the decode flash-attention kernel over the traced
wave: the least time its calls need (each call the larger of its
operations over the bf16 peak and its bytes over the HBM peak;
``bench/flops.py``: Q, the valid K/V prefix and the output) over the
kernel's device time in the trace. Step ``t`` of a wave attends
``t + 1`` cache positions in every layer."""

from bench import flops

KERNEL = r"union_flash_attention"
STEP = r"serve_step"


def read(ctx):
    red = ctx.trace_reduction
    t_kernel = red.ops_matching(KERNEL)
    n, _ = red.modules_matching(STEP)
    if t_kernel <= 0 or n == 0 or not ctx.peaks:
        return None
    cfg, slots = ctx.cell.config, ctx.counters["slots"]
    need = sum(
        flops.roofline_seconds(*flops.flash_decode_call(cfg, slots, kv), ctx.peaks)
        for kv in range(1, n + 1)
    ) * cfg["n_layers"]
    return 100.0 * need / t_kernel
