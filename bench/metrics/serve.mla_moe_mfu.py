"""Model FLOP/s utilization of the traced wave of a latent-attention,
routed-expert model: the forward operations its requests need (real
prompt and fed-back tokens, each against its own context, with attention
counted in its latent form and the held experts at their expected share;
``bench/flops_mla_moe.py``) over the wave's host-clock duration, over the
chip's bf16 peak."""

from bench import flops_mla_moe


def read(ctx):
    c = ctx.counters
    if not c.get("wave_s") or not ctx.peaks:
        return None
    cfg = ctx.cell.config
    need = sum(flops_mla_moe.request_flops(cfg, p + s - 1)
               for p, s in zip(c["prompt_lens"], c["served"]))
    return 100.0 * need / c["wave_s"] / (ctx.peaks["bf16_flops_per_s"] * len(ctx.devices))
