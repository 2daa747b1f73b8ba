"""How many times over the decode flash-attention kernel moves the bytes
its calls need, over the traced wave: the bytes the program records for
one decode call (``repro.kernels.flash_attention.ops.kernel_record``:
counted from the call's grid and BlockSpecs, one copy per grid step whose
block index changes) times the calls, over the bytes those calls need
(``bench/flops.py``: Q, the valid K/V prefix and the output, as
``union_flash_attention_roofline`` counts them). Step ``t`` of a wave
attends ``t + 1`` cache positions; every layer makes one call a step, so
the layer count cancels. None where the program keeps no record."""

from bench import flops

STEP = r"serve_step"


def moved_per_call(cfg, slots):
    """Bytes the program records for one decode call of the cell's shape,
    or None. The decode call is the cell's batch and heads at the
    shortest query length recorded."""
    try:
        from repro.kernels.flash_attention.ops import kernel_record
    except ImportError:
        return None
    shape = (slots, cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"])
    calls = [(c.q_seq, sum(b.values())) for c, b in kernel_record().items()
             if (c.batch, c.q_heads, c.kv_heads, c.head_dim) == shape]
    return min(calls)[1] if calls else None


def read(ctx):
    red = ctx.trace_reduction
    if red is None:
        return None
    n, _ = red.modules_matching(STEP)
    cfg, slots = ctx.cell.config, ctx.counters.get("slots")
    moved = moved_per_call(cfg, slots) if n and slots else None
    if moved is None:
        return None
    need = sum(flops.flash_decode_call(cfg, slots, kv)[1] for kv in range(1, n + 1))
    return moved * n / need
