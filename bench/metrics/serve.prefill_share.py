"""Share of the traced wave from its submission to its first token (the
token-by-token prefill), on the host clock."""


def read(ctx):
    c = ctx.counters
    if not c.get("wave_s"):
        return None
    return 100.0 * c["prefill_s"] / c["wave_s"]
