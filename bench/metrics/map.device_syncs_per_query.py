"""Host syncs of the device-resident search loop per query
(``SearchResult.device_syncs``)."""


def read(ctx):
    c = ctx.counters
    return c["device_syncs"] / c["queries"] if c.get("queries") else None
