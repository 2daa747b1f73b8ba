"""Candidate mappings the engine evaluated per second of query time:
``SearchResult.evaluated`` summed over the window's queries, over their
summed host-clock duration."""


def read(ctx):
    c = ctx.counters
    return c["evaluated"] / c["query_s"] if c.get("query_s") else None
