"""Cache hit ratio of the co-tenant scan's block reads in the window."""


def read(ctx):
    c = ctx.counters
    n = c.get("scan_hits", 0) + c.get("scan_misses", 0)
    return 100.0 * c["scan_hits"] / n if n else None
