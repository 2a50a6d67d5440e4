"""Bytes that crossed the remote link in the window, per trained token
(all the cache's tenants together)."""


def read(ctx):
    c = ctx.counters
    return c["link_bytes"] / c["tokens"] if c.get("tokens") else None
