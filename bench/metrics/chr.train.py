"""Cache hit ratio of the trainer's block reads in the window
(``PipelineStats`` deltas)."""


def read(ctx):
    c = ctx.counters
    n = c.get("hits", 0) + c.get("misses", 0)
    return 100.0 * c["hits"] / n if n else None
