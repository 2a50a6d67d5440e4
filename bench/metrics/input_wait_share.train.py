"""Share of the window, less the time that collecting the trace took in
it, that the trainer spent reading its batch through the pipeline and
placing it on the device (the benchmark's clock around ``next(batches)``
and ``device_put``)."""


def read(ctx):
    c = ctx.counters
    span = c.get("window_s", 0) - c["trace_stop_s"]
    return 100.0 * c["input_s"] / span if span > 0 else None
