"""Whole-step model FLOP utilization: the forward and backward operations
a trained token needs (recomputation excluded, from the configuration's
shapes) times tokens per second of the window, less the time that
collecting the trace took in it, over chips times the chip's peak."""
import importlib


def read(ctx):
    c = ctx.counters
    if not c.get("tokens"):
        return None
    fam = importlib.import_module(f"bench.families.{ctx.cj['family']}")
    rate = c["tokens"] / (c["window_s"] - c["trace_stop_s"])
    return (100.0 * fam.train_ops_per_token(ctx.cj) * rate
            / (ctx.chips * ctx.peaks["flops"]))
