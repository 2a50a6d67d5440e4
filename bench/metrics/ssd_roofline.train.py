"""Share of the SSD intra-chunk kernel's device time that the chip's
roofline needs for its work: per call, the larger of its operations over
peak FLOP/s and its bytes over peak HBM bandwidth, over the measured time
of the calls in the traced window.

The kernel is found in the trace as the Pallas custom call
(``tpu_custom_call``) with the intra-chunk block's two float32 outputs,
Y (b, c, h, l, p) and the chunk states (b, c, h, n, p)."""
import importlib


def signature(cj, batch, seq_len):
    s = cj["ssm_cfg"]
    h = s["expand"] * cj["d_model"] // s["headdim"]
    c, l, p, n = seq_len // s["chunk_size"], s["chunk_size"], s["headdim"], \
        s["d_state"]
    return f"custom-call (f32[{batch},{c},{h},{l},{p}], " \
           f"f32[{batch},{c},{h},{n},{p}]) tpu_custom_call"


def read(ctx):
    if ctx.trace is None:
        return None
    job = ctx.cell["job"]
    batch = job["batch"] // ctx.chips
    sig = signature(ctx.cj, batch, job["seq_len"])
    names = [n for n in ctx.trace["ops"] if n.endswith(sig)]
    n_calls = sum(ctx.trace["op_counts"][n] for n in names)
    secs = sum(ctx.trace["ops"][n] for n in names)
    if not n_calls or not secs:
        return None
    fam = importlib.import_module(f"bench.families.{ctx.cj['family']}")
    ops, nbytes = fam.ssd_kernel_work(ctx.cj, batch, job["seq_len"])
    least = max(ops / ctx.peaks["flops"], nbytes / ctx.peaks["hbm_Bps"])
    return 100.0 * n_calls * least / secs
