"""Share of the flash-attention forward kernel's device time that the
chip's roofline needs for its work: per call, the larger of its
operations over peak FLOP/s and its bytes over peak HBM bandwidth, over
the measured time of the calls in the traced window.

The kernel is found in the trace as the Pallas custom call
(``tpu_custom_call``) whose one output is the attention of one chip's
rows, (rows, heads, seq, head_dim) in the compute dtype; the forward
and the recomputed forward both count."""
import importlib


def signature(cj, batch, seq_len):
    short = {"bfloat16": "bf16", "float32": "f32"}[cj["compute_dtype"]]
    return (f"custom-call {short}[{batch},{cj['num_attention_heads']},"
            f"{seq_len},{cj['head_dim']}] tpu_custom_call")


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
    ops, nbytes = fam.flash_kernel_work(ctx.cj, batch, job["seq_len"])
    least = max(ops / ctx.peaks["flops"], nbytes / ctx.peaks["hbm_Bps"])
    return 100.0 * n_calls * least / secs
