"""Operations and bytes that the algorithms need, from shapes alone.

These are the numerators of MFU and of the kernels' roofline shares: they
count the work of the published algorithm, whatever the program runs to do
it, and exclude recomputation.  Products count 2 operations per
multiply-add."""
from __future__ import annotations


def ssd_chunk_work(b: int, s: int, h: int, p: int, n: int, chunk: int,
                   in_itemsize: int) -> tuple:
    """(operations, bytes) of SSD's intra-chunk block over (b, s) tokens:
    C B^T (shared by the heads), the masked product with X, and the chunk
    states.  Bytes are its inputs X, B, C at ``in_itemsize`` (the compute
    dtype's), the decays a and its outputs Y and states at float32."""
    c = s // chunk
    ops = b * c * (2 * chunk * chunk * n + 2 * chunk * chunk * h * p
                   + 2 * chunk * h * p * n)
    narrow = b * s * h * p + 2 * b * s * n                   # X, B, C
    wide = b * s * h + b * s * h * p + b * c * h * p * n     # a, Y, states
    return ops, narrow * in_itemsize + wide * 4


def ssd_forward_ops_per_token(h: int, p: int, n: int, chunk: int) -> float:
    """Intra-chunk block plus the chunk states' contribution to the
    outputs, per token."""
    return 2 * chunk * n + 2 * chunk * h * p + 4 * h * p * n


def mamba2_train_ops_per_token(cj: dict) -> float:
    """Forward and backward operations per trained token (3 x forward):
    every projection, the head over the padded vocabulary and the SSD
    chunk work.  The embedding gather, conv, norms and elementwise work
    are left out."""
    s = cj["ssm_cfg"]
    m = cj["pad_vocab_size_multiple"]
    d, L, V = cj["d_model"], cj["n_layer"], -(-cj["vocab_size"] // m) * m
    d_in, n = s["expand"] * d, s["d_state"]
    h = d_in // s["headdim"]
    proj = d * (2 * d_in + 2 * n + h) + d_in * d
    fwd = (2 * (L * proj + d * V)
           + L * ssd_forward_ops_per_token(h, s["headdim"], n,
                                           s["chunk_size"]))
    return 3.0 * fwd

