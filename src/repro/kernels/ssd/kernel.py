"""Pallas TPU kernel for the SSD intra-chunk block (the compute hot spot).

For one (batch, chunk, head) the kernel fuses, entirely in VMEM:
    scores   = C Bᵀ ∘ exp(segsum(a))      (l × l masked decay matmul)
    y_diag   = scores @ x                 (l × p)
    state    = (Bᵀ ∘ decay_to_end) @ x    (n × p chunk output state)
avoiding three HBM round-trips of (l, l) intermediates.  The cross-chunk
recurrence (tiny (h, p, n) states) stays in jnp — it is latency-, not
bandwidth-bound.

Layouts are chosen so the kernel needs no in-kernel cumsum, reshape or
transpose: the wrapper passes the chunk-local cumulative decay as a lane
row ``(1, l)`` per head and B already transposed to ``(n, l)``, so both
matmuls are plain (M, K) @ (K, N).  The column copy of the cumulative decay
that ``segsum`` needs is taken from the row by a diagonal mask and a lane
reduction.

VMEM at l=256, n=128, p=64: x 64 KB, B/C 128 KB each, scores 256 KB f32 —
comfortably within budget; all matmul dims are 64/128-aligned for the MXU.

Differentiable through ``jax.custom_vjp``: the forward is the Pallas kernel;
the backward is the VJP of the jnp oracle (``ref.ssd_chunk_ref``), recomputed
from the saved inputs.  On a TPU that backward is ordinary XLA code in the
same program as the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import ssd_chunk_ref

NEG_INF = -1e30


def _ssd_chunk_kernel(x_ref, cum_ref, bt_ref, c_ref, y_ref, st_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)        # (l, p)
    cum = cum_ref[0, 0, 0].astype(jnp.float32)    # (1, l)
    Bt = bt_ref[0, 0].astype(jnp.float32)         # (n, l)
    C = c_ref[0, 0].astype(jnp.float32)           # (l, n)
    l = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    cum_col = jnp.sum(jnp.where(ii == jj, cum, 0.0), axis=1,
                      keepdims=True)              # (l, 1)
    L = jnp.exp(jnp.where(ii >= jj, cum_col - cum, NEG_INF))
    scores = jnp.dot(C, Bt, preferred_element_type=jnp.float32) * L
    y_ref[0, 0, 0] = jnp.dot(scores, x, preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)
    decay_end = jnp.exp(cum[:, l - 1:] - cum)     # (1, l)
    st_ref[0, 0, 0] = jnp.dot(Bt * decay_end, x,
                              preferred_element_type=jnp.float32
                              ).astype(st_ref.dtype)


def _ssd_chunk_call(xc, ac, Bc, Cc, interpret):
    b, c, l, h, p = xc.shape
    n = Bc.shape[-1]
    xt = xc.transpose(0, 1, 3, 2, 4)                          # (b, c, h, l, p)
    cum = jnp.cumsum(ac, axis=2).transpose(0, 1, 3, 2)[:, :, :, None, :]
    Bt = Bc.transpose(0, 1, 3, 2)                             # (b, c, n, l)
    y, st = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(b, c, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, l, p), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, l), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, n, l), lambda i, j, k: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, l, p), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, n, p), lambda i, j, k: (i, j, k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, h, l, p), jnp.float32),
            jax.ShapeDtypeStruct((b, c, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(xt, cum, Bt, Cc)
    return y.transpose(0, 1, 3, 2, 4), st.transpose(0, 1, 2, 4, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ssd_chunk_pallas(xc, ac, Bc, Cc, interpret: bool = False):
    """xc (b, c, l, h, p); ac (b, c, l, h); Bc/Cc (b, c, l, n)
    → (y_diag (b, c, l, h, p), states (b, c, h, p, n))."""
    return _ssd_chunk_call(xc, ac, Bc, Cc, interpret)


def _fwd(xc, ac, Bc, Cc, interpret):
    return _ssd_chunk_call(xc, ac, Bc, Cc, interpret), (xc, ac, Bc, Cc)


def _bwd(interpret, res, g):
    _, vjp = jax.vjp(ssd_chunk_ref, *res)
    return vjp(g)


ssd_chunk_pallas.defvjp(_fwd, _bwd)
