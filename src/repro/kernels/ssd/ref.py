"""Pure-jnp Mamba2 SSD (state-space duality) oracle — chunked algorithm.

Follows the minimal SSD formulation (Dao & Gu, arXiv:2405.21060): the
sequence is split into chunks; within a chunk the recurrence is the masked
quadratic form (C Bᵀ ∘ L) X (matmul-friendly, the "duality"), across chunks a
small state recurrence carries (h, p, n) states.

Conventions: x (b, s, h, p) pre-multiplied by dt; a (b, s, h) = dt * A_log
(negative); B, C (b, s, n) single group shared across heads.
Returns (y, final_state (b, h, p, n)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def segsum(x):
    """x (..., l) → (..., l, l): S[i, j] = sum_{k in (j, i]} x[k], -inf for j>i."""
    l = x.shape[-1]
    cum = jnp.cumsum(x, axis=-1)
    d = cum[..., :, None] - cum[..., None, :]
    i = jnp.arange(l)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, d, NEG_INF)


def ssd_chunk_ref(xc, ac, Bc, Cc):
    """Intra-chunk block (the Pallas kernel's oracle).

    xc (b, c, l, h, p); ac (b, c, l, h); Bc/Cc (b, c, l, n) →
    (y_diag (b, c, l, h, p), chunk output states (b, c, h, p, n))."""
    at = ac.transpose(0, 3, 1, 2)                          # (b,h,c,l)
    a_cum = jnp.cumsum(at, axis=-1)
    L = jnp.exp(segsum(at))                                # (b,h,c,l,l)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xc)
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)        # (b,h,c,l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, xc)
    return y_diag, states


def ssd_ref(x, a, B, C, chunk: int = 256, initial_state=None,
            chunk_fn=ssd_chunk_ref):
    """Chunked SSD.  ``chunk_fn`` computes the intra-chunk block with
    :func:`ssd_chunk_ref`'s contract; the TPU path passes the Pallas
    kernel, everything around it is shared."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = x.shape[1]
    c = sp // chunk

    xc = x.astype(jnp.float32).reshape(b, c, chunk, h, p)
    ac = a.astype(jnp.float32).reshape(b, c, chunk, h)
    Bc = B.astype(jnp.float32).reshape(b, c, chunk, n)
    Cc = C.astype(jnp.float32).reshape(b, c, chunk, n)

    y_diag, states = chunk_fn(xc, ac, Bc, Cc)
    a_cum = jnp.cumsum(ac.transpose(0, 3, 1, 2), axis=-1)  # (b,h,c,l)
    # inter-chunk recurrence
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    states = jnp.concatenate([initial_state[:, None], states], axis=1)  # (b,c+1,h,p,n)
    chunk_decay = a_cum[..., -1]                          # (b,h,c)
    dc = jnp.exp(segsum(jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0)))))  # (b,h,c+1,c+1)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", dc, states)
    carry, final = new_states[:, :-1], new_states[:, -1]
    # inter-chunk contribution
    out_decay = jnp.exp(a_cum)                            # (b,h,c,l)
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, carry, out_decay)
    y = (y_diag + y_off).reshape(b, sp, h, p)[:, :s]
    return y.astype(x.dtype), final


def ssd_decode_ref(x_t, a_t, B_t, C_t, state):
    """One decode step.  x_t (b, h, p) pre-multiplied by dt; a_t (b, h);
    B_t, C_t (b, n); state (b, h, p, n) → (y_t, new_state)."""
    decay = jnp.exp(a_t.astype(jnp.float32))[..., None, None]      # (b,h,1,1)
    upd = jnp.einsum("bhp,bn->bhpn", x_t.astype(jnp.float32),
                     B_t.astype(jnp.float32))
    new_state = state * decay + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, C_t.astype(jnp.float32))
    return y.astype(x_t.dtype), new_state
