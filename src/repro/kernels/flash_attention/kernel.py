"""Pallas TPU flash-attention kernels (causal, GQA): forward and backward.

Forward: grid (B, H, num_q_blocks, num_kv_blocks); the kv axis is the
innermost (sequential on TPU), so the online-softmax running state (m, l,
acc) lives in VMEM scratch and persists across kv steps.  GQA is expressed in
the k/v ``index_map`` (kv head = q head // groups) — no host-side repeat.

Block shapes are MXU-aligned (q/kv tiles multiples of 128 on the contracting
dim, head_dim itself 64/128).  VMEM footprint per step:
  q (Bq, hd) bf16 + k,v (Bk, hd) bf16 + acc (Bq, hd) f32 + m,l (Bq,) f32
≈ 0.8 MB at Bq=Bk=512, hd=128 — well inside the ~16 MB VMEM budget.

Differentiable through ``jax.custom_vjp``: the forward rule saves q, k, v and
the output; the backward is three Pallas kernels that keep every score block
in VMEM (nothing of size Sq x Skv reaches HBM):

* ``_lse_kernel``, grid (B, H, nq, nk): each query row's log-sum-exp;
* ``_dq_kernel``, grid (B, H, nq, nk): dq of one q block over the kv blocks
  it sees;
* ``_dkv_kernel``, grid (B, KV, nk, groups, nq): dk and dv of one kv block,
  summed over its group's q heads and the q blocks that see it.

With D = rowsum(dO * O) (float32, in XLA), P = exp(S - lse) and
dS = P * (dO V^T - D): dv = P^T dO, dk = scale dS^T Q, dq = scale dS K.
The backward works on transposed score blocks S^T = K Q^T (Bk, Bq), so the
per-row lse and D are lane rows (1, Bq) and dk/dv need no in-kernel
transpose.  Every product takes its operands in the dtype of q, k and v (P
and dS are cast to it) and accumulates in float32; lse, D and the
accumulators are float32.  Blocks wholly above the causal diagonal are
skipped, and their index maps clamped so that they are not fetched; the
mask is built only for blocks that straddle the diagonal or hold padded
keys.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_kv: int, causal: bool,
                  q_offset: int, kv_valid: int, num_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = k_pos < kv_valid
    if causal:
        mask = mask & (q_pos >= k_pos)

    # skip fully-masked blocks (above the causal diagonal)
    run = (not causal) or True

    @pl.when(jnp.any(mask))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (Bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (Bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (Bq, Bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    @pl.when(ki == num_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _flash_call(q, k, v, causal, q_offset, block_q, block_kv, scale,
                interpret):
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    groups = H // KV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    kv_valid = Skv
    if Sq % block_q:
        raise ValueError(f"Sq={Sq} not divisible by block_q={block_q}")
    if Skv % block_kv:
        pad = block_kv - Skv % block_kv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Skv += pad
    nq, nk = Sq // block_q, Skv // block_kv

    qt = q.transpose(0, 2, 1, 3)   # (B, H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)   # (B, KV, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, q_offset=q_offset, kv_valid=kv_valid, num_kv=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


# ------------------------------------------------------------- backward

def _run_block(step, i, j, *, block_q, block_kv, causal, q_offset, kv_valid,
               kv_len, **_):
    """``step(masked)`` for q block i against kv block j: skipped where the
    block lies wholly above the causal diagonal, masked only where some
    (query, key) pair in it is masked (it straddles the diagonal or holds
    padded keys)."""
    visible, partial = True, []
    if causal:
        visible = q_offset + (i + 1) * block_q - 1 >= j * block_kv
        partial.append(q_offset + i * block_q < (j + 1) * block_kv - 1)
    if kv_valid < kv_len:
        partial.append((j + 1) * block_kv > kv_valid)
    if not partial:
        pl.when(visible)(functools.partial(step, False))
        return
    partial = functools.reduce(jnp.logical_or, partial)
    pl.when(jnp.logical_and(visible, partial))(functools.partial(step, True))
    pl.when(jnp.logical_and(visible, jnp.logical_not(partial)))(
        functools.partial(step, False))


def _scores_t(q, k, i, j, masked, *, scale, block_q, block_kv, causal,
              q_offset, kv_valid, **_):
    """S^T = scale K Q^T of one block, (Bk, Bq) float32, masked entries at
    NEG_INF."""
    s = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if masked:
        k_pos = j * block_kv + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        keep = k_pos < kv_valid
        if causal:
            q_pos = q_offset + i * block_q + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            keep = keep & (q_pos >= k_pos)
        s = jnp.where(keep, s, NEG_INF)
    return s


def _probs_and_dscores_t(q, k, v, do, lse, di, i, j, masked, **kw):
    """P^T and dS^T of one block, (Bk, Bq) float32."""
    p = jnp.exp(_scores_t(q, k, i, j, masked, **kw) - lse)
    dp = lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return p, p * (dp - di)


def _lse_kernel(q_ref, k_ref, lse_ref, m_ref, l_ref, *, num_kv, **kw):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked):
        s = _scores_t(q_ref[0, 0], k_ref[0, 0], i, j, masked, **kw)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                      + jnp.exp(s - m_new).sum(axis=0, keepdims=True))
        m_ref[...] = m_new

    _run_block(step, i, j, **kw)

    @pl.when(j == num_kv - 1)
    def _finish():
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, *,
               num_kv, **kw):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def step(masked):
        k = k_ref[0, 0]
        _, ds = _probs_and_dscores_t(q_ref[0, 0], k, v_ref[0, 0],
                                     do_ref[0, 0], lse_ref[0, 0],
                                     di_ref[0, 0], i, j, masked, **kw)
        dq_ref[0, 0] += lax.dot_general(ds.astype(k.dtype), k,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    _run_block(step, i, j, **kw)

    @pl.when(j == num_kv - 1)
    def _finish():
        dq_ref[...] = dq_ref[...] * kw["scale"]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, num_q, groups, **kw):
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _probs_and_dscores_t(q, k_ref[0, 0], v_ref[0, 0], do,
                                     lse_ref[0, 0], di_ref[0, 0], i, j,
                                     masked, **kw)
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    _run_block(step, i, j, **kw)

    @pl.when((g == groups - 1) & (i == num_q - 1))
    def _finish():
        dk_ref[0, 0] = (dk_acc[...] * kw["scale"]).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_call(q, k, v, out, do, causal, q_offset, block_q, block_kv,
                    scale, interpret):
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    groups = H // KV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    kv_valid = Skv
    if Sq % block_q:
        raise ValueError(f"Sq={Sq} not divisible by block_q={block_q}")
    if Skv % block_kv:
        pad = block_kv - Skv % block_kv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Skv += pad
    nq, nk = Sq // block_q, Skv // block_kv

    qt = q.transpose(0, 2, 1, 3)    # (B, H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)    # (B, KV, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)
    gt = do.transpose(0, 2, 1, 3)    # dO, (B, H, Sq, hd)
    # D = rowsum(dO * O) per query row, as a lane row: (B, H, 1, Sq)
    di = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                    out.astype(jnp.float32))[:, :, None, :]

    kw = dict(scale=scale, block_q=block_q, block_kv=block_kv,
              causal=causal, q_offset=q_offset, kv_valid=kv_valid,
              kv_len=Skv)

    def last_kv(i):   # the last kv block that q block i sees
        if not causal:
            return nk - 1
        return jnp.clip((q_offset + (i + 1) * block_q - 1) // block_kv,
                        0, nk - 1)

    def first_q(j):   # the first q block that sees kv block j
        if not causal:
            return 0
        return jnp.clip((j * block_kv - q_offset) // block_q, 0, nq - 1)

    # grid (b, h, i, j): q-side blocks fixed, kv blocks past the diagonal
    # clamped to the last one seen, so the pipeline fetches nothing new
    q_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda b, h, i, j: (b, h, 0, i))
    kv_spec = pl.BlockSpec(
        (1, 1, block_kv, hd),
        lambda b, h, i, j: (b, h // groups, jnp.minimum(j, last_kv(i)), 0))
    lse = pl.pallas_call(
        functools.partial(_lse_kernel, num_kv=nk, **kw),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_q), jnp.float32),
                        pltpu.VMEM((1, block_q), jnp.float32)],
        interpret=interpret,
    )(qt, kt)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_kv=nk, **kw),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), jnp.float32),
        interpret=interpret,
    )(qt, kt, vt, gt, lse, di)

    # grid (b, kv head, j, g, i): q blocks before the diagonal clamped to
    # the first one that sees kv block j
    def q_head(h, j, g, i):
        return h * groups + g, jnp.maximum(i, first_q(j))

    def q_index(b, h, j, g, i):
        qh, qi = q_head(h, j, g, i)
        return b, qh, qi, 0

    def row_index(b, h, j, g, i):
        qh, qi = q_head(h, j, g, i)
        return b, qh, 0, qi

    dkv_q_spec = pl.BlockSpec((1, 1, block_q, hd), q_index)
    dkv_row_spec = pl.BlockSpec((1, 1, 1, block_q), row_index)
    dkv_kv_spec = pl.BlockSpec((1, 1, block_kv, hd),
                               lambda b, h, j, g, i: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q=nq, groups=groups, **kw),
        grid=(B, KV, nk, groups, nq),
        in_specs=[dkv_q_spec, dkv_kv_spec, dkv_kv_spec, dkv_q_spec,
                  dkv_row_spec, dkv_row_spec],
        out_specs=[dkv_kv_spec, dkv_kv_spec],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, hd), jnp.float32),
                        pltpu.VMEM((block_kv, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, gt, lse, di)

    dq = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    dk = dk[:, :, :kv_valid].transpose(0, 2, 1, 3)
    dv = dv[:, :, :kv_valid].transpose(0, 2, 1, 3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, q_offset, block_q, block_kv, scale, interpret):
    return _flash_call(q, k, v, causal, q_offset, block_q, block_kv, scale,
                       interpret)


def _flash_fwd(q, k, v, causal, q_offset, block_q, block_kv, scale,
               interpret):
    out = _flash_call(q, k, v, causal, q_offset, block_q, block_kv, scale,
                      interpret)
    return out, (q, k, v, out)


def _flash_bwd(causal, q_offset, block_q, block_kv, scale, interpret, res,
               g):
    return _flash_bwd_call(*res, g, causal, q_offset, block_q, block_kv,
                           scale, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, q_offset: int = 0,
                           block_q: int = 512, block_kv: int = 512,
                           softmax_scale=None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd)."""
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(q.shape[-1]))
    return _flash(q, k, v, causal, q_offset, block_q, block_kv, scale,
                  interpret)
