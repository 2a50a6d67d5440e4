"""Pallas TPU flash-attention forward kernel (causal, GQA).

Grid (B, H, num_q_blocks, num_kv_blocks); the kv axis is the innermost
(sequential on TPU), so the online-softmax running state (m, l, acc) lives in
VMEM scratch and persists across kv steps.  GQA is expressed in the k/v
``index_map`` (kv head = q head // groups) — no host-side repeat.

Block shapes are MXU-aligned (q/kv tiles multiples of 128 on the contracting
dim, head_dim itself 64/128).  VMEM footprint per step:
  q (Bq, hd) bf16 + k,v (Bk, hd) bf16 + acc (Bq, hd) f32 + m,l (Bq,) f32
≈ 0.8 MB at Bq=Bk=512, hd=128 — well inside the ~16 MB VMEM budget.

Differentiable through ``jax.custom_vjp``: the forward is the Pallas kernel;
the backward is the VJP of the jnp oracle (``ref.flash_attention_ref``),
recomputed from the saved q, k, v.  On a TPU that backward is ordinary XLA
code in the same program as the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import flash_attention_ref

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, q_offset, block_q, block_kv, scale, interpret):
    return _flash_call(q, k, v, causal, q_offset, block_q, block_kv, scale,
                       interpret)


def _flash_fwd(q, k, v, causal, q_offset, block_q, block_kv, scale,
               interpret):
    out = _flash_call(q, k, v, causal, q_offset, block_q, block_kv, scale,
                      interpret)
    return out, (q, k, v)


def _flash_bwd(causal, q_offset, block_q, block_kv, scale, interpret, res,
               g):
    ref = functools.partial(flash_attention_ref, causal=causal,
                            q_offset=q_offset, block_kv=block_kv,
                            softmax_scale=scale)
    _, vjp = jax.vjp(ref, *res)
    return vjp(g)


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
