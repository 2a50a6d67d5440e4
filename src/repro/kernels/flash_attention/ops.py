"""Dispatching wrapper: Pallas on TPU, jnp oracle elsewhere (CPU dry-run &
tests).  On a TPU the forward and the backward (dq, dk, dv) are both Pallas
kernels (``kernel.py``); elsewhere the gradient is autodiff through the
oracle.  The two paths, gradients included, are numerically cross-checked in
tests/test_kernels.py (interpret=True)."""
from __future__ import annotations

import functools

import jax

from ...sharding.context import batch_parallel
from .kernel import flash_attention_pallas
from .ref import decode_attention_ref, flash_attention_ref


def flash_attention(q, k, v, *, causal=True, q_offset=0, block_kv=1024,
                    softmax_scale=None, force_ref=False):
    if jax.default_backend() == "tpu" and not force_ref:
        kernel = functools.partial(flash_attention_pallas, causal=causal,
                                   q_offset=q_offset,
                                   softmax_scale=softmax_scale)
        return batch_parallel(kernel, (q, k, v), (True, True, True))
    return flash_attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                               block_kv=block_kv, softmax_scale=softmax_scale)


def decode_attention(q, k, v, kv_len, softmax_scale=None):
    # Single-query attention is memory-bound; the einsum form lets XLA fuse
    # and shard it (incl. sequence-sharded caches) without a custom kernel.
    return decode_attention_ref(q, k, v, kv_len, softmax_scale=softmax_scale)
