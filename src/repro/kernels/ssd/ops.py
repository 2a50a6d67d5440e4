"""SSD dispatch: Pallas intra-chunk kernel + jnp inter-chunk recurrence on
TPU; full jnp oracle elsewhere."""
from __future__ import annotations

import jax

from ...sharding.context import batch_parallel
from .kernel import ssd_chunk_pallas
from .ref import ssd_decode_ref, ssd_ref


def ssd(x, a, B, C, chunk: int = 256, initial_state=None, force_ref=False):
    if jax.default_backend() != "tpu" or force_ref:
        return ssd_ref(x, a, B, C, chunk=chunk, initial_state=initial_state)

    def chunk_fn(xc, ac, Bc, Cc):
        return batch_parallel(ssd_chunk_pallas, (xc, ac, Bc, Cc),
                              (True, True, True, True))

    return ssd_ref(x, a, B, C, chunk=chunk, initial_state=initial_state,
                   chunk_fn=chunk_fn)


def ssd_decode(x_t, a_t, B_t, C_t, state):
    return ssd_decode_ref(x_t, a_t, B_t, C_t, state)
