from __future__ import annotations

import functools

import jax

from ...sharding.context import batch_parallel
from .kernel import rmsnorm_pallas
from .ref import gated_rmsnorm_ref, rmsnorm_ref


def rmsnorm(x, weight, eps: float = 1e-5, force_ref: bool = False):
    if jax.default_backend() == "tpu" and not force_ref:
        return batch_parallel(functools.partial(rmsnorm_pallas, eps=eps),
                              (x, weight), (True, False))
    return rmsnorm_ref(x, weight, eps=eps)


def gated_rmsnorm(x, gate, weight, eps: float = 1e-5):
    return gated_rmsnorm_ref(x, gate, weight, eps=eps)
