"""Ambient activation-sharding context.

Model code calls ``constrain(x, ("batch", "seq", "act_embed"))``; when a
(mesh, rules) context is active (set by the train/serve step builders), this
lowers to ``with_sharding_constraint`` with the logical rules applied —
otherwise it is a no-op (CPU smoke tests, plain eager use).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

from .rules import LogicalRules, apply_rules

_CTX: contextvars.ContextVar = contextvars.ContextVar("shard_ctx",
                                                      default=None)


@contextlib.contextmanager
def sharding_ctx(mesh, rules: Optional[LogicalRules] = None):
    token = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(token)


def batch_parallel(fn, args: Tuple[jax.Array, ...],
                   batched: Tuple[bool, ...]):
    """``fn(*args)``, run once per batch shard when the active context
    shards the batch.

    A Pallas kernel is a custom call the SPMD partitioner cannot split: left
    alone it gathers every operand onto each device and computes the whole
    batch there.  Under ``shard_map`` each device runs the kernel on its own
    batch slice.  Args flagged in ``batched`` split on dim 0 along the
    rules' ``batch`` axes, the rest are replicated, and every output splits
    on dim 0.  Without a context, or when dim 0 cannot split, this is
    ``fn(*args)``."""
    ctx = _CTX.get()
    if ctx is None:
        return fn(*args)
    mesh, rules = ctx
    spec = apply_rules(("batch",), args[0].shape[:1], mesh, rules)
    if mesh.size == 1 or spec[0] is None:
        return fn(*args)
    in_specs = tuple(spec if b else P() for b in batched)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=spec,
                         check_vma=False)(*args)


def constrain(x: jax.Array, names: Sequence[Optional[str]]) -> jax.Array:
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = apply_rules(names, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))
