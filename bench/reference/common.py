"""What the plain references share: the seed's key, float32 products at
the highest precision, the float8 control's rounding, RMSNorm and the
per-leaf norms that the comparison reads.  Imports nothing of the
program."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, 64-bit ones included."""
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def fp8_round(x):
    """x rounded to float8 e4m3 under one scale for the tensor."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(x):
    """A product's operand in float8; its gradient is rounded the same way
    on the way back, as a float8 training path computes it."""
    return fp8_round(x)


fp8.defvjp(lambda x: (fp8_round(x), None),
           lambda _, g: (fp8_round(g),))


def quantizer(kind: str):
    if kind == "none":
        return lambda x: x.astype(jnp.float32)
    if kind == "fp8":
        return fp8
    raise ValueError(f"unknown quantization {kind!r}")


def einsum(q, spec, *ops):
    return jnp.einsum(spec, *[q(o) for o in ops], precision=HIGHEST)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _stacked(path) -> bool:
    """A leaf stacked over the layers: the scan's ``blocks``."""
    return getattr(path[0], "key", None) == "blocks"


def leaf_names(tree, per_layer: bool = False) -> List[str]:
    """The leaves' key paths; with ``per_layer`` each leaf stacked over the
    layers counts as one leaf per layer, named ``<path>[<layer>]``."""
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if per_layer and _stacked(path):
            out += [f"{name}[{i}]" for i in range(a.shape[0])]
        else:
            out.append(name)
    return out


def leaf_norms(tree, per_layer: bool = False) -> jax.Array:
    """The float32 norm of every leaf of :func:`leaf_names`, in its order."""
    parts = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sq = jnp.square(a.astype(jnp.float32))
        if per_layer and _stacked(path):
            parts.append(jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, a.ndim)))))
        else:
            parts.append(jnp.sqrt(jnp.sum(sq))[None])
    return jnp.concatenate(parts)
