"""Train step: loss → grad → AdamW, with microbatch gradient accumulation,
per-layer remat, and logical-rule sharding on params / optimizer state /
batch.  The returned step is a plain jit-able function; ``lower_train_step``
gives the dry-run entry point (AOT lower + compile on abstract inputs).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig, ShapeSpec
from ..models.transformer import (abstract_params, build_specs, forward,
                                  lm_loss, lm_loss_chunked)
from ..sharding import (DEFAULT_RULES, LogicalRules, apply_rules,
                        logical_sharding, sharding_ctx, shardings_for)
from .optimizer import AdamWConfig, AdamWState, abstract_state, apply_updates


def batch_structs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStructs for one global training batch."""
    B, S = shape.global_batch, shape.seq_len
    batch = {
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    if cfg.family == "audio":
        # frontend stub: precomputed EnCodec frame embeddings
        batch["inputs_embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                      jnp.bfloat16)
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    return batch


def batch_shardings(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                    rules: Optional[LogicalRules] = None):
    structs = batch_structs(cfg, shape)
    names = {
        "labels": ("batch", "seq"),
        "tokens": ("batch", "seq"),
        "inputs_embeds": ("batch", "seq", "act_embed"),
        "img_embeds": ("batch", "seq", "act_embed"),
    }
    return {k: logical_sharding(names[k], v.shape, mesh, rules)
            for k, v in structs.items()}


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, mesh: Mesh,
                    rules: Optional[LogicalRules] = None, *,
                    remat: str = "full", microbatches: int = 1,
                    unroll: int = 1, loss_impl: str = "dense"):
    """Returns ``train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)`` with sharding applied inside via the ambient context.
    ``loss_impl='chunked'`` streams the vocab in the CE (memory-efficient)."""

    def loss_fn(params, batch):
        with sharding_ctx(mesh, rules):
            out, aux = forward(
                params, cfg,
                batch.get("tokens"),
                inputs_embeds=batch.get("inputs_embeds"),
                img_embeds=batch.get("img_embeds"),
                remat=remat, unroll=unroll,
                return_hidden=(loss_impl == "chunked"))
            maux = aux if cfg.family == "moe" else None
            if loss_impl == "chunked":
                return lm_loss_chunked(out, params, cfg, batch["labels"],
                                       maux)
            return lm_loss(out, batch["labels"], maux)

    def train_step(params, opt_state: AdamWState, batch):
        if microbatches > 1:
            def micro(g_acc, mb):
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return jax.tree.map(jnp.add, g_acc,
                                    jax.tree.map(
                                        lambda x: x.astype(jnp.float32) /
                                        microbatches, g)), l
            mbs = jax.tree.map(
                lambda x: x.reshape((microbatches,
                                     x.shape[0] // microbatches) + x.shape[1:]),
                batch)
            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              params)
            grads, losses = jax.lax.scan(micro, g0, mbs)
            loss = losses.mean()
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with sharding_ctx(mesh, rules):
            params, opt_state, metrics = apply_updates(params, grads,
                                                       opt_state, opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def train_shardings(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                    rules: Optional[LogicalRules] = None):
    """(params, optimizer state, batch) shardings of one train step."""
    params_sh = shardings_for(build_specs(cfg), mesh, rules)
    opt_sh = AdamWState(NamedSharding(mesh, P()), params_sh, params_sh)
    return params_sh, opt_sh, batch_shardings(cfg, shape, mesh, rules)


def lower_train_step(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                     rules: Optional[LogicalRules] = None, *,
                     remat: str = "full", microbatches: int = 1,
                     opt_cfg: Optional[AdamWConfig] = None, unroll: int = 1,
                     loss_impl: str = "dense"):
    """AOT-lower the train step on abstract inputs (the dry-run entry, and
    the trainer's own compile)."""
    opt_cfg = opt_cfg or AdamWConfig()
    params_s = abstract_params(cfg)
    opt_s = abstract_state(params_s)
    batch_s = batch_structs(cfg, shape)
    params_sh, opt_sh, batch_sh = train_shardings(cfg, shape, mesh, rules)

    step = make_train_step(cfg, opt_cfg, mesh, rules, remat=remat,
                           microbatches=microbatches, unroll=unroll,
                           loss_impl=loss_impl)
    jitted = jax.jit(
        step,
        in_shardings=(params_sh, opt_sh, batch_sh),
        out_shardings=(params_sh, opt_sh, None),
        donate_argnums=(0, 1))
    return jitted.lower(params_s, opt_s, batch_s)
