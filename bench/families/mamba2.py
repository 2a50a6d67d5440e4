"""The program's side of the ``mamba2`` family: its model configuration,
built from the benchmark's configuration file."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.config import ModelConfig

from bench import flops
from bench.reference.mamba2 import vocab_rows


def model_config(cj: dict) -> ModelConfig:
    s = cj["ssm_cfg"]
    return ModelConfig(
        name=cj["name"], family="ssm", n_layers=cj["n_layer"],
        d_model=cj["d_model"], n_heads=0, n_kv_heads=0, d_ff=0,
        vocab=vocab_rows(cj), norm_eps=cj["norm_epsilon"],
        tie_embeddings=cj["tie_embeddings"], ssm_state=s["d_state"],
        ssm_head_dim=s["headdim"], ssm_expand=s["expand"],
        ssm_chunk=s["chunk_size"], conv_width=s["d_conv"])


def train_ops_per_token(cj: dict) -> float:
    return flops.mamba2_train_ops_per_token(cj)


def ssd_kernel_work(cj: dict, batch: int, seq_len: int) -> tuple:
    """(operations, bytes) of one call of the intra-chunk SSD kernel over
    ``batch`` rows of ``seq_len`` tokens, its X, B and C in the
    configuration's compute dtype."""
    s = cj["ssm_cfg"]
    d_in = s["expand"] * cj["d_model"]
    return flops.ssd_chunk_work(
        batch, seq_len, d_in // s["headdim"], s["headdim"], s["d_state"],
        s["chunk_size"], in_itemsize=jnp.dtype(cj["compute_dtype"]).itemsize)
