"""The program's side of the ``qwen3`` family: its model configuration,
built from the benchmark's configuration file, and the work that MFU and
the flash kernel's roofline share are measured against."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.config import ModelConfig


def model_config(cj: dict) -> ModelConfig:
    return ModelConfig(
        name=cj["name"], family="dense", n_layers=cj["num_hidden_layers"],
        d_model=cj["hidden_size"], n_heads=cj["num_attention_heads"],
        n_kv_heads=cj["num_key_value_heads"], head_dim=cj["head_dim"],
        d_ff=cj["intermediate_size"], vocab=cj["vocab_size"], qk_norm=True,
        qkv_bias=cj["attention_bias"], rope_theta=float(cj["rope_theta"]),
        norm_eps=cj["rms_norm_eps"],
        tie_embeddings=cj["tie_word_embeddings"])


def train_ops_per_token(cj: dict) -> float:
    """Forward and backward operations per trained token (3 x forward) at
    the deployment's sequence length: the q, k, v, o and SwiGLU
    projections, causal attention at the algorithm's half (QK^T and PV
    over S/2 keys on average) and the head over the whole vocabulary.
    The embedding gather, norms, RoPE and softmax are left out."""
    d, L, V = cj["hidden_size"], cj["num_hidden_layers"], cj["vocab_size"]
    H, KV, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                 cj["head_dim"])
    f, S = cj["intermediate_size"], cj["deployment"]["seq_len"]
    proj = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    attn = 2 * (S // 2) * H * hd                 # QK^T and PV, causal half
    return 3.0 * (2 * L * (proj + attn) + 2 * d * V)


def flash_kernel_work(cj: dict, batch: int, seq_len: int) -> tuple:
    """(operations, bytes) of one call of the forward flash-attention
    kernel over ``batch`` rows of ``seq_len`` tokens: the causal half of
    QK^T and PV; q, k, v and o read or written once in the
    configuration's compute dtype."""
    H, KV, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                 cj["head_dim"])
    ops = 2 * 2 * batch * H * hd * (seq_len * seq_len // 2)
    elems = batch * seq_len * hd * (2 * H + 2 * KV)
    return ops, elems * jnp.dtype(cj["compute_dtype"]).itemsize
