"""Sharding-variant rules + chunked-CE lowering smoke tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.launch.mesh import make_local_mesh
from repro.launch.variants import apply_variant
from repro.models.config import ShapeSpec
from repro.models.transformer import forward, init_params, lm_loss, lm_loss_chunked
from repro.sharding import DEFAULT_RULES
from repro.train.optimizer import AdamWConfig, init_state
from repro.train.train_step import make_train_step

ALL_VARIANTS = ["fsdp_pod", "no_fsdp", "seq_shard", "expert_data",
                "vocab_data", "cache_seq_model", "pure_fsdp",
                "embed_replicated", "decode_weights_stationary",
                "ep_capacity", "ep_only"]


@pytest.mark.parametrize("v", ALL_VARIANTS)
def test_variants_produce_valid_rules(v):
    rules = apply_variant(dict(DEFAULT_RULES), "qwen3-1.7b", "train_4k", v)
    assert isinstance(rules, dict)
    assert set(DEFAULT_RULES) <= set(rules)


def test_unknown_variant_raises():
    with pytest.raises(KeyError):
        apply_variant(dict(DEFAULT_RULES), "x", "train_4k", "nope")


def test_chunked_ce_matches_dense():
    cfg = reduced_config("qwen3-1.7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    logits, _ = forward(params, cfg, tokens, remat="none")
    dense = lm_loss(logits, tokens)
    x, _ = forward(params, cfg, tokens, remat="none", return_hidden=True)
    for chunk in (64, 100, 256):
        ck = lm_loss_chunked(x, params, cfg, tokens, vocab_chunk=chunk)
        assert float(jnp.abs(dense - ck)) < 1e-3


def test_chunked_ce_grad_matches_dense():
    cfg = reduced_config("qwen3-1.7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)

    def dense_loss(p):
        lg, _ = forward(p, cfg, tokens, remat="none")
        return lm_loss(lg, tokens)

    def chunked(p):
        x, _ = forward(p, cfg, tokens, remat="none", return_hidden=True)
        return lm_loss_chunked(x, p, cfg, tokens, vocab_chunk=64)

    g1 = jax.grad(dense_loss)(params)
    g2 = jax.grad(chunked)(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-2, rtol=2e-2)


def test_train_step_chunked_loss_runs():
    cfg = reduced_config("qwen3-1.7b")
    mesh = make_local_mesh()
    step = jax.jit(make_train_step(cfg, AdamWConfig(), mesh, None,
                                   remat="none", loss_impl="chunked"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    params, opt, m = step(params, opt, {"tokens": tokens, "labels": tokens})
    assert bool(jnp.isfinite(m["loss"]))
