"""The program's dense model with QK-norm against the benchmark's plain
Qwen3 reference (``bench/reference/qwen3.py``), element by element, at a
tiny size on the CPU in float32: loss, logits and every gradient leaf.

Every weight is random, the norm weights too: with unit norm weights a
per-head RMSNorm commutes with RoPE (a rotation keeps the head's norm),
so a norm placed after RoPE would go unseen."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import qwen3 as family
from bench.reference import qwen3 as ref
from repro.models.transformer import forward, lm_loss

REPO = Path(__file__).resolve().parents[1]
# float32 on both sides; the program's attention is the blockwise
# online-softmax oracle and the reference a plain softmax, so they agree
# to float32 round-off of sums over at most 128 terms: 1e-5 relative,
# with an absolute floor for entries that are themselves near 0
RTOL, ATOL = 1e-4, 1e-6


def tiny_cj(**kw) -> dict:
    cj = json.loads((REPO / "bench/configs/qwen3-1.7b.json").read_text())
    cj.update(name="tiny-qwen3", hidden_size=64, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              intermediate_size=128, vocab_size=256, param_dtype="float32",
              compute_dtype="float32",
              # larger than published, so that attention is far from
              # uniform and the logits spread
              initializer_range=0.2)
    cj["deployment"] = dict(cj["deployment"], rows=4, seq_len=32)
    cj.update(kw)
    return cj


def random_weights(cj, seed=0):
    """The reference's weights with every norm weight moved off 1."""
    w = ref.init_weights(cj, ref.seed_key(seed))
    key = jax.random.PRNGKey(seed + 1)

    def move(path, a):
        if "norm" not in jax.tree_util.keystr(path):
            return a
        k = jax.random.fold_in(key, a.size + a.shape[-1])
        return a + 0.3 * jax.random.normal(k, a.shape, a.dtype)
    return jax.tree_util.tree_map_with_path(move, w)


def rows(cj, seed=0, b=2, T=32):
    tok = jax.random.randint(jax.random.PRNGKey(100 + seed), (b, T + 1), 0,
                             cj["vocab_size"])
    return tok[:, :-1], tok[:, 1:]


def program_loss(w, tokens, labels, cfg):
    logits, _ = forward(w, cfg, tokens, remat="none")
    return lm_loss(logits, labels)


@pytest.fixture(scope="module")
def case():
    cj = tiny_cj()
    w = random_weights(cj)
    tokens, labels = rows(cj)
    return cj, family.model_config(cj), w, tokens, labels


def test_the_norm_weights_are_random(case):
    cj, _, w, _, _ = case
    for name in ("attn_norm", "q_norm", "k_norm", "ffn_norm"):
        assert float(jnp.std(w["blocks"][name])) > 0.1, name
    assert float(jnp.std(w["final_norm"])) > 0.1


def test_logits_match_element_by_element(case):
    cj, cfg, w, tokens, _ = case
    got, _ = jax.jit(lambda w, t: forward(w, cfg, t, remat="none"))(w, tokens)
    want = jax.jit(lambda w, t: ref.logits(w, t, cj))(w, tokens)
    assert float(jnp.std(want)) > 0.5           # far from uniform
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=1e-5)


def test_loss_and_every_gradient_leaf_match(case):
    cj, cfg, w, tokens, labels = case
    lp, gp = jax.jit(jax.value_and_grad(
        lambda w: program_loss(w, tokens, labels, cfg)))(w)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda w: ref.loss(w, tokens, labels, cj)))(w)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)
    names = ref.leaf_names(w)
    for name, a, b in zip(names, jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert a.shape == b.shape, name
        # the gradient's own scale sets the absolute floor
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=RTOL,
                                   atol=ATOL * max(scale, 1.0),
                                   err_msg=name)


def test_the_program_runs_the_published_norm_epsilon():
    from repro.configs.qwen3_1_7b import CONFIG
    cj = json.loads((REPO / "bench/configs/qwen3-1.7b.json").read_text())
    assert CONFIG.norm_eps == cj["rms_norm_eps"] == 1e-6
    assert family.model_config(cj).norm_eps == 1e-6


@pytest.mark.parametrize("mutant", ["norm_after_rope", "interleaved_gqa",
                                    "interleaved_rope", "no_qk_norm"])
def test_a_departure_in_the_reference_is_seen(case, monkeypatch, mutant):
    """Each departure that a norm comparison could miss changes some
    logit by far more than the tolerance."""
    cj, cfg, w, tokens, _ = case
    rope, rms = ref.rope, ref.rms
    if mutant == "norm_after_rope":
        # RoPE first, then the per-head norm: the program normalises first
        def rms_spy(x, weight, eps):
            if x.ndim == 4:                      # the per-head norm
                return rms(rope(x, cj["rope_theta"]), weight, eps)
            return rms(x, weight, eps)
        monkeypatch.setattr(ref, "rms", rms_spy)
        monkeypatch.setattr(ref, "rope", lambda x, theta: x)
    elif mutant == "interleaved_gqa":
        # query head i reading kv head i % KV instead of i // (H / KV)
        attention = ref.attention

        def interleaved(q, k, v, qz):
            b, T, H, hd = q.shape
            KV = k.shape[2]
            q = q.reshape(b, T, H // KV, KV, hd).swapaxes(2, 3).reshape(
                b, T, H, hd)
            o = attention(q, k, v, qz).reshape(b, T, KV, H // KV, hd)
            return o.swapaxes(2, 3).reshape(b, T, H * hd)
        monkeypatch.setattr(ref, "attention", interleaved)
    elif mutant == "interleaved_rope":
        # rotating pairs (2i, 2i+1) instead of (i, i + hd/2)
        def pairwise(x, theta):
            b, T, h, hd = x.shape
            y = x.reshape(b, T, h, hd // 2, 2).swapaxes(-1, -2).reshape(
                b, T, h, hd)
            y = rope(y, theta)
            return y.reshape(b, T, h, 2, hd // 2).swapaxes(-1, -2).reshape(
                b, T, h, hd)
        monkeypatch.setattr(ref, "rope", pairwise)
    else:
        # q and k left unnormalised
        def rms_spy(x, weight, eps):
            return x if x.ndim == 4 else rms(x, weight, eps)
        monkeypatch.setattr(ref, "rms", rms_spy)
    got, _ = jax.jit(lambda w, t: forward(w, cfg, t, remat="none"))(w, tokens)
    want = jax.jit(lambda w, t: ref.logits(w, t, cj))(w, tokens)
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap > 100 * (RTOL * float(jnp.max(jnp.abs(want))) + 1e-5), gap
