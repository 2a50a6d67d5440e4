"""Kernel oracles vs Pallas (interpret=True) — shape/dtype sweeps."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (decode_attention_ref,
                                           flash_attention_pallas,
                                           flash_attention_ref)
from repro.kernels.rmsnorm import (gated_rmsnorm_ref, rmsnorm_pallas,
                                   rmsnorm_ref)
from repro.kernels.ssd import ssd_chunk_pallas, ssd_decode_ref, ssd_ref
from repro.kernels.ssd.ref import segsum, ssd_chunk_ref


def naive_attention(q, k, v, causal=True, q_offset=0):
    groups = q.shape[2] // k.shape[2]
    kk = jnp.repeat(k, groups, axis=2)
    vv = jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / math.sqrt(q.shape[-1])
    if causal:
        qp = q_offset + jnp.arange(q.shape[1])
        kp = jnp.arange(k.shape[1])
        s = jnp.where((qp[:, None] >= kp[None, :])[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv.astype(jnp.float32))


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 32),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 192, 6, 1, 64),     # MQA, non-pow2 seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_ref_sweep(B, S, H, KV, hd, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
    want = naive_attention(q, k, v)
    got = flash_attention_ref(q, k, v, block_kv=64).astype(jnp.float32)
    tol = 5e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("B,S,H,KV,hd,bq,bk", [
    (1, 256, 4, 2, 64, 128, 128),
    (2, 256, 4, 4, 128, 64, 128),
])
def test_flash_pallas_interpret(B, S, H, KV, hd, bq, bk):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    want = naive_attention(q, k, v)
    got = flash_attention_pallas(q, k, v, block_q=bq, block_kv=bk,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_decode_attention_matches_last_row():
    rng = np.random.default_rng(2)
    B, S, H, KV, hd = 2, 64, 8, 4, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    want = naive_attention(q, k, v)[:, -1:]
    got = decode_attention_ref(q[:, -1:], k, v, kv_len=S)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("shape", [(4, 64), (3, 17, 96), (2, 2, 2, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    w = jnp.asarray(rng.normal(size=shape[-1:]), dtype)
    got = rmsnorm_pallas(x, w, interpret=True, block_rows=8)
    want = rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def _ssd_seq_oracle(x, a, B, C):
    b, s, h, p = x.shape
    n = B.shape[-1]
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y, state = ssd_decode_ref(x[:, t], a[:, t], B[:, t], C[:, t], state)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_ssd_chunked_vs_sequential(chunk):
    rng = np.random.default_rng(4)
    b, s, h, p, n = 2, 64, 3, 8, 4
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32) * 0.5
    a = -jnp.abs(jnp.asarray(rng.normal(size=(b, s, h)), jnp.float32)) * 0.1
    Bm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32) * 0.5
    Cm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32) * 0.5
    y_ref, st_ref = ssd_ref(x, a, Bm, Cm, chunk=chunk)
    y_seq, st_seq = _ssd_seq_oracle(x, a, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_seq),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_ref), np.asarray(st_seq),
                               atol=1e-4)


def test_ssd_pallas_chunk_kernel():
    rng = np.random.default_rng(5)
    b, s, h, p, n = 1, 64, 2, 16, 8
    chunk = 16
    c = s // chunk
    x = jnp.asarray(rng.normal(size=(b, c, chunk, h, p)), jnp.float32) * 0.5
    a = -jnp.abs(jnp.asarray(rng.normal(size=(b, c, chunk, h)),
                             jnp.float32)) * 0.1
    Bm = jnp.asarray(rng.normal(size=(b, c, chunk, n)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b, c, chunk, n)), jnp.float32)
    y, st = ssd_chunk_pallas(x, a, Bm, Cm, interpret=True)
    aT = a.transpose(0, 3, 1, 2)
    L = jnp.exp(segsum(aT))
    y_want = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cm, Bm, L, x)
    acum = jnp.cumsum(aT, -1)
    dec = jnp.exp(acum[..., -1:] - acum)
    st_want = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bm, dec, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want), atol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_want), atol=1e-4)


def test_gated_rmsnorm_finite():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    w = jnp.ones((32,), jnp.float32)
    out = gated_rmsnorm_ref(x, g, w)
    assert bool(jnp.isfinite(out).all())


def _cotangent_loss(out, seed):
    """Scalar loss with a random cotangent on every output leaf, so the
    gradient checks each output element rather than only their sum."""
    leaves = jax.tree.leaves(out)
    rng = np.random.default_rng(seed)
    return sum((leaf.astype(jnp.float32)
                * jnp.asarray(rng.normal(size=leaf.shape), jnp.float32)).sum()
               for leaf in leaves)


def _grad_case(name):
    rng = np.random.default_rng(7)
    if name == "rmsnorm":
        args = (jnp.asarray(rng.normal(size=(3, 24, 128)), jnp.float32),
                jnp.asarray(rng.normal(size=(128,)), jnp.float32))
        return (args, lambda x, w: rmsnorm_pallas(x, w, block_rows=16,
                                                  interpret=True),
                rmsnorm_ref)
    if name in ("flash_causal", "flash_cross"):
        causal = name == "flash_causal"
        B, S, H, KV, hd = 1, 128, 4, 2, 64
        args = tuple(jnp.asarray(rng.normal(size=s), jnp.float32)
                     for s in ((B, S, H, hd), (B, S, KV, hd),
                               (B, S, KV, hd)))
        return (args,
                lambda q, k, v: flash_attention_pallas(
                    q, k, v, causal=causal, block_q=64, block_kv=64,
                    interpret=True),
                lambda q, k, v: flash_attention_ref(q, k, v, causal=causal,
                                                    block_kv=64))
    assert name == "ssd_chunk"
    b, c, l, h, p, n = 1, 2, 16, 2, 8, 8
    args = (jnp.asarray(rng.normal(size=(b, c, l, h, p)), jnp.float32) * 0.5,
            -jnp.abs(jnp.asarray(rng.normal(size=(b, c, l, h)),
                                 jnp.float32)) * 0.1,
            jnp.asarray(rng.normal(size=(b, c, l, n)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, c, l, n)), jnp.float32))
    return (args, lambda *a: ssd_chunk_pallas(*a, interpret=True),
            ssd_chunk_ref)


@pytest.mark.parametrize("name", ["rmsnorm", "flash_causal", "flash_cross",
                                  "ssd_chunk"])
def test_pallas_grad_matches_reference(name):
    """jax.grad through each Pallas kernel (its custom_vjp) equals jax.grad
    of its jnp oracle, for every differentiable input."""
    args, kernel, ref = _grad_case(name)
    argnums = tuple(range(len(args)))
    got = jax.grad(lambda *a: _cotangent_loss(kernel(*a), 0), argnums)(*args)
    want = jax.grad(lambda *a: _cotangent_loss(ref(*a), 0), argnums)(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


# name: (B, Sq, Skv, H, KV, hd, block_q, block_kv, causal, q_offset)
# Several q and kv blocks, so that the causal skip and the clamped index
# maps are crossed; uneven blocks; KV lengths that need padding.
FLASH_BWD_CASES = {
    "causal_mha": (1, 256, 256, 2, 2, 64, 64, 64, True, 0),
    "causal_gqa2": (2, 256, 256, 4, 2, 64, 64, 64, True, 0),
    "causal_gqa2_bq32": (1, 256, 256, 4, 2, 64, 32, 64, True, 0),
    "causal_gqa2_bk32": (1, 256, 256, 4, 2, 64, 64, 32, True, 0),
    "causal_gqa2_offset_padded": (1, 128, 176, 4, 2, 64, 64, 64, True, 48),
    "cross_mha": (1, 128, 192, 2, 2, 64, 64, 64, False, 0),
    "cross_gqa2_padded": (1, 128, 100, 4, 2, 64, 64, 64, False, 0),
}
# bfloat16 keeps 8 significant bits: one rounding moves a value by up to
# 2^-9 of itself.  The kernel rounds P and dS before their products and
# its gradients once more at the end, and the oracle rounds its float32
# gradients once, so a few 2^-8 of the largest gradient is rounding (the
# cases read up to 7.2e-3).  2e-2 of it, about five 2^-8, leaves room for
# sums over a few hundred keys; a wrong mask or a skipped block moves
# gradients by tens of percent of the largest.
FLASH_BWD_TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(FLASH_BWD_CASES))
def test_flash_pallas_backward(name, dtype):
    """dq, dk and dv from the Pallas backward kernels (interpret mode)
    equal jax.grad of the jnp oracle: float32 at 1e-4, bfloat16 at
    FLASH_BWD_TOL of the largest gradient."""
    B, Sq, Skv, H, KV, hd, bq, bk, causal, q_offset = FLASH_BWD_CASES[name]
    rng = np.random.default_rng(11)
    args = tuple(jnp.asarray(rng.normal(size=s), dtype)
                 for s in ((B, Sq, H, hd), (B, Skv, KV, hd),
                           (B, Skv, KV, hd)))
    kw = dict(causal=causal, q_offset=q_offset)
    got = jax.grad(lambda *a: _cotangent_loss(flash_attention_pallas(
        *a, block_q=bq, block_kv=bk, interpret=True, **kw), 0),
        (0, 1, 2))(*args)
    want = jax.grad(lambda *a: _cotangent_loss(flash_attention_ref(
        *a, block_kv=bk, **kw), 0), (0, 1, 2))(*args)
    tol = FLASH_BWD_TOL[dtype]
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, rtol=tol, atol=tol)
        else:
            np.testing.assert_allclose(g, w, rtol=0,
                                       atol=tol * np.abs(w).max())
