"""Plain Mamba2 language model and AdamW, in float32, for checking the
program's training steps.

Follows the Mamba2 paper (Dao & Gu, arXiv:2405.21060) and the
configuration file as run (``bench/configs/mamba2-370m.json``): pre-norm
residual blocks of in-projection, causal depthwise conv over (x, B, C),
SiLU, the SSD recurrence (the paper's minimal chunked listing), the D skip, the gated RMSNorm ``norm(y * silu(z))`` and the
out-projection; a final RMSNorm, the head (the embedding's transpose
where the configuration ties them), mean token cross-entropy over the
padded vocabulary.  Every matrix product runs at ``Precision.HIGHEST``.

It imports nothing of the program.  ``init_weights`` is the benchmark's
own weight maker: the program is handed its output, and the reference
makes the same weights again from the seed.

``quant="fp8"`` rounds the operands of every product and the residual
stream, and their gradients, to float8 (e4m3, one scale per tensor): the
control, one precision step below the bfloat16 in which the
configuration keeps weights, products and the residual.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import (  # noqa: F401
    einsum, leaf_names, leaf_norms, quantizer, rms, seed_key)


def dims(cj: dict):
    s = cj["ssm_cfg"]
    d = cj["d_model"]
    d_in = s["expand"] * d
    return d, d_in, d_in // s["headdim"], s["d_state"], s["d_conv"]


def vocab_rows(cj: dict) -> int:
    """The vocabulary padded to ``pad_vocab_size_multiple``: the rows of
    the embedding and the logits of the head."""
    m = cj["pad_vocab_size_multiple"]
    return -(-cj["vocab_size"] // m) * m


def init_weights(cj: dict, key: jax.Array) -> Dict:
    """Weights in the layout the program's ``ssm`` family takes, in the
    configuration's parameter dtype."""
    d, d_in, nh, n, W = dims(cj)
    L, V = cj["n_layer"], vocab_rows(cj)
    std = cj["assumed"]["initializer_range"]
    dt = jnp.dtype(cj["param_dtype"])
    k = jax.random.split(key, 8)

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, jnp.float32) * scale

    dt0 = jnp.exp(jax.random.uniform(k[5], (L, nh), jnp.float32,
                                     math.log(1e-3), math.log(1e-1)))
    dt0 = jnp.maximum(dt0, 1e-4)
    w = {
        "embed": normal(k[0], (V, d), std),
        "final_norm": jnp.ones((d,)),
        "blocks": {
            "norm": jnp.ones((L, d)),
            "in_proj": normal(k[2], (L, d, 2 * d_in + 2 * n + nh), std),
            "conv_w": jax.random.uniform(k[3], (L, W, d_in + 2 * n),
                                         jnp.float32, -1, 1) / math.sqrt(W),
            "A_log": jnp.log(jax.random.uniform(k[4], (L, nh), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "D": jnp.ones((L, nh)),
            "out_norm": jnp.ones((L, d_in)),
            "out_proj": normal(k[6], (L, d_in, d), std / math.sqrt(L)),
        },
    }
    if not cj["tie_embeddings"]:
        w["lm_head"] = normal(k[1], (d, V), std)
    return jax.tree.map(lambda a: a.astype(dt), w)


# ----------------------------------------------------------------- model

def _segsum(a):
    """a (..., T) -> S (..., T, T) with S[i, j] = a[j+1] + ... + a[i] for
    i >= j and -inf above the diagonal, summed without cancellation."""
    T = a.shape[-1]
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1),
                  jnp.broadcast_to(a[..., :, None], a.shape + (T,)), 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def _ssd(x, a, B, C, chunk, q):
    """y_t = sum_{s<=t} C_t . B_s exp(a_{s+1} + .. + a_t) x_s, by the SSD
    paper's minimal chunked listing: the quadratic form inside each chunk
    of ``chunk`` steps, a state recurrence across chunks.
    x (b, T, h, p); a (b, T, h); B, C (b, T, n)."""
    b, T, h, p = x.shape
    c = T // chunk
    x = x.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, -1)
    C = C.reshape(b, c, chunk, -1)
    a = jnp.moveaxis(a.reshape(b, c, chunk, h), -1, 1)     # (b, h, c, l)
    a_cum = jnp.cumsum(a, -1)
    # inside each chunk
    L = jnp.exp(_segsum(a))                                # (b, h, c, l, l)
    cb = einsum(q, "bcln,bcsn->bcls", C, B)
    y = einsum(q, "bhcls,bcshp->bclhp", cb[:, None] * L, x)
    # each chunk's final state, then the states carried into each chunk
    decay = jnp.exp(a_cum[..., -1:] - a_cum)               # (b, h, c, l)
    states = einsum(q, "bcln,bhcl,bclhp->bchpn", B, decay, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    carry = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                     (1, 0)))))
    states = einsum(q, "bhzc,bchpn->bzhpn", carry, states)[:, :-1]
    y = y + einsum(q, "bcln,bchpn,bhcl->bclhp", C, states, jnp.exp(a_cum))
    return y.reshape(b, T, h, p)


def _block(x, lp, cj, q):
    d, d_in, nh, n, W = dims(cj)
    eps = cj["norm_epsilon"]
    b, T, _ = x.shape
    h = rms(x, lp["norm"], eps)
    proj = einsum(q, "btd,de->bte", h, lp["in_proj"])
    z = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * n]
    dt = proj[..., 2 * d_in + 2 * n:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = sum(pad[:, k:k + T] * lp["conv_w"][k] for k in range(W))
    xbc = jax.nn.silu(xbc)
    xs, B, C = xbc[..., :d_in], xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                 # (b, T, nh)
    A = -jnp.exp(lp["A_log"])
    xh = xs.reshape(b, T, nh, -1)
    y = _ssd(xh * dt[..., None], dt * A, B, C,
             cj["ssm_cfg"]["chunk_size"], q)
    y = y + lp["D"][:, None] * xh
    y = rms(y.reshape(b, T, d_in) * jax.nn.silu(z), lp["out_norm"], eps)
    return x + einsum(q, "bte,ed->btd", y, lp["out_proj"])


def loss(w, tokens, labels, cj: dict, quant: str = "none"):
    """Mean next-token cross-entropy of rows ``tokens`` (b, T)."""
    q = quantizer(quant)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"][tokens]

    def body(x, lp):
        # the residual stream is kept in the computation's precision
        return q(_block(x, lp, cj, q)), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, w["blocks"])
    x = rms(x, w["final_norm"], cj["norm_epsilon"])
    head = w["embed"].T if cj["tie_embeddings"] else w["lm_head"]
    logits = einsum(q, "btd,dv->btv", x, head)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


# ------------------------------------------------------------- optimizer

def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr``, then cosine down to ``min_lr_ratio``."""
    warm = step / opt["warmup_steps"] if opt["warmup_steps"] else 1.0
    prog = min(max((step - opt["warmup_steps"])
                   / max(1.0, opt["total_steps"] - opt["warmup_steps"]),
                   0.0), 1.0)
    decayed = (opt["min_lr_ratio"]
               + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi
                                                                * prog)))
    return opt["lr"] * min(warm, decayed)


@functools.partial(jax.jit, static_argnames=("opt_t",),
                   donate_argnums=(0, 2, 3))
def _adamw(w, g, m, v, step, lr, opt_t):
    opt = dict(opt_t)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    b1c = 1 - opt["b1"] ** step
    b2c = 1 - opt["b2"] ** step

    def upd(p, g, m, v):
        g = g * scale
        m = opt["b1"] * m + (1 - opt["b1"]) * g
        v = opt["b2"] * v + (1 - opt["b2"]) * g * g
        delta = (m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
        delta = delta + opt["weight_decay"] * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat, tdef = jax.tree.flatten(w)
    out = [upd(*a) for a in zip(flat, tdef.flatten_up_to(g),
                                tdef.flatten_up_to(m), tdef.flatten_up_to(v))]
    w, m, v = (tdef.unflatten([o[i] for o in out]) for i in range(3))
    return (w, m, v, leaf_norms(m) / (1 - opt["b1"]),
            leaf_norms(m, per_layer=True) / (1 - opt["b1"]))


class Reference:
    """Compiled once per (configuration, quantization, row shape)."""

    def __init__(self, cj: dict, quant: str = "none") -> None:
        self.cj = cj
        self.quant = quant
        grad = jax.value_and_grad(functools.partial(loss, cj=cj,
                                                    quant=quant))
        # the gradient of the float32 weights, not rounded to their dtype
        self._grad = jax.jit(lambda w, t, l: grad(
            jax.tree.map(lambda a: a.astype(jnp.float32), w), t, l))
        self._acc = jax.jit(lambda a, g, s: jax.tree.map(
            lambda x, y: x + y.astype(jnp.float32) * s, a, g),
            donate_argnums=0)
        self._diff = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))

    def train(self, w0, batches: Sequence[Dict[str, np.ndarray]],
              opt: dict, rows_per_pass: int = 2) -> dict:
        """AdamW steps from ``w0`` over ``batches`` (one per step), each
        batch's gradient summed from passes of ``rows_per_pass`` rows.
        Returns the loss of every step, the per-leaf norm of the first
        gradient as the optimizer takes it (clipped), also per layer, and
        the per-leaf norm of the parameters' change over all steps."""
        opt_t = tuple(sorted(opt.items()))
        zeros = lambda: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), w0)
        w = jax.tree.map(jnp.copy, w0)
        m, v = zeros(), zeros()
        losses, grad_norms, layer_norms = [], None, None
        for step, batch in enumerate(batches, start=1):
            tok, lab = batch["tokens"], batch["labels"]
            n_rows = tok.shape[0]
            g, total = zeros(), 0.0
            for r in range(0, n_rows, rows_per_pass):
                sl = slice(r, r + rows_per_pass)
                l, gr = self._grad(w, jnp.asarray(tok[sl]),
                                   jnp.asarray(lab[sl]))
                g = self._acc(g, gr, rows_per_pass / n_rows)
                total += float(l) * rows_per_pass / n_rows
            losses.append(total)
            w, m, v, gn, gl = _adamw(w, g, m, v, step, lr_at(opt, step),
                                     opt_t)
            if grad_norms is None:
                grad_norms, layer_norms = np.asarray(gn), np.asarray(gl)
        change = np.asarray(self._diff(w, w0))
        names = leaf_names(w0)
        return {"losses": losses,
                "grad_norms": dict(zip(names, grad_norms.tolist())),
                "layer_grad_norms": dict(zip(leaf_names(w0, per_layer=True),
                                             layer_norms.tolist())),
                "change_norms": dict(zip(names, change.tolist()))}
