"""Plain Qwen3 dense language model and AdamW, in float32, for checking
the program's training steps.

Follows the Qwen3 description in Hugging Face transformers
(``Qwen3ForCausalLM``) and the configuration file as run
(``bench/configs/qwen3-1.7b.json``): the token embedding; pre-norm
residual blocks of RMSNorm, q/k/v projections without bias, a per-head
RMSNorm on q and on k before RoPE (rotate-half form), causal softmax
attention in which query head ``i`` reads key/value head
``i // (heads / kv_heads)``, the output projection, then RMSNorm and the
SwiGLU MLP ``down(silu(gate(h)) * up(h))``; a final RMSNorm, the head
(the embedding's transpose where the configuration ties them), mean token
cross-entropy.  AdamW as ``bench/reference/mamba2.py`` has it.  Every
matrix product runs at ``Precision.HIGHEST``.

It imports nothing of the program.  ``init_weights`` is the benchmark's
own weight maker: the program is handed its output, and the reference
makes the same weights again from the seed.

At the published widths a float32 copy of the model, its gradient and
AdamW's moments do not fit on one chip, nor do the float32 logits of a
batch.  So the weights, gradients and moments are placed over every chip
present (:func:`placements`, plain ``NamedSharding``s on a one-axis mesh),
the rows of a batch are split over the chips where they divide, each
layer runs under ``jax.checkpoint``, and the head and loss are computed
in blocks of positions, so no full logits tensor is ever held.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference.common import (  # noqa: F401
    einsum, leaf_names, leaf_norms, quantizer, rms, seed_key)
from bench.reference.mamba2 import _adamw, lr_at

AXIS = "chips"
HEAD_BLOCK = 256          # positions per block of the head and loss


def dims(cj: dict):
    return (cj["hidden_size"], cj["num_hidden_layers"],
            cj["num_attention_heads"], cj["num_key_value_heads"],
            cj["head_dim"], cj["intermediate_size"], cj["vocab_size"])


# ------------------------------------------------------------- placement

def mesh() -> Mesh:
    """Every chip present on one axis, in the order that ``jax.make_mesh``
    gives them: the same order as any other mesh it makes of them, as a
    computation that mixes two meshes of one device set requires."""
    return jax.make_mesh((len(jax.devices()),), (AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def placement(shape) -> NamedSharding:
    """Where one leaf lives: a matrix split over the chips along its
    input dimension (the vocabulary for the embedding), or along its
    last where that one does not divide; norm weights, small enough,
    on every chip."""
    m = mesh()
    n = m.size
    spec = [None] * len(shape)
    if len(shape) >= 2 and math.prod(shape) >= 1 << 20:
        for dim in (len(shape) - 2, len(shape) - 1):
            if shape[dim] % n == 0:
                spec[dim] = AXIS
                break
    return NamedSharding(m, P(*spec))


def placements(tree):
    return jax.tree.map(lambda a: placement(a.shape), tree)


def rows_placement(n_rows: int, ndim: int = 2) -> NamedSharding:
    """A batch's rows split over the chips where they divide."""
    m = mesh()
    rows = AXIS if n_rows % m.size == 0 else None
    return NamedSharding(m, P(rows, *[None] * (ndim - 1)))


def init_weights(cj: dict, key: jax.Array) -> Dict:
    """Weights in the layout the program's ``dense`` family takes, in the
    configuration's parameter dtype, placed by :func:`placements`."""
    d, L, H, KV, hd, f, V = dims(cj)
    std = cj["initializer_range"]
    dt = jnp.dtype(cj["param_dtype"])
    k = jax.random.split(key, 8)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    w = {
        "embed": normal(k[0], (V, d)),
        "final_norm": jnp.ones((d,), dt),
        "blocks": {
            "attn_norm": jnp.ones((L, d), dt),
            "wq": normal(k[1], (L, d, H * hd)),
            "wk": normal(k[2], (L, d, KV * hd)),
            "wv": normal(k[3], (L, d, KV * hd)),
            "wo": normal(k[4], (L, H * hd, d)),
            "q_norm": jnp.ones((L, hd), dt),
            "k_norm": jnp.ones((L, hd), dt),
            "ffn_norm": jnp.ones((L, d), dt),
            "w_gate": normal(k[5], (L, d, f)),
            "w_up": normal(k[6], (L, d, f)),
            "w_down": normal(k[7], (L, f, d)),
        },
    }
    if not cj["tie_word_embeddings"]:
        w["lm_head"] = normal(jax.random.fold_in(key, 8), (d, V))
    return jax.tree.map(lambda a: jax.lax.with_sharding_constraint(
        a, placement(a.shape)), w)


# ----------------------------------------------------------------- model

def rope(x, theta: float):
    """Rotary embedding in rotate-half form at positions 0..T-1;
    x (b, T, heads, hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None]
    half = hd // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def attention(q, k, v, qz):
    """Causal softmax attention; q (b, T, H, hd), k and v (b, T, KV, hd).
    Query head ``i`` reads key/value head ``i // (H // KV)``."""
    b, T, H, hd = q.shape
    KV = k.shape[2]
    qg = q.reshape(b, T, KV, H // KV, hd)
    s = einsum(qz, "btkgd,bskd->bkgts", qg, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum(qz, "bkgts,bskd->btkgd", p, v)
    return o.reshape(b, T, H * hd)


def _block(x, lp, cj, qz):
    d, L, H, KV, hd, f, V = dims(cj)
    eps = cj["rms_norm_eps"]
    b, T, _ = x.shape
    h = rms(x, lp["attn_norm"], eps)
    # q, k and v as one product (as one gate and up below): the same
    # sums, and fewer float32 products for the compiler to emit
    qkv = einsum(qz, "btd,de->bte", h, jnp.concatenate(
        [lp["wq"], lp["wk"], lp["wv"]], -1))
    q, k, v = jnp.split(qkv, [H * hd, (H + KV) * hd], -1)
    q = q.reshape(b, T, H, hd)
    k = k.reshape(b, T, KV, hd)
    v = v.reshape(b, T, KV, hd)
    q = rope(rms(q, lp["q_norm"], eps), cj["rope_theta"])
    k = rope(rms(k, lp["k_norm"], eps), cj["rope_theta"])
    # the residual stream is kept in the computation's precision
    x = qz(x + einsum(qz, "bte,ed->btd", attention(q, k, v, qz), lp["wo"]))
    h = rms(x, lp["ffn_norm"], eps)
    g, u = jnp.split(einsum(qz, "btd,df->btf", h, jnp.concatenate(
        [lp["w_gate"], lp["w_up"]], -1)), 2, -1)
    g = jax.nn.silu(g)
    return qz(x + einsum(qz, "btf,fd->btd", g * u, lp["w_down"]))


def _head_loss(x, labels, head, qz):
    """Summed token cross-entropy of hidden states x (b, T, d), over
    blocks of ``HEAD_BLOCK`` positions so that only one block's logits
    are held at a time."""
    b, T, d = x.shape
    blk = min(HEAD_BLOCK, T)
    xs = jnp.moveaxis(x.reshape(b, T // blk, blk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, T // blk, blk), 1, 0)

    def body(total, xl):
        xb, lb = xl
        logits = einsum(qz, "bpd,dv->bpv", xb, head)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return total + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            (xs, ls))
    return total


def _hidden(w, tokens, cj: dict, qz):
    """The final-normed hidden states of rows ``tokens`` (b, T) and the
    head, all in float32."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = w["embed"][tokens]
    x = jax.lax.with_sharding_constraint(x, rows_placement(x.shape[0], 3))

    def body(x, lp):
        return _block(x, lp, cj, qz), None

    x, _ = jax.lax.scan(jax.checkpoint(body), qz(x), w["blocks"])
    x = rms(x, w["final_norm"], cj["rms_norm_eps"])
    head = w["embed"].T if cj["tie_word_embeddings"] else w["lm_head"]
    return x, head


def loss(w, tokens, labels, cj: dict, quant: str = "none"):
    """Mean next-token cross-entropy of rows ``tokens`` (b, T)."""
    qz = quantizer(quant)
    x, head = _hidden(w, tokens, cj, qz)
    return _head_loss(x, labels, head, qz) / labels.size


def logits(w, tokens, cj: dict):
    """The full logits (b, T, V) of rows ``tokens``: for comparisons at
    small sizes only."""
    qz = quantizer("none")
    x, head = _hidden(w, tokens, cj, qz)
    return einsum(qz, "btd,dv->btv", x, head)


# ------------------------------------------------------------- training

class Reference:
    """Compiled once per (configuration, quantization, row shape)."""

    def __init__(self, cj: dict, quant: str = "none") -> None:
        shapes = jax.eval_shape(functools.partial(init_weights, cj),
                                jax.random.PRNGKey(0))
        self.where = placements(shapes)
        grad = jax.value_and_grad(
            lambda w, t, l, s: s * loss(w, t, l, cj, quant))
        # the gradient of the float32 weights, not rounded to their dtype
        self._grad = jax.jit(
            lambda w, t, l, s: grad(jax.tree.map(
                lambda a: a.astype(jnp.float32), w), t, l, s),
            out_shardings=(None, self.where))
        self._acc = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                            donate_argnums=0)
        self._copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t),
                             out_shardings=self.where)
        self._zeros = jax.jit(lambda t: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), t),
            out_shardings=self.where)
        self._diff = jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            a, b)))

    def train(self, w0, batches: Sequence[Dict[str, np.ndarray]],
              opt: dict, rows_per_pass: int = 4) -> dict:
        """AdamW steps from ``w0`` over ``batches`` (one per step), each
        batch's gradient summed from passes of ``rows_per_pass`` rows (a
        half batch of the cell is one pass and compiles nothing new).
        Returns the loss of every step, the per-leaf norm of the first
        gradient as the optimizer takes it (clipped), also per layer, and
        the per-leaf norm of the parameters' change over all steps."""
        opt_t = tuple(sorted(opt.items()))
        w0 = jax.device_put(w0, self.where)
        w = self._copy(w0)
        m, v = self._zeros(w0), self._zeros(w0)
        losses, grad_norms, layer_norms = [], None, None
        for step, batch in enumerate(batches, start=1):
            tok, lab = batch["tokens"], batch["labels"]
            n_rows = tok.shape[0]
            k = min(rows_per_pass, n_rows)
            g, total = None, 0.0
            for r in range(0, n_rows, k):
                rows = rows_placement(k)
                l, gr = self._grad(w, jax.device_put(tok[r:r + k], rows),
                                   jax.device_put(lab[r:r + k], rows),
                                   k / n_rows)
                g = gr if g is None else self._acc(g, gr)
                total += float(l)
            losses.append(total)
            w, m, v, gn, gl = _adamw(w, g, m, v, step, lr_at(opt, step),
                                     opt_t)
            del g
            if grad_norms is None:
                grad_norms, layer_norms = np.asarray(gn), np.asarray(gl)
        change = np.asarray(self._diff(w, w0))
        names = leaf_names(w0)
        return {"losses": losses,
                "grad_norms": dict(zip(names, grad_norms.tolist())),
                "layer_grad_norms": dict(zip(leaf_names(w0, per_layer=True),
                                             layer_norms.tolist())),
                "change_norms": dict(zip(names, change.tolist()))}
