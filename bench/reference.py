"""What every architecture's plain reference shares: float32 building
blocks at the highest matmul precision, importing nothing of the program,
and the gaps of served tokens below the reference's best logit.  Each
architecture's forward pass is its module's ``hidden`` (``bench/archs/``).

``quant="fp8"`` is the control: every matmul takes float8 (e4m3) operands,
scaled per row of the activations and per output column of the weights,
the step a later change might take below the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512          # query rows per attention block
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(x, w, quant=None):
    if quant == "fp8":
        x, w = _quant(x, -1), _quant(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x (t, heads, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v):
    """Causal GQA attention of q (t, nh, hd) over k/v (t, nkv, hd), in
    blocks of query rows."""
    t, nh, hd = q.shape
    qb_n = min(Q_BLOCK, t)
    group = nh // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qb_n, qb_n)
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        qpos = i * qb_n + jnp.arange(qb_n)
        s = jnp.where(kpos[None, None] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(block, jnp.arange(t // qb_n))
    return out.reshape(t, nh, hd)


@functools.partial(jax.jit, static_argnames=("quant",))
def _logit_stats(h, head, toks, quant=None):
    with jax.default_matmul_precision("highest"):
        logits = mm(h, head, quant)
    best = jnp.max(logits, -1)
    picked = jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]
    return best, picked, jnp.argmax(logits, -1).astype(jnp.int32)


def gaps(h, head, toks, quant: Optional[str] = None):
    """Per row: (best logit - logit of ``toks``, argmax), computed one
    sequence at a time so the (r, vocab) logits of one sequence are the
    largest block alive."""
    best, picked, top = [], [], []
    for i in range(h.shape[0]):
        b, p, a = _logit_stats(h[i], head, jnp.asarray(toks[i], jnp.int32),
                               quant=quant)
        best.append(np.asarray(b))
        picked.append(np.asarray(p))
        top.append(np.asarray(a))
    return np.stack(best) - np.stack(picked), np.stack(top)
