"""The plain reference: a Llama-family decoder (Qwen3 when ``qk_norm``) in
float32 at the highest matmul precision, written from the published
architecture and importing nothing of the program.

It regenerates the weights from the seed layer by layer
(:mod:`bench.weights`), runs every sampled sequence through one layer at a
time, and reads logits only at the rows that are compared, so it fits on
one chip next to nothing else.  ``quant="fp8"`` is the control: every
matmul takes float8 (e4m3) operands, scaled per row of the activations and
per output column of the weights, the step a later change might take below
the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

Q_BLOCK = 512          # query rows per attention block
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, quant):
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


def layer(w, x, cfg, quant=None):
    """One decoder layer over one sequence x (t, d)."""
    t = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   W.head_dim(cfg))
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(t)
    h = rmsnorm(x, w["ln1"], eps)
    q = _mm(h, w["wq"], quant).reshape(t, nh, hd)
    k = _mm(h, w["wk"], quant).reshape(t, nkv, hd)
    v = _mm(h, w["wv"], quant).reshape(t, nkv, hd)
    if cfg.get("qk_norm"):
        q = rmsnorm(q, w["q_norm"], eps)
        k = rmsnorm(k, w["k_norm"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    x = x + _mm(attention(q, k, v).reshape(t, nh * hd), w["wo"], quant)
    h = rmsnorm(x, w["ln2"], eps)
    g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(g, w["w_down"], quant)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, quant):
    cfg = dict(cfg_items)
    dt = jnp.dtype(cfg["torch_dtype"])        # the weights as served
    top = jax.jit(lambda key: _f32(W.top_weights(key, cfg, dt)))
    lw = jax.jit(lambda key, l: _f32(W.layer_weights(key, cfg, l, dt)))
    run = jax.jit(lambda w, xs: jax.lax.map(
        lambda x: layer(w, x, cfg, quant), xs))
    embed = jax.jit(lambda e, toks: e[toks])

    def final(ln_f, xs, rows):
        h = jnp.take_along_axis(xs, rows[..., None], axis=1)
        return rmsnorm(h, ln_f, cfg["rms_norm_eps"])

    return top, lw, run, embed, jax.jit(final)


def _key(cfg) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def hidden(seed: int, cfg: dict, seqs, rows, quant: Optional[str] = None):
    """Final normalised hidden states (k, r, d) at ``rows`` of each
    sequence: the input to the head.  seqs (k, t) int32 token ids, t a
    multiple of ``Q_BLOCK`` or less than it; rows (k, r) positions whose
    next-token logits are compared (pad with 0, masked by the caller)."""
    top_fn, lw_fn, run, embed, final = _programs(_key(cfg), quant)
    key = W.seed_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    with jax.default_matmul_precision("highest"):
        top = top_fn(key)
        xs = embed(top["embed"], seqs)
        for l in range(cfg["num_hidden_layers"]):
            xs = run(lw_fn(key, jnp.int32(l)), xs)
        out = final(top["ln_f"], xs, jnp.asarray(rows, jnp.int32))
    return out, top["head"]


@functools.partial(jax.jit, static_argnames=("quant",))
def _logit_stats(h, head, toks, quant=None):
    with jax.default_matmul_precision("highest"):
        logits = _mm(h, head, quant)
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
