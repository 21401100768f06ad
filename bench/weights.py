"""Weights made from the seed, for the program and for the reference alike.

Every tensor is drawn from its own key, ``fold_in(fold_in(base, leaf),
layer)``, as uniform bits scaled to the tensor's standard deviation and
rounded once to the served dtype (bfloat16).  Uniform bits go through the
same few exactly-rounded float operations in any program, so the stacked
call that makes the served weights on the device and the per-layer call
that the reference makes later give the same bits.

Names here are the benchmark's own; ``bench/system.py`` maps them onto the
program's parameter tree, and the reference reads them directly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# (name, shape from the widths, std) of one layer's tensors; the order is
# part of each tensor's key and never changes.
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "ln_f", "head")
NORMS = ("ln1", "ln2", "q_norm", "k_norm", "ln_f")
NORM_STD = 0.1        # norm scales are 1 + N(0, 0.1^2)-like, never all ones
EMBED_STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any non-negative seed of up to 64 bits (PRNGKey alone
    keeps only the low 32)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def layer_shapes(cfg) -> dict:
    d, nh, nkv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], head_dim(cfg),
                          cfg["intermediate_size"])
    shapes = {"ln1": (d,), "wq": (d, nh * hd), "wk": (d, nkv * hd),
              "wv": (d, nkv * hd), "wo": (nh * hd, d), "ln2": (d,),
              "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    if cfg.get("qk_norm"):
        shapes["q_norm"] = (hd,)
        shapes["k_norm"] = (hd,)
    return shapes


def top_shapes(cfg) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, d), "ln_f": (d,), "head": (d, v)}


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def _draw(key, name: str, shape, dtype):
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    if name in NORMS:
        w = 1.0 + u * (NORM_STD * math.sqrt(3.0))
    else:
        std = EMBED_STD if name == "embed" else 1.0 / math.sqrt(shape[0])
        w = u * (std * math.sqrt(3.0))
    return w.astype(dtype)


def _leaf_key(key, names, name):
    return jax.random.fold_in(key, names.index(name))


def layer_weights(key, cfg, layer, dtype=jnp.bfloat16) -> dict:
    """One layer's tensors; ``layer`` may be traced."""
    lk = jax.random.fold_in(key, 1)
    return {n: _draw(jax.random.fold_in(_leaf_key(lk, LAYER_LEAVES, n),
                                        layer), n, s, dtype)
            for n, s in layer_shapes(cfg).items()}


def top_weights(key, cfg, dtype=jnp.bfloat16) -> dict:
    tk = jax.random.fold_in(key, 0)
    return {n: _draw(_leaf_key(tk, TOP_LEAVES, n), n, s, dtype)
            for n, s in top_shapes(cfg).items()}


def all_weights(key, cfg, dtype=jnp.bfloat16) -> dict:
    """Every tensor, the layer tensors stacked on a leading layer axis.
    Layers are made one at a time (``lax.map``) so no more than one layer's
    float32 draws is alive at once."""
    n = cfg["num_hidden_layers"]
    stacked = jax.lax.map(lambda l: layer_weights(key, cfg, l, dtype),
                          jnp.arange(n))
    return {**top_weights(key, cfg, dtype), "layers": stacked}
