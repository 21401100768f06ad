"""The dense Llama-family decoder (Qwen3 when ``qk_norm``): pre-norm
layers of GQA attention with RoPE and a SwiGLU MLP, a final RMSNorm and an
untied head.

The reference is written from the published architecture in float32 at
the highest matmul precision.  It regenerates the weights from the seed
layer by layer (:mod:`bench.weights`), runs every sampled sequence through
one layer at a time, and reads logits only at the rows that are compared,
so it fits on one chip next to nothing else.

Work counts: matmul FLOPs count 2 per multiply-add.  Decode counts only
slots that are owed the token (a slot past its request's length decodes
padding that is thrown away), and attention over each slot's valid
context, not over the cache's capacity.  Bytes count each weight read once
per step, the valid KV of each slot read, the new KV written and the
embedding rows read.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.reference import attention, mm, rmsnorm, rope

__all__ = ["model_config", "to_program", "LAYER_LEAVES", "TOP_LEAVES",
           "NORMS", "layer_shapes", "top_shapes", "hidden",
           "decode_step_flops", "decode_step_bytes", "prefill_flops",
           "weight_bytes", "kv_bytes_per_position", "layer_matmul_params",
           "head_params", "head_dim", "SMOKE", "CONTROL"]

# the CPU tests' widths: whole runs, and the fp8 control (big enough for it
# to show, 0.61-1.03 on the tests' seeds, small enough for the CPU)
SMOKE = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=256)
CONTROL = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               vocab_size=4096)

BF16 = 2


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def model_config(cfg: dict):
    from repro.models.common import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=head_dim(cfg), qk_norm=bool(cfg.get("qk_norm")),
        qkv_bias=bool(cfg.get("attention_bias")),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        max_seq=cfg["engine"]["max_seq"])


def to_program(w: dict, cfg: dict) -> dict:
    """The benchmark's weights as the program's parameter tree."""
    from repro.models.attention import AttnParams
    from repro.models.ffn import MlpParams
    from repro.models.transformer import BlockParams
    L = w["layers"]
    qk = bool(cfg.get("qk_norm"))
    attn = AttnParams(L["wq"], L["wk"], L["wv"], L["wo"], None, None, None,
                      L["q_norm"] if qk else None, L["k_norm"] if qk else None)
    return {"embed": w["embed"], "ln_f": w["ln_f"], "head": w["head"],
            "blocks": BlockParams(L["ln1"], attn, L["ln2"],
                                  MlpParams(L["w_gate"], L["w_up"],
                                            L["w_down"]))}


# ---------------------------------------------------------------------------
# the weights
# ---------------------------------------------------------------------------

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "ln_f", "head")
NORMS = ("ln1", "ln2", "q_norm", "k_norm", "ln_f")


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


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def layer(w, x, cfg, quant=None):
    """One decoder layer over one sequence x (t, d)."""
    t = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(t)
    h = rmsnorm(x, w["ln1"], eps)
    q = mm(h, w["wq"], quant).reshape(t, nh, hd)
    k = mm(h, w["wk"], quant).reshape(t, nkv, hd)
    v = mm(h, w["wv"], quant).reshape(t, nkv, hd)
    if cfg.get("qk_norm"):
        q = rmsnorm(q, w["q_norm"], eps)
        k = rmsnorm(k, w["k_norm"], eps)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    x = x + mm(attention(q, k, v).reshape(t, nh * hd), w["wo"], quant)
    h = rmsnorm(x, w["ln2"], eps)
    g = jax.nn.silu(mm(h, w["w_gate"], quant)) * mm(h, w["w_up"], quant)
    return x + mm(g, w["w_down"], quant)


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
    multiple of ``reference.Q_BLOCK`` or less than it; rows (k, r)
    positions whose next-token logits are compared (pad with 0, masked by
    the caller)."""
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


# ---------------------------------------------------------------------------
# the work
# ---------------------------------------------------------------------------

def _w(cfg):
    d, nh, nkv, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["intermediate_size"])
    return d, nh, nkv, head_dim(cfg), ff, cfg["vocab_size"], cfg[
        "num_hidden_layers"]


def layer_matmul_params(cfg) -> int:
    d, nh, nkv, hd, ff, _, _ = _w(cfg)
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * ff


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg, boundary=None, spans=()) -> int:
    """Bytes of every weight a decode step reads (the embedding table is
    gathered, not read whole, and is not among them)."""
    d, _, _, hd, _, _, n = _w(cfg)
    norms = 2 * d + (2 * hd if cfg.get("qk_norm") else 0)
    return BF16 * (n * (layer_matmul_params(cfg) + norms) + head_params(cfg)
                   + d)


def kv_bytes_per_position(cfg) -> int:
    """K and V of one position across every layer."""
    _, _, nkv, hd, _, _, n = _w(cfg)
    return n * 2 * nkv * hd * BF16


def decode_step_flops(cfg, contexts, boundary=None, spans=()) -> float:
    """One decode step; ``contexts`` holds, per slot owed a token, the
    positions it attends (its cached context plus the new token)."""
    _, nh, _, hd, _, _, n = _w(cfg)
    per_token = 2 * (n * layer_matmul_params(cfg) + head_params(cfg))
    return float(sum(per_token + n * 4 * nh * hd * c for c in contexts))


def decode_step_bytes(cfg, contexts, boundary=None, spans=()) -> float:
    if not contexts:
        return 0.0
    kv = kv_bytes_per_position(cfg)
    d = cfg["hidden_size"]
    return float(weight_bytes(cfg)
                 + sum(kv * (c - 1) + kv + BF16 * d for c in contexts))


def prefill_flops(cfg, prompt_len: int, boundary=None, spans=()) -> float:
    """A prompt of ``prompt_len`` real tokens (padding excluded), causal
    attention, and the head at the last position only."""
    _, nh, _, hd, _, _, n = _w(cfg)
    p = prompt_len
    return float(2 * n * layer_matmul_params(cfg) * p + 2 * head_params(cfg)
                 + n * 2 * nh * hd * p * (p + 1))
