"""Operations and bytes that the algorithm needs, from a configuration's
widths alone: the same work whatever implements it.

Matmul FLOPs count 2 per multiply-add.  Decode counts only slots that are
owed the token (a slot past its request's length decodes padding that is
thrown away), and attention over each slot's valid context, not over the
cache's capacity.  Bytes count each weight read once per step, the valid
KV of each slot read, the new KV written and the embedding rows read.
"""
from __future__ import annotations

from bench.weights import head_dim

BF16 = 2


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


def weight_bytes(cfg) -> int:
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


def decode_step_flops(cfg, contexts) -> float:
    """One decode step; ``contexts`` holds, per slot owed a token, the
    positions it attends (its cached context plus the new token)."""
    _, nh, _, hd, _, _, n = _w(cfg)
    per_token = 2 * (n * layer_matmul_params(cfg) + head_params(cfg))
    return float(sum(per_token + n * 4 * nh * hd * c for c in contexts))


def decode_step_bytes(cfg, contexts) -> float:
    if not contexts:
        return 0.0
    kv = kv_bytes_per_position(cfg)
    d = cfg["hidden_size"]
    return float(weight_bytes(cfg)
                 + sum(kv * (c - 1) + kv + BF16 * d for c in contexts))


def prefill_flops(cfg, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` real tokens (padding excluded), causal
    attention, and the head at the last position only."""
    _, nh, _, hd, _, _, n = _w(cfg)
    p = prompt_len
    return float(2 * n * layer_matmul_params(cfg) * p + 2 * head_params(cfg)
                 + n * 2 * nh * hd * p * (p + 1))
