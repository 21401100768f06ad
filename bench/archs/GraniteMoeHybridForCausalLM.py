"""Granite 4.0-H (``GraniteMoeHybridForCausalLM``) without experts: a
period of Mamba-2 layers and attention layers (``layer_types``), each mixer
followed by a SwiGLU MLP of its own, with muP multipliers: embeddings times
``embedding_multiplier``, each residual branch times
``residual_multiplier``, attention scores times ``attention_multiplier``
(not 1/sqrt(head_dim)), logits divided by ``logits_scaling``.  Attention
is GQA with no position embedding (``position_embedding_type`` ``nope``).

A Mamba-2 layer (one group of B and C) is, per position t,

    z | x B C | dt_raw = in_proj(norm(h_t))
    x B C <- silu(conv(x B C) + bias)
    dt = softplus(dt_raw + dt_bias);   A = -exp(A_log)
    s_t = exp(dt A) s_{t-1} + dt x_t B_t^T;   y_t = s_t C_t + D x_t
    out = out_proj(rmsnorm(y * silu(z)) * w)

The reference is written from that description in float32 at the highest
matmul precision and runs the recurrence one position at a time (a
``lax.scan``), independent of the program's chunked form.  Like the dense
decoder's it regenerates the weights layer by layer and runs every sampled
sequence through one layer at a time.

Every layer draws the union of the leaves (``weights.all_weights`` stacks
one ``layer_shapes``); a layer uses only its own kind's.  The drawn bits of
``a_log`` and ``dt_bias`` are mapped onto Mamba-2's initial ranges by
:func:`ssm_params`, which the program's weights and the reference share:
decays of A in [1, 16] and steps softplus(dt_bias) in [0.001, 0.1], so the
recurrence remembers over hundreds of positions.

Work counts: matmul FLOPs count 2 per multiply-add.  A decode step counts
each weight read once, the valid KV of each slot read, the new KV written,
the embedding rows, and the recurrent state each live slot's lane reads
and writes, as the boundary's ``serve.ssm_state`` instant states it (none
without it).  The SSD counts its matmuls over the real positions, causal
within each chunk.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.reference import attention, mm, rmsnorm

__all__ = ["model_config", "to_program", "LAYER_LEAVES", "TOP_LEAVES",
           "NORMS", "layer_shapes", "top_shapes", "hidden", "ssm_params",
           "decode_step_flops", "decode_step_bytes", "prefill_flops",
           "weight_bytes", "kv_bytes_per_position", "state_bytes_per_slot",
           "head_dim", "SMOKE", "CONTROL"]

# the CPU tests' widths: whole runs (two periods of three layers, SSD
# chunks of 32), and the fp8 control, at the published depth and period:
# the error of a lower precision grows with depth (its gaps at 4, 8, 16
# and 40 layers read about 0.04, 0.08, 0.13 and 0.24 on the CPU, the last
# beside the chip's 0.235 at full width)
SMOKE = dict(hidden_size=64, shared_intermediate_size=128,
             num_hidden_layers=6,
             layer_types=["mamba", "attention", "mamba"] * 2,
             num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
             mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=32,
             vocab_size=256)
CONTROL = dict(hidden_size=256, shared_intermediate_size=512,
               num_hidden_layers=40,
               layer_types=(["mamba"] * 5 + ["attention"]
                            + ["mamba"] * 4) * 4,
               num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=64, mamba_d_state=32,
               mamba_chunk_size=64, vocab_size=4096)

BF16, F32 = 2, 4
A_RANGE = (1.0, 16.0)        # Mamba-2's initial decay rates
DT_RANGE = (0.001, 0.1)      # and steps, log-uniform


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def _types(cfg):
    return list(cfg["layer_types"])


def _ssm(cfg):
    """(inner width, heads, head width, state size, conv width)."""
    nh, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if cfg["mamba_n_groups"] != 1:
        raise NotImplementedError("one group of B and C is implemented")
    return nh * hp, nh, hp, cfg["mamba_d_state"], cfg["mamba_d_conv"]


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def model_config(cfg: dict):
    from repro.models.common import ModelConfig
    din, nh, hp, n, k = _ssm(cfg)
    return ModelConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=head_dim(cfg), qkv_bias=bool(cfg.get("attention_bias")),
        ssm_state=n, layer_types=tuple(_types(cfg)), mamba_n_heads=nh,
        mamba_d_head=hp, mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=k,
        mamba_conv_bias=bool(cfg["mamba_conv_bias"]),
        mamba_chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        rope=cfg["position_embedding_type"] != "nope",
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        max_seq=cfg["engine"]["max_seq"])


def ssm_params(a_log_bits, dt_bias_bits):
    """(A_log, dt_bias) in float32 from their drawn leaves, each uniform in
    [-c, c] with c = sqrt(3 / heads): A uniform in ``A_RANGE`` and
    softplus(dt_bias) log-uniform in ``DT_RANGE``, as Mamba-2 initialises
    them."""
    def unit(w):
        c = math.sqrt(3.0 / w.shape[-1])
        return jnp.clip((w.astype(jnp.float32) / c + 1.0) / 2.0, 0.0, 1.0)
    lo, hi = A_RANGE
    a_log = jnp.log(lo + (hi - lo) * unit(a_log_bits))
    dlo, dhi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
    dt = jnp.exp(dlo + (dhi - dlo) * unit(dt_bias_bits))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)


def _period(cfg):
    """The shortest prefix of ``layer_types`` that repeats to the whole
    stack: the program scans the layers one period at a time."""
    t = _types(cfg)
    return next(t[:p] for p in range(1, len(t) + 1)
                if len(t) % p == 0 and t == t[:p] * (len(t) // p))


def to_program(w: dict, cfg: dict) -> dict:
    """The benchmark's weights as the program's parameter tree: G periods
    of the layer pattern, each layer keeping only its own kind's leaves."""
    from repro.models.attention import AttnParams
    from repro.models.ffn import MlpParams
    from repro.models.mamba2 import Mamba2Params
    from repro.models.transformer import HybridParams
    L = w["layers"]
    period = _period(cfg)
    p = len(period)
    g = cfg["num_hidden_layers"] // p
    mam = [i for i, t in enumerate(period) if t == "mamba"]
    att = [i for i, t in enumerate(period) if t == "attention"]

    def rows(name, idx=None):          # (L, ...) -> (G, |idx|, ...)
        a = L[name].reshape((g, p) + L[name].shape[1:])
        return a if idx is None else a[:, jnp.asarray(idx)]

    a_log, dt_bias = ssm_params(rows("a_log", mam), rows("dt_bias", mam))
    w_in = rows("w_in", mam)
    zxbc = w_in.shape[-1] - cfg["mamba_n_heads"]       # (z | x B C), dt
    mamba = Mamba2Params(
        w_in=w_in[..., :zxbc], w_dt=w_in[..., zxbc:],
        conv_w=rows("conv_w", mam),
        a_log=a_log, dt_bias=dt_bias,
        d_skip=rows("d_skip", mam).astype(jnp.float32),
        norm_w=rows("mamba_norm", mam), w_out=rows("w_out", mam),
        conv_b=rows("conv_b", mam) if cfg["mamba_conv_bias"] else None)
    attn = AttnParams(rows("wq", att), rows("wk", att), rows("wv", att),
                      rows("wo", att), None, None, None, None, None)
    return {"embed": w["embed"], "ln_f": w["ln_f"], "head": w["head"],
            "blocks": HybridParams(
                mamba=mamba, mamba_ln=rows("ln_mix", mam), attn=attn,
                attn_ln=rows("ln_mix", att),
                mlp=MlpParams(rows("w_gate"), rows("w_up"), rows("w_down")),
                mlp_ln=rows("ln_mlp"))}


# ---------------------------------------------------------------------------
# the weights
# ---------------------------------------------------------------------------

LAYER_LEAVES = ("ln_mix", "w_in", "conv_w", "conv_b", "a_log", "dt_bias",
                "d_skip", "mamba_norm", "w_out", "wq", "wk", "wv", "wo",
                "ln_mlp", "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "ln_f", "head")
# scales near 1: the norms and the skip D
NORMS = ("ln_mix", "mamba_norm", "ln_mlp", "ln_f", "d_skip")


def layer_shapes(cfg) -> dict:
    d, nh, nkv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], head_dim(cfg),
                          cfg["shared_intermediate_size"])
    din, h, _, n, k = _ssm(cfg)
    conv = din + 2 * n
    shapes = {"ln_mix": (d,), "w_in": (d, 2 * din + 2 * n + h),
              "conv_w": (k, conv), "a_log": (h,), "dt_bias": (h,),
              "d_skip": (h,), "mamba_norm": (din,), "w_out": (din, d),
              "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
              "wo": (nh * hd, d), "ln_mlp": (d,), "w_gate": (d, ff),
              "w_up": (d, ff), "w_down": (ff, d)}
    if cfg["mamba_conv_bias"]:
        shapes["conv_b"] = (conv,)
    return shapes


def top_shapes(cfg) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, d), "ln_f": (d,), "head": (d, v)}


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def _mlp(w, x, cfg, quant):
    h = rmsnorm(x, w["ln_mlp"], cfg["rms_norm_eps"])
    g = jax.nn.silu(mm(h, w["w_gate"], quant)) * mm(h, w["w_up"], quant)
    return mm(g, w["w_down"], quant)


def _mamba(w, x, cfg, quant):
    """A Mamba-2 mixer over one sequence x (t, d), one position at a
    time."""
    t = x.shape[0]
    din, nh, hp, n, k = _ssm(cfg)
    h = rmsnorm(x, w["ln_mix"], cfg["rms_norm_eps"])
    proj = mm(h, w["w_in"], quant)
    z, xbc, dt_raw = (proj[:, :din], proj[:, din:2 * din + 2 * n],
                      proj[:, 2 * din + 2 * n:])
    ext = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = sum(w["conv_w"][i] * ext[i:i + t] for i in range(k))
    if cfg["mamba_conv_bias"]:
        conv = conv + w["conv_b"]
    conv = jax.nn.silu(conv)
    xs, b, c = conv[:, :din], conv[:, din:din + n], conv[:, din + n:]
    a_log, dt_bias = ssm_params(w["a_log"], w["dt_bias"])
    dt = jax.nn.softplus(dt_raw + dt_bias)               # (t, nh)
    a = -jnp.exp(a_log)
    xh = xs.reshape(t, nh, hp)

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (s * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * x_t)[..., None] * b_t[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, c_t,
                             precision=jax.lax.Precision.HIGHEST)
    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, n)), (xh, b, c, dt))
    y = (y + w["d_skip"][None, :, None] * xh).reshape(t, din)
    g = rmsnorm(y * jax.nn.silu(z), w["mamba_norm"], cfg["rms_norm_eps"])
    return mm(g, w["w_out"], quant)


def _attention(w, x, cfg, quant):
    """Causal GQA with no position embedding, scores times
    ``attention_multiplier``."""
    t = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   head_dim(cfg))
    h = rmsnorm(x, w["ln_mix"], cfg["rms_norm_eps"])
    q = mm(h, w["wq"], quant).reshape(t, nh, hd)
    k = mm(h, w["wk"], quant).reshape(t, nkv, hd)
    v = mm(h, w["wv"], quant).reshape(t, nkv, hd)
    # reference.attention divides the scores by sqrt(hd)
    q = q * (cfg["attention_multiplier"] * math.sqrt(hd))
    return mm(attention(q, k, v).reshape(t, nh * hd), w["wo"], quant)


def layer(w, x, cfg, kind: str, quant=None):
    """One layer of ``kind`` over one sequence x (t, d)."""
    r = cfg["residual_multiplier"]
    mixer = _mamba if kind == "mamba" else _attention
    x = x + r * mixer(w, x, cfg, quant)
    return x + r * _mlp(w, x, cfg, quant)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, quant):
    cfg = dict(cfg_items)
    cfg["layer_types"] = list(cfg["layer_types"])
    dt = jnp.dtype(cfg["torch_dtype"])        # the weights as served
    top = jax.jit(lambda key: _f32(W.top_weights(key, cfg, dt)))
    lw = jax.jit(lambda key, l: _f32(W.layer_weights(key, cfg, l, dt)))
    run = {kind: jax.jit(lambda w, xs, kind=kind: jax.lax.map(
        lambda x: layer(w, x, cfg, kind, quant), xs))
        for kind in set(cfg["layer_types"])}
    embed = jax.jit(lambda e, toks: e[toks] * cfg["embedding_multiplier"])

    def final(ln_f, xs, rows):
        h = jnp.take_along_axis(xs, rows[..., None], axis=1)
        return rmsnorm(h, ln_f, cfg["rms_norm_eps"])

    return top, lw, run, embed, jax.jit(final)


def _key(cfg) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))
                        or k == "layer_types"))


def hidden(seed: int, cfg: dict, seqs, rows, quant: Optional[str] = None):
    """Final normalised hidden states (k, r, d) at ``rows`` of each
    sequence, and the head divided by ``logits_scaling``: their product is
    the logits.  seqs (k, t) int32 token ids, t a multiple of
    ``reference.Q_BLOCK`` or less than it; rows (k, r) positions whose
    next-token logits are compared (pad with 0, masked by the caller)."""
    top_fn, lw_fn, run, embed, final = _programs(_key(cfg), quant)
    key = W.seed_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    with jax.default_matmul_precision("highest"):
        top = top_fn(key)
        xs = embed(top["embed"], seqs)
        for l, kind in enumerate(_types(cfg)):
            xs = run[kind](lw_fn(key, jnp.int32(l)), xs)
        out = final(top["ln_f"], xs, jnp.asarray(rows, jnp.int32))
    return out, top["head"] / cfg["logits_scaling"]


# ---------------------------------------------------------------------------
# the work
# ---------------------------------------------------------------------------

def _mamba_matmul_params(cfg) -> int:
    d = cfg["hidden_size"]
    din, h, _, n, _ = _ssm(cfg)
    return d * (2 * din + 2 * n + h) + din * d


def _attn_matmul_params(cfg) -> int:
    d, nh, nkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], head_dim(cfg))
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d


def _counts(cfg):
    """(Mamba layers, attention layers)."""
    t = _types(cfg)
    return t.count("mamba"), t.count("attention")


def _matmul_params(cfg) -> int:
    """Matmul parameters a token passes through below the head."""
    nm, na = _counts(cfg)
    d, ff = cfg["hidden_size"], cfg["shared_intermediate_size"]
    return (nm * _mamba_matmul_params(cfg) + na * _attn_matmul_params(cfg)
            + (nm + na) * 3 * d * ff)


def _head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg, boundary=None, spans=()) -> int:
    """Bytes of every weight a decode step reads (the embedding table is
    gathered, not read whole, and is not among them); A_log, dt_bias and D
    are float32 in the program."""
    d = cfg["hidden_size"]
    din, h, _, n, k = _ssm(cfg)
    nm, na = _counts(cfg)
    conv = din + 2 * n
    mamba_small = BF16 * (k * conv + (conv if cfg["mamba_conv_bias"] else 0)
                          + din) + F32 * 3 * h
    norms = BF16 * ((nm + na) * 2 * d + d)
    return (BF16 * (_matmul_params(cfg) + _head_params(cfg))
            + nm * mamba_small + norms)


def kv_bytes_per_position(cfg) -> int:
    """K and V of one position across the attention layers."""
    _, na = _counts(cfg)
    return na * 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BF16


def state_bytes_per_slot(cfg) -> int:
    """One slot's recurrent state over the Mamba layers: the conv tail in
    bfloat16 and the SSM state in float32."""
    din, h, hp, n, k = _ssm(cfg)
    nm, _ = _counts(cfg)
    return nm * (BF16 * (k - 1) * (din + 2 * n) + F32 * h * hp * n)


def _live_state_bytes(spans) -> int:
    """State read and written by the step: 2 x slots x bytes, from the
    boundary's ``serve.ssm_state`` instant (0 without one)."""
    for s in spans:
        if s.name == "serve.ssm_state" and s.args:
            return 2 * int(s.args["slots"]) * int(s.args["bytes_per_slot"])
    return 0


def _ssd_step_flops(cfg) -> int:
    """One token through one Mamba layer's recurrence and conv: the state
    decayed and fed (3 per element), read out (2 per element)."""
    din, h, hp, n, k = _ssm(cfg)
    return 5 * h * hp * n + 2 * k * (din + 2 * n)


def decode_step_flops(cfg, contexts, boundary=None, spans=()) -> float:
    """One decode step; ``contexts`` holds, per slot owed a token, the
    positions it attends (its cached context plus the new token)."""
    nh, hd = cfg["num_attention_heads"], head_dim(cfg)
    nm, na = _counts(cfg)
    per_token = (2 * (_matmul_params(cfg) + _head_params(cfg))
                 + nm * _ssd_step_flops(cfg))
    return float(sum(per_token + na * 4 * nh * hd * c for c in contexts))


def decode_step_bytes(cfg, contexts, boundary=None, spans=()) -> float:
    if not contexts:
        return 0.0
    kv = kv_bytes_per_position(cfg)
    d = cfg["hidden_size"]
    return float(weight_bytes(cfg)
                 + sum(kv * (c - 1) + kv + BF16 * d for c in contexts)
                 + _live_state_bytes(spans))


def _ssd_prefill_flops(cfg, p: int) -> int:
    """The chunked SSD over p real positions in one Mamba layer: within a
    chunk of q, C B^T and the weighted sum over the causal pairs; across
    chunks, the state read out and updated; plus the conv."""
    din, h, hp, n, k = _ssm(cfg)
    q0 = cfg["mamba_chunk_size"]
    total = 0
    for start in range(0, p, q0):
        q = min(q0, p - start)
        pairs = q * (q + 1) // 2
        total += 2 * pairs * n + 2 * pairs * h * hp + 2 * 2 * q * n * h * hp
    return total + p * 2 * k * (din + 2 * n)


def prefill_flops(cfg, prompt_len: int, boundary=None, spans=()) -> float:
    """A prompt of ``prompt_len`` real tokens (padding excluded), causal
    attention, and the head at the last position only."""
    nh, hd = cfg["num_attention_heads"], head_dim(cfg)
    nm, na = _counts(cfg)
    p = prompt_len
    return float(2 * _matmul_params(cfg) * p + 2 * _head_params(cfg)
                 + na * 2 * nh * hd * p * (p + 1)
                 + nm * _ssd_prefill_flops(cfg, p))
