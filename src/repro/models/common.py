"""Model configuration + shared components (embeddings, norms, RoPE, init)."""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str            # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None      # defaults to d_model // n_heads
    qk_norm: bool = False
    qkv_bias: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25
    # SSM / hybrid
    ssm_state: int = 0                  # Mamba-2 d_state
    attn_every: int = 0                 # hybrid: shared attn every k blocks
    # hybrid with per-layer types ("mamba" | "attention"): attention layers
    # with their own weights and an MLP after every mixer
    layer_types: Tuple[str, ...] = ()
    # Mamba-2 widths: heads of mamba_d_head (mamba_n_heads 0: an inner
    # width of 2 * d_model), B and C in mamba_n_groups groups, causal conv
    # of mamba_d_conv taps, SSD prefill in chunks of mamba_chunk positions
    mamba_n_heads: int = 0
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = False
    mamba_chunk: int = 256
    # muP multipliers: embeddings scaled up, each residual branch scaled,
    # attention scores scaled by attention_multiplier (None: 1/sqrt(hd)),
    # logits divided by logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    rope: bool = True                   # False: no position embedding (NoPE)
    # audio (musicgen): codebooks summed at the embedding (frontend stub)
    n_codebooks: int = 0
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # runtime / distribution knobs
    remat: bool = True
    fsdp: bool = False                  # ZeRO-style param+opt sharding on data
    opt_8bit: bool = False              # 8-bit Adam moments (100B+ configs)
    max_seq: int = 4096

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def mamba_d_inner(self) -> int:
        if self.mamba_n_heads:
            return self.mamba_n_heads * self.mamba_d_head
        return 2 * self.d_model

    def hybrid_period(self) -> Tuple[str, ...]:
        """The hybrid's repeating unit of layers, in order: the shortest
        prefix of ``layer_types`` that repeats to the whole stack, or
        (zamba2, no ``layer_types``) ``attn_every`` Mamba layers then the
        shared attention block ("shared")."""
        if not self.layer_types:
            return ("mamba",) * self.attn_every + ("shared",)
        lt, n = tuple(self.layer_types), self.n_layers
        if len(lt) != n:
            raise ValueError(f"layer_types has {len(lt)} entries for "
                             f"{n} layers")
        for p in range(1, n + 1):
            if n % p == 0 and lt == lt[:p] * (n // p):
                return lt[:p]
        raise AssertionError("unreachable: the whole stack is a period")

    def attention_layers(self) -> int:
        """Layers that attend, each with its own KV cache: the attention
        entries of ``layer_types``, one shared block per group for zamba2,
        none for ssm, every layer otherwise."""
        if self.family == "ssm":
            return 0
        if self.layer_types:
            return sum(t == "attention" for t in self.layer_types)
        if self.family == "hybrid":
            return self.n_layers // self.attn_every
        return self.n_layers

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (embedding included once)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        hd, nh, nkv = self.hd, self.n_heads, self.n_kv_heads
        attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
        ffn = 3 * d * f  # SwiGLU
        if self.n_experts:
            ffn = self.n_experts * 3 * d * f + d * self.n_experts  # + router
        per_layer = attn + ffn + 2 * d
        if self.family == "ssm":        # rwkv6: time-mix + channel-mix
            tm = d * d * 4 + d * 64 * 2 + d * 6  # r,k,v,o + lora decay + mixes
            cm = d * f + f * d + d * d
            per_layer = tm + cm + 2 * d
        if self.family == "hybrid":
            din, g = self.mamba_d_inner, self.mamba_n_groups
            nheads_m = din // self.mamba_d_head
            conv = (din + 2 * g * self.ssm_state)
            mamba = (d * (2 * din + 2 * g * self.ssm_state + nheads_m)
                     + din * d + self.mamba_d_conv * conv + 3 * nheads_m
                     + din + (conv if self.mamba_conv_bias else 0))
            if self.layer_types:
                # each layer: its own mixer and norm, an MLP and its norm
                n_attn = self.attention_layers()
                mixers = (n_attn * attn
                          + (self.n_layers - n_attn) * mamba)
                return (mixers + self.n_layers * (3 * d * f + 2 * d)
                        + d + v * d + v * d)
            # mamba2-only layers; the SHARED block (attn + MLP) counts once
            per_layer = mamba + d
            shared = attn + 3 * d * f + 2 * d
            return self.n_layers * per_layer + shared + v * d + v * d
        emb = v * d
        head = v * d
        return self.n_layers * per_layer + emb + head

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top_k experts)."""
        if not self.n_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        dense_moe = self.n_experts * 3 * d * f
        active_moe = self.top_k * 3 * d * f
        return self.param_count() - self.n_layers * (dense_moe - active_moe)


def init_dense(key, d_in: int, d_out: int, dtype: str, scale: float = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_tokens(emb, tokens):
    return jnp.take(emb, tokens, axis=0)


def rmsnorm(x, w, eps: float = 1e-6):
    return ops.rmsnorm(x, w, eps=eps)


def rope_freqs(hd: int, theta: float, positions):
    """positions: (... ,seq) int32 -> (..., seq, hd//2) cos/sin."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., seq, heads, hd); cos/sin: (..., seq, hd//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def cross_entropy(logits, labels, *, z_loss: float = 1e-4):
    """Next-token CE in float32 with z-loss regulariser; labels -100 masked."""
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    labels_safe = jnp.where(mask, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels_safe[..., None],
                             axis=-1)[..., 0] - lse
    loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)
    zl = z_loss * ((lse * mask) ** 2).sum() / jnp.maximum(mask.sum(), 1)
    return loss + zl
