"""Expert-parallel MoE dispatch with explicit all-to-all (shard_map island).

GSPMD cannot infer all-to-all from a scatter across a sharded expert dim —
it falls back to all-gathers of token tensors (measured: the dominant
collective term on dbrx/grok train cells, EXPERIMENTS.md section Perf).  This
module does the exchange manually:

  per (dp x model) shard: local top-k routing
    -> fixed-capacity per-destination buckets (cumsum slotting)
    -> lax.all_to_all over 'model'  (payload ~ t*k*d/shards, the EP ideal)
    -> local expert FFN (each model shard owns e/model_size experts)
    -> all_to_all back, gate-weighted combine at the source.

Requirements: mesh has a 'model' axis, n_experts % model_size == 0, and the
local token count divides evenly; otherwise callers fall back to ffn.moe
(the GSPMD path).  Differentiable (all_to_all transposes to all_to_all).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from .common import ModelConfig
from .ffn import MoeParams


def applicable(cfg: ModelConfig, mesh) -> bool:
    if mesh is None or "model" not in mesh.shape:
        return False
    return cfg.n_experts > 0 and cfg.n_experts % mesh.shape["model"] == 0


def moe_ep(p: MoeParams, cfg: ModelConfig, x) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for ffn.moe with explicit EP all-to-all.  x: (b, s, d)."""
    from repro.sharding import ctx

    mesh = ctx.get_mesh()
    assert applicable(cfg, mesh)
    dp = ctx.dp_axes() or ()
    model_size = mesh.shape["model"]
    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    e_local = e // model_size
    b, s, _ = x.shape

    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    # local token geometry: batch over dp, sequence over model (SP layout)
    if (b % dp_size) or (s % model_size):
        from . import ffn
        return ffn.moe(p, cfg, x)
    tl = (b // dp_size) * (s // model_size)
    cap = max(int(np.ceil(cfg.moe_capacity_factor * tl * k / model_size)), 4)

    def body(xl, router, w_gate, w_up, w_down):
        # xl: (b_l, s_l, d); weights: router (d, e) replicated,
        # w_* (e_local, d, f) — this shard's experts.
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)

        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_i = jax.lax.top_k(probs, k)               # (t, k)
        topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)

        me = jax.lax.pmean(jnp.mean(probs, axis=0), "model")
        oh = jax.nn.one_hot(topk_i, e, dtype=jnp.float32)
        ce = jax.lax.pmean(jnp.mean(jnp.sum(oh, axis=1), axis=0), "model")
        aux = e * jnp.sum(me * ce) / k

        flat_e = topk_i.reshape(-1)                             # (t*k,)
        dst = flat_e // e_local                                 # dest shard
        e_loc = flat_e % e_local                                # expert @ dst
        # slot within (dst) bucket via masked cumsum
        oh_dst = jax.nn.one_hot(dst, model_size, dtype=jnp.int32)
        pos = jnp.sum(jnp.cumsum(oh_dst, axis=0) * oh_dst, axis=-1) - 1
        keep = pos < cap
        gate = topk_p.reshape(-1) * keep
        pos_c = jnp.clip(pos, 0, cap - 1)

        tok_idx = jnp.repeat(jnp.arange(t), k)
        xk = jnp.take(xt, tok_idx, axis=0)
        xk = xk * keep[:, None].astype(xt.dtype)
        send = jnp.zeros((model_size, cap, d), xt.dtype)
        send = send.at[dst, pos_c].add(xk, mode="drop")
        meta = jnp.zeros((model_size, cap), jnp.int32)
        meta = meta.at[dst, pos_c].add(
            jnp.where(keep, e_loc + 1, 0), mode="drop")

        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        meta_r = jax.lax.all_to_all(meta, "model", split_axis=0,
                                    concat_axis=0, tiled=False)

        # local expert compute
        re = (meta_r.reshape(-1) - 1)                           # (-1 = empty)
        occupied = re >= 0
        slots = recv.reshape(model_size * cap, d)
        slots = slots * occupied[:, None].astype(slots.dtype)
        if e_local == 1:
            # one expert per shard (the common at-scale case): slots feed the
            # expert directly — no zero-padded per-expert buffers
            h = jax.nn.silu(jnp.einsum("cd,df->cf", slots, w_gate[0]))
            h = h * jnp.einsum("cd,df->cf", slots, w_up[0])
            yslots = jnp.einsum("cf,fd->cd", h, w_down[0])
        else:
            re_c = jnp.clip(re, 0, e_local - 1)
            slot_pos = jnp.arange(model_size * cap)
            ebuf = jnp.zeros((e_local, model_size * cap, d), slots.dtype)
            ebuf = ebuf.at[re_c, slot_pos].add(slots, mode="drop")
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, w_gate))
            h = h * jnp.einsum("ecd,edf->ecf", ebuf, w_up)
            ybuf = jnp.einsum("ecf,efd->ecd", h, w_down)
            yslots = ybuf[re_c, slot_pos]                       # gather back
        yslots = yslots * occupied[:, None].astype(yslots.dtype)

        yback = jax.lax.all_to_all(
            yslots.reshape(model_size, cap, d), "model",
            split_axis=0, concat_axis=0, tiled=False)

        yk = yback[dst, pos_c]                                  # (t*k, d)
        yk = yk * gate[:, None].astype(yback.dtype)
        out = jnp.zeros((t, d), yback.dtype).at[tok_idx].add(yk)
        return out.reshape(bl, sl, d).astype(xl.dtype), aux

    dp_spec = dp if dp else None
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PS(dp_spec, "model", None),        # x: batch x seq(SP) x d
                  PS(None, None),                    # router replicated
                  PS("model", None, None),           # experts over model
                  PS("model", None, None),
                  PS("model", None, None)),
        out_specs=(PS(dp_spec, "model", None), PS()),
        check_vma=False)
    out, aux = sm(x, p.router, p.w_gate, p.w_up, p.w_down)
    return out, aux
