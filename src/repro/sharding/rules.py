"""Sharding rules: parameters, optimizer state, activations, KV caches.

Strategy (DESIGN.md section 6):
  * TP   — the 'model' axis splits head/ff/expert/vocab dims (Megatron col/row);
  * FSDP — when cfg.fsdp, the 'data' (+'pod') axes additionally shard the
           complementary dim of every matrix (ZeRO-3 style);
  * EP   — expert dim over 'model' when divisible, else TP inside experts;
  * SP   — sequence dim of activations over 'model' between blocks.

Implementation: a dimension-size-aware auto-sharder with a small override
table, so every architecture (dense/MoE/mamba/rwkv) shards without per-arch
spec tables, and never emits a spec that does not divide.  Layer-stacked
params (leading L dim from scan) keep their leading dim unsharded.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _divides(mesh: Mesh, axes, dim: int) -> bool:
    return dim % _axis_size(mesh, axes) == 0


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
    return "/".join(parts)


def param_spec(leaf, path: str, mesh: Mesh, *, fsdp: bool,
               stacked_dims: int = 0) -> PS:
    """Auto-shard one parameter leaf.

    stacked_dims: number of leading layer-stack dims to leave unsharded
    (inferred by the caller from path membership in 'blocks')."""
    shape = leaf.shape[stacked_dims:]
    lead = (None,) * stacked_dims
    model = "model" if "model" in mesh.shape else None
    fs = dp_axes(mesh) if fsdp else None

    if len(shape) == 0:
        return PS(*lead)
    if len(shape) == 1:
        # vectors: shard over model when cleanly divisible and large
        if model and shape[0] >= 1024 and _divides(mesh, model, shape[0]):
            return PS(*lead, model)
        return PS(*lead, None)

    # matrices / tensors: pick the model dim = last dim by default (column
    # parallel); for *_out / w_down / wo style (detected by name) use first
    # (row parallel).  FSDP takes the complementary dim.
    row_parallel = any(t in path for t in ("wo", "w_down", "w_out", "cw_v",
                                           "w_lora_b", "head"))
    dims: list = [None] * len(shape)
    m_dim = len(shape) - 1
    f_dim = len(shape) - 2

    if "router" in path:
        return PS(*lead, *( [None] * len(shape) ))
    if path.endswith("embed"):
        # (vocab, d): shard vocab over model, d over fsdp axes
        spec = [None] * len(shape)
        if model and _divides(mesh, model, shape[-2]):
            spec[-2] = model
        if fs and _divides(mesh, fs, shape[-1]):
            spec[-1] = fs
        return PS(*lead, *spec)

    if len(shape) == 3 and ("w_gate" in path or "w_up" in path
                            or "w_down" in path):
        # MoE expert tensors (E, d, f) / (E, f, d): experts over model (EP)
        # when divisible, else TP on the ff dim.
        e = shape[0]
        if model and _divides(mesh, model, e):
            spec = [model, None, None]
            if fs and _divides(mesh, fs, shape[1]):
                spec[1] = fs
            return PS(*lead, *spec)
        ff_dim = 2 if "w_down" not in path else 1
        spec = [None, None, None]
        if model and _divides(mesh, model, shape[ff_dim]):
            spec[ff_dim] = model
        other = 1 if ff_dim == 2 else 2
        if fs and _divides(mesh, fs, shape[other]):
            spec[other] = fs
        return PS(*lead, *spec)

    if row_parallel:
        m_dim, f_dim = 0 if len(shape) == 2 else len(shape) - 2, len(shape) - 1
    else:
        m_dim, f_dim = len(shape) - 1, len(shape) - 2

    spec = [None] * len(shape)
    if model and _divides(mesh, model, shape[m_dim]):
        spec[m_dim] = model
    if fs and _divides(mesh, fs, shape[f_dim]) and spec[f_dim] is None:
        spec[f_dim] = fs
    return PS(*lead, *spec)


def params_specs(params, mesh: Mesh, cfg) -> object:
    """PartitionSpec pytree for a parameter pytree."""
    def assign(path, leaf):
        p = _path_str(path)
        stacked = 0
        if p.startswith("blocks"):
            # scan-stacked: 1 leading dim; the hybrid's per-period stacks
            # have 2 (G, per period), its shared block none
            stacked = 1
            if cfg.family == "hybrid":
                stacked = 0 if "shared" in p else 2
        return param_spec(leaf, p, mesh, fsdp=cfg.fsdp, stacked_dims=stacked)

    return jax.tree_util.tree_map_with_path(assign, params)


def opt_state_specs(params_spec_tree, mesh: Mesh, cfg) -> object:
    """Adam moments shard exactly like their parameters (plus they are always
    FSDP-sharded when the config asks for it — ZeRO-1)."""
    return params_spec_tree  # moments mirror param specs


def batch_specs(mesh: Mesh) -> PS:
    return PS(dp_axes(mesh) or None)


def activation_spec(mesh: Mesh, *, sp: bool = True) -> PS:
    """(b, s, d) activations: batch over dp axes, seq over model (SP)."""
    model = "model" if (sp and "model" in mesh.shape) else None
    return PS(dp_axes(mesh) or None, model, None)


def cache_specs(cfg, mesh: Mesh, cache) -> object:
    """KV cache / SSM state sharding for decode: batch over dp; kv-heads over
    model when divisible, else sequence-sharded KV (flash-decode layout)."""
    model = "model" if "model" in mesh.shape else None
    dp = dp_axes(mesh) or None

    dp_sz = _axis_size(mesh, dp)

    def assign(path, leaf):
        p = _path_str(path)
        shape = leaf.shape
        if "kv" in p or p.endswith("k") or p.endswith("v"):
            # stacked (L, b, s, kv, hd)
            if len(shape) == 5:
                bspec = dp if shape[1] % dp_sz == 0 else None
                if model and shape[3] % _axis_size(mesh, model) == 0:
                    return PS(None, bspec, None, model, None)
                if model and shape[2] % _axis_size(mesh, model) == 0:
                    return PS(None, bspec, model, None, None)  # seq-sharded KV
                return PS(None, bspec, None, None, None)
        if len(shape) >= 2:
            spec = [None] * len(shape)
            # batch dim is the first non-layer dim
            bdim = 1 if len(shape) >= 3 else 0
            if shape[bdim] % dp_sz == 0:
                spec[bdim] = dp
            # try model on the largest remaining divisible dim
            rest = [(i, s) for i, s in enumerate(shape)
                    if i != bdim and spec[i] is None]
            rest.sort(key=lambda t: -t[1])
            for i, s in rest:
                if model and s % _axis_size(mesh, model) == 0 and s >= 64:
                    spec[i] = model
                    break
            return PS(*spec)
        return PS(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(assign, cache)


def named(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), spec_tree,
                                  is_leaf=lambda x: isinstance(x, PS))
