"""Model assembly: blocks, scan-over-layers, the Model API (train fwd / loss /
prefill / decode) for all four architecture families.

Families:
  dense / moe / vlm / audio — pre-norm GQA transformer (+ MoE FFN);
    vlm (chameleon): early-fusion discrete tokens, frontend stubbed to ids;
    audio (musicgen): n_codebooks embeddings summed (EnCodec frontend stub).
  hybrid (zamba2) — mamba2 backbone with a *shared* attention block applied
    every ``attn_every`` layers (one set of attn weights, G call sites).
  ssm (rwkv6) — attention-free time-mix/channel-mix.

Layers are stacked and scanned (compact HLO at 512 devices); blocks are
rematerialised when cfg.remat.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import ffn as ffn_mod
from . import mamba2 as mamba_mod
from . import rwkv6 as rwkv_mod
from .attention import AttnParams, KVCache
from .common import ModelConfig, cross_entropy, init_dense


# ---------------------------------------------------------------------------
# dense / moe block
# ---------------------------------------------------------------------------

class BlockParams(NamedTuple):
    ln1: jax.Array
    attn: AttnParams
    ln2: jax.Array
    mlp: Any  # MlpParams | MoeParams


def _init_block(key, cfg: ModelConfig) -> BlockParams:
    k1, k2 = jax.random.split(key)
    mlp = (ffn_mod.init_moe(k2, cfg) if cfg.n_experts
           else ffn_mod.init_mlp(k2, cfg))
    return BlockParams(
        ln1=jnp.ones((cfg.d_model,), cfg.dtype),
        attn=attn_mod.init_attn(k1, cfg),
        ln2=jnp.ones((cfg.d_model,), cfg.dtype),
        mlp=mlp)


def _block_fwd(p: BlockParams, cfg: ModelConfig, x, positions):
    from .common import rmsnorm
    from repro.sharding import ctx
    # sequence parallelism: residual-stream activations live seq-sharded over
    # 'model' between blocks, so the TP boundary collective is a
    # reduce-scatter instead of a full all-reduce (half the bytes; the
    # all-gather happens where heads/ff need the full sequence)
    x = ctx.constraint(x, ctx.dp_axes(), "model", None)
    h = x + attn_mod.attention(p.attn, cfg, rmsnorm(x, p.ln1, cfg.norm_eps),
                               positions)
    h = ctx.constraint(h, ctx.dp_axes(), "model", None)
    y = rmsnorm(h, p.ln2, cfg.norm_eps)
    if cfg.n_experts:
        from . import moe_ep
        if moe_ep.applicable(cfg, ctx.get_mesh()):
            # explicit all-to-all EP exchange (EXPERIMENTS.md Perf, dbrx it.5)
            out, aux = moe_ep.moe_ep(p.mlp, cfg, y)
        else:
            out, aux = ffn_mod.moe(p.mlp, cfg, y)
    else:
        out, aux = ffn_mod.mlp(p.mlp, y), jnp.zeros((), jnp.float32)
    return h + out, aux


# ---------------------------------------------------------------------------
# rwkv6 block
# ---------------------------------------------------------------------------

class RwkvBlockParams(NamedTuple):
    ln1: jax.Array
    ln2: jax.Array
    mix: rwkv_mod.Rwkv6Params


def _init_rwkv_block(key, cfg: ModelConfig) -> RwkvBlockParams:
    return RwkvBlockParams(
        ln1=jnp.ones((cfg.d_model,), cfg.dtype),
        ln2=jnp.ones((cfg.d_model,), cfg.dtype),
        mix=rwkv_mod.init_rwkv6(key, cfg))


def _rwkv_block_fwd(p: RwkvBlockParams, cfg: ModelConfig, x,
                    state: rwkv_mod.Rwkv6State, lengths=None):
    from .common import rmsnorm
    xn = rmsnorm(x, p.ln1, cfg.norm_eps)
    tm, tshift, wkv = rwkv_mod.time_mix(p.mix, cfg, xn, state,
                                        lengths=lengths)
    h = x + tm
    hn = rmsnorm(h, p.ln2, cfg.norm_eps)
    cm, cshift = rwkv_mod.channel_mix(p.mix, cfg, hn, state, lengths=lengths)
    new_state = rwkv_mod.Rwkv6State(tshift, cshift, wkv)
    return h + cm, new_state


# ---------------------------------------------------------------------------
# hybrid (zamba2) block group
# ---------------------------------------------------------------------------

class HybridParams(NamedTuple):
    mamba: Any                 # stacked (G, E, ...) Mamba2Params
    mamba_ln: jax.Array        # (G, E, d)
    shared_ln: jax.Array       # (d,)
    shared_attn: AttnParams    # ONE set of weights, applied G times
    shared_ln2: jax.Array      # (d,)
    shared_mlp: Any            # MlpParams, shared like the attention


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- init ---------------------------------------------------------------
    def init_params(self, key) -> Dict:
        cfg = self.cfg
        kE, kB, kH, kF = jax.random.split(key, 4)
        if cfg.n_codebooks:
            embed = jnp.stack([
                init_dense(k, cfg.vocab, cfg.d_model, cfg.dtype, scale=0.02)
                for k in jax.random.split(kE, cfg.n_codebooks)])
        else:
            embed = init_dense(kE, cfg.vocab, cfg.d_model, cfg.dtype,
                               scale=0.02)
        params = {
            "embed": embed,
            "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
            "head": init_dense(kH, cfg.d_model, cfg.vocab, cfg.dtype),
        }
        if cfg.family == "ssm":
            keys = jax.random.split(kB, cfg.n_layers)
            params["blocks"] = jax.vmap(
                lambda k: _init_rwkv_block(k, cfg))(keys)
        elif cfg.family == "hybrid":
            g = cfg.n_layers // cfg.attn_every
            keys = jax.random.split(kB, g * cfg.attn_every).reshape(
                g, cfg.attn_every, 2)
            mamba = jax.vmap(jax.vmap(
                lambda k: mamba_mod.init_mamba2(k, cfg)))(keys)
            kF1, kF2 = jax.random.split(kF)
            params["blocks"] = HybridParams(
                mamba=mamba,
                mamba_ln=jnp.ones((g, cfg.attn_every, cfg.d_model), cfg.dtype),
                shared_ln=jnp.ones((cfg.d_model,), cfg.dtype),
                shared_attn=attn_mod.init_attn(kF1, cfg),
                shared_ln2=jnp.ones((cfg.d_model,), cfg.dtype),
                shared_mlp=ffn_mod.init_mlp(kF2, cfg))
        else:
            keys = jax.random.split(kB, cfg.n_layers)
            params["blocks"] = jax.vmap(lambda k: _init_block(k, cfg))(keys)
        return params

    # -- embedding ----------------------------------------------------------
    def embed(self, params, tokens):
        cfg = self.cfg
        if cfg.n_codebooks:
            # tokens: (b, s, K) — summed codebook embeddings (EnCodec stub)
            return sum(jnp.take(params["embed"][i], tokens[..., i], axis=0)
                       for i in range(cfg.n_codebooks))
        return jnp.take(params["embed"], tokens, axis=0)

    # -- forward (train / scoring) -------------------------------------------
    def forward(self, params, tokens):
        cfg = self.cfg
        x = self.embed(params, tokens)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        if cfg.family == "ssm":
            def body(carry, layer):
                x = carry
                x, _ = _rwkv_block_fwd(layer, cfg, x, None)
                return x, None
            fn = jax.checkpoint(body) if cfg.remat else body
            x, _ = jax.lax.scan(fn, x, params["blocks"])
        elif cfg.family == "hybrid":
            hp: HybridParams = params["blocks"]
            from .common import rmsnorm

            def group(carry, layer):
                x = carry
                mam, lns = layer

                def inner(c, l):
                    mp, ln = l
                    y, _ = mamba_mod.forward(mp, cfg, rmsnorm(c, ln,
                                                              cfg.norm_eps))
                    return c + y, None
                x, _ = jax.lax.scan(inner, x, (mam, lns))
                xa = rmsnorm(x, hp.shared_ln, cfg.norm_eps)
                x = x + attn_mod.attention(hp.shared_attn, cfg, xa, positions)
                xm = rmsnorm(x, hp.shared_ln2, cfg.norm_eps)
                x = x + ffn_mod.mlp(hp.shared_mlp, xm)
                return x, None
            fn = jax.checkpoint(group) if cfg.remat else group
            x, _ = jax.lax.scan(fn, x, (hp.mamba, hp.mamba_ln))
        else:
            aux0 = jnp.zeros((), jnp.float32)

            def body(carry, layer):
                x, aux = carry
                x, a = _block_fwd(layer, cfg, x, positions)
                return (x, aux + a), None
            fn = jax.checkpoint(body) if cfg.remat else body
            (x, aux), _ = jax.lax.scan(fn, (x, aux0), params["blocks"])
            self._last_aux = aux

        from .common import rmsnorm
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
        return logits

    def loss(self, params, batch) -> jax.Array:
        logits = self.forward(params, batch["tokens"])
        loss = cross_entropy(logits, batch["labels"])
        if self.cfg.n_experts:
            loss = loss + 0.01 * getattr(self, "_last_aux", 0.0)
        return loss

    # -- serving -------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int):
        cfg = self.cfg
        if cfg.family == "ssm":
            states = rwkv_mod.init_state(cfg, batch)
            return jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (cfg.n_layers,) + l.shape),
                states)
        if cfg.family == "hybrid":
            g = cfg.n_layers // cfg.attn_every
            ms = mamba_mod.init_state(cfg, batch)
            mstack = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(
                    l, (g, cfg.attn_every) + l.shape), ms)
            kv = attn_mod.init_cache(cfg, batch, max_seq)
            kvstack = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (g,) + l.shape), kv)
            return {"mamba": mstack, "kv": kvstack}
        kv = attn_mod.init_cache(cfg, batch, max_seq)
        return jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (cfg.n_layers,) + l.shape), kv)

    # -- paged serving cache -------------------------------------------------
    def init_paged_cache(self, batch: int, max_seq: int, *, n_blocks: int,
                         block_size: int):
        """The ``kv_layout="paged"`` engine cache: same pytree *structure*
        as :meth:`init_cache`, but KV leaves are page pools
        (``(n_blocks, block_size, nkv, hd)`` per layer/group) with no slot
        axis — slots map into the pool through their block tables.
        Recurrent state (ssm / the hybrid's mamba backbone) is O(1) per
        slot and stays slot-indexed; the ssm family has no KV at all, so
        its paged cache IS its dense cache."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return self.init_cache(batch, max_seq)
        pool = attn_mod.init_paged_kv(cfg, n_blocks, block_size)
        if cfg.family == "hybrid":
            g = cfg.n_layers // cfg.attn_every
            ms = mamba_mod.init_state(cfg, batch)
            mstack = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(
                    l, (g, cfg.attn_every) + l.shape), ms)
            kvstack = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (g,) + l.shape), pool)
            return {"mamba": mstack, "kv": kvstack}
        return jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (cfg.n_layers,) + l.shape), pool)

    def split_paged_cache(self, cache):
        """(kv pools, slot-indexed recurrent state) — either may be None."""
        if self.cfg.family == "ssm":
            return None, cache
        if self.cfg.family == "hybrid":
            return cache["kv"], cache["mamba"]
        return cache, None

    def merge_paged_cache(self, kv, state):
        """Inverse of :meth:`split_paged_cache`."""
        if self.cfg.family == "ssm":
            return state
        if self.cfg.family == "hybrid":
            return {"mamba": state, "kv": kv}
        return kv

    def init_prefill_state(self, batch: int = 1):
        """Fresh batch-``batch`` recurrent staging state for a chunked
        admission (None for pure-attention families — their prefill state
        lives entirely in the page pool)."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return self.init_cache(batch, 1)
        if cfg.family == "hybrid":
            g = cfg.n_layers // cfg.attn_every
            ms = mamba_mod.init_state(cfg, batch)
            return jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(
                    l, (g, cfg.attn_every) + l.shape), ms)
        return None

    def prefill(self, params, tokens, cache, start: int = 0, lengths=None,
                attend_cache: bool = False):
        """Fill the cache with ``tokens``; returns (last_logits, cache).

        ``lengths`` ((b,) int32) marks the real prompt length per row for
        RIGHT-padded batches: logits are gathered at ``lengths - 1`` instead
        of the final position, so bucket padding on the right never leaks
        into the returned next-token distribution.  For attention families a
        right-padded prefill is bitwise the unpadded computation — causal
        masking means real tokens never attend to the padding; for the
        recurrent families (ssm/hybrid) the state updates past ``lengths``
        are masked off (rwkv6.time_mix / mamba2.forward), so the returned
        cache is ALSO the unpadded cache and padded prefill is
        padding-invariant across every family.

        ``attend_cache=True`` is the CHUNKED-prefill continuation form: the
        attention families attend against the whole (updated) cache masked
        by ``kpos <= qpos`` instead of within ``tokens`` alone, so a chunk
        at offset ``start > 0`` sees every earlier chunk's positions.
        ``start`` may be traced in that form (one executable per chunk
        shape serves every offset).  Recurrent families carry their state
        through ``cache`` either way, so the flag only changes attention."""
        cfg = self.cfg
        x = self.embed(params, tokens)
        b, s = x.shape[:2]
        positions = start + jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        from .common import rmsnorm

        if cfg.family == "ssm":
            def body(carry, layer_and_state):
                x = carry
                layer, st = layer_and_state
                x, new_st = _rwkv_block_fwd(layer, cfg, x, st,
                                            lengths=lengths)
                return x, new_st
            x, new_states = jax.lax.scan(body, x, (params["blocks"], cache))
            new_cache = new_states
        elif cfg.family == "hybrid":
            hp: HybridParams = params["blocks"]

            def group(carry, inp):
                x = carry
                (mam, lns), mstates, kv = inp

                def inner(c, l):
                    (mp, ln), st = l
                    y, nst = mamba_mod.forward(
                        mp, cfg, rmsnorm(c, ln, cfg.norm_eps), st,
                        lengths=lengths)
                    return c + y, nst
                x, new_mst = jax.lax.scan(inner, x, ((mam, lns), mstates))
                xa = rmsnorm(x, hp.shared_ln, cfg.norm_eps)
                if attend_cache:
                    y, new_kv = attn_mod.attention_prefill_cached(
                        hp.shared_attn, cfg, xa, kv, start)
                else:
                    y, new_kv = attn_mod.attention_prefill(
                        hp.shared_attn, cfg, xa, kv, start)
                x = x + y
                xm = rmsnorm(x, hp.shared_ln2, cfg.norm_eps)
                x = x + ffn_mod.mlp(hp.shared_mlp, xm)
                return x, (new_mst, new_kv)
            x, (new_mst, new_kv) = jax.lax.scan(
                group, x, ((hp.mamba, hp.mamba_ln), cache["mamba"],
                           cache["kv"]))
            new_cache = {"mamba": new_mst, "kv": new_kv}
        else:
            def body(carry, layer_and_cache):
                x, aux = carry
                layer, kv = layer_and_cache
                h_in = rmsnorm(x, layer.ln1, cfg.norm_eps)
                if attend_cache:
                    y_attn, new_kv = attn_mod.attention_prefill_cached(
                        layer.attn, cfg, h_in, kv, start)
                else:
                    y_attn, new_kv = attn_mod.attention_prefill(
                        layer.attn, cfg, h_in, kv, start)
                h = x + y_attn
                y = rmsnorm(h, layer.ln2, cfg.norm_eps)
                if cfg.n_experts:
                    out, a = ffn_mod.moe(layer.mlp, cfg, y)
                else:
                    out, a = ffn_mod.mlp(layer.mlp, y), 0.0
                return (h + out, aux + a), new_kv
            (x, _), new_cache = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (params["blocks"], cache))

        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        if lengths is None:
            x_last = x[:, -1]
        else:
            idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, s - 1)
            x_last = jnp.take_along_axis(
                x, idx[:, None, None], axis=1)[:, 0]
        logits = jnp.einsum("bd,dv->bv", x_last, params["head"])
        return logits, new_cache

    def prefill_paged(self, params, tokens, kv, bt_row, state, start,
                      lengths, *, first: bool):
        """Prefill one prompt chunk of ONE slot into paged KV pools.

        tokens: (1, s); kv: the engine's pooled KV leaves
        (:meth:`split_paged_cache`; None for ssm); bt_row: the slot's
        (max_blocks,) block-table row; state: batch-1 recurrent staging
        state (:meth:`init_prefill_state`; None for attention-only
        families); start: chunk offset (traced ok when ``not first``);
        lengths: (1,) real token count WITHIN this chunk.  Returns
        (last_logits, kv, state).

        ``first`` (static) is the chunk-0 form: attention runs within
        ``tokens`` exactly like the dense admission prefill — bitwise the
        oracle's computation for prompts that fit one chunk; continuation
        chunks gather the slot's pages and attend ``kpos <= qpos``."""
        cfg = self.cfg
        if cfg.family == "ssm":
            logits, new_state = self.prefill(params, tokens, state,
                                             lengths=lengths)
            return logits, kv, new_state
        from .common import rmsnorm
        x = self.embed(params, tokens)
        b, s = x.shape[:2]
        start = jnp.asarray(start, jnp.int32)
        positions = start + jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        if cfg.family == "hybrid":
            hp: HybridParams = params["blocks"]

            def group(carry, inp):
                x, ck, cv, gi = carry
                (mam, lns), mstates = inp

                def inner(c, l):
                    (mp, ln), st = l
                    y, nst = mamba_mod.forward(
                        mp, cfg, rmsnorm(c, ln, cfg.norm_eps), st,
                        lengths=lengths)
                    return c + y, nst
                x, new_mst = jax.lax.scan(inner, x, ((mam, lns), mstates))
                xa = rmsnorm(x, hp.shared_ln, cfg.norm_eps)
                y, ck, cv = attn_mod.paged_attention_prefill(
                    hp.shared_attn, cfg, xa, ck, cv, gi, bt_row, start,
                    first=first)
                x = x + y
                xm = rmsnorm(x, hp.shared_ln2, cfg.norm_eps)
                x = x + ffn_mod.mlp(hp.shared_mlp, xm)
                return (x, ck, cv, gi + 1), new_mst
            (x, ck, cv, _), new_state = jax.lax.scan(
                group, (x, kv.k, kv.v, jnp.int32(0)),
                ((hp.mamba, hp.mamba_ln), state))
        else:
            def body(carry, layer):
                x, ck, cv, li = carry
                with jax.named_scope("attention"):
                    h_in = rmsnorm(x, layer.ln1, cfg.norm_eps)
                    y, ck, cv = attn_mod.paged_attention_prefill(
                        layer.attn, cfg, h_in, ck, cv, li, bt_row, start,
                        first=first)
                h = x + y
                with jax.named_scope("mlp"):
                    z = rmsnorm(h, layer.ln2, cfg.norm_eps)
                    if cfg.n_experts:
                        out, _ = ffn_mod.moe(layer.mlp, cfg, z)
                    else:
                        out = ffn_mod.mlp(layer.mlp, z)
                return (h + out, ck, cv, li + 1), None
            (x, ck, cv, _), _ = jax.lax.scan(
                body, (x, kv.k, kv.v, jnp.int32(0)), params["blocks"])
            new_state = state

        with jax.named_scope("head"):
            x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
            idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0, s - 1)
            x_last = jnp.take_along_axis(x, idx[:, None, None],
                                         axis=1)[:, 0]
            logits = jnp.einsum("bd,dv->bv", x_last, params["head"])
        return logits, KVCache(ck, cv), new_state

    def gather_paged_view(self, cache, block_tables):
        """The per-slot logical (vk, vv) view of a paged cache's pools —
        gathered once per decode chunk (None for the ssm family, which has
        no KV).  See :func:`repro.models.attention.gather_paged_view`."""
        if self.cfg.family == "ssm":
            return None
        kv, _ = self.split_paged_cache(cache)
        return attn_mod.gather_paged_view(kv.k, kv.v, block_tables)

    def decode_step(self, params, token, cache, pos, block_tables=None,
                    kv_view=None, paged_kernel: bool = False):
        """token: (b, 1[, K]) -> (logits (b, vocab), new cache).

        ``pos`` is a scalar (lock-step batch) or a (b,) per-slot position
        vector (continuous batching) — threaded through to
        ``attention_decode_inplace``; recurrent families ignore it.

        ``block_tables`` ((b, max_blocks) int32) selects the PAGED KV
        path: the cache's KV leaves are page pools and attention goes
        through :func:`repro.models.attention.paged_attention_decode_inplace`
        — same masked math over a gathered per-slot view, so the layout is
        a strategy choice, not a fork in the model.  With ``kv_view`` (the
        (vk, vv) pair from :meth:`gather_paged_view`, gathered once per
        chunk) attention runs against the view and the return value is
        ``(logits, cache, view)`` — the fused chunk's amortised-gather
        form.  With ``paged_kernel`` (static; the pools live on one TPU,
        :func:`repro.models.attention.paged_kernel_engages`) attention
        reads each slot's valid pages straight from the pool through the
        Pallas kernel
        (:func:`repro.models.attention.paged_attention_decode_kernel`)
        and no view is gathered."""
        cfg = self.cfg
        x = self.embed(params, token)
        b = x.shape[0]
        from .common import rmsnorm

        new_view = None
        if cfg.family == "ssm":
            def body(carry, layer_and_state):
                x = carry
                layer, st = layer_and_state
                x, new_st = _rwkv_block_fwd(layer, cfg, x, st)
                return x, new_st
            x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
        elif cfg.family == "hybrid":
            hp: HybridParams = params["blocks"]
            # KV caches ride in the scan CARRY with token-sized in-place
            # updates (attention_decode_inplace); small mamba states stay
            # as scanned xs/ys.
            ck0, cv0 = cache["kv"].k, cache["kv"].v   # (G, b, s, kv, hd)
            # the view (when given) rides in the carry NEXT TO the pools —
            # attention reads/updates the view, the pool gets the mirrored
            # token write; without a view the carry keeps its dense shape
            kv0 = kv_view if kv_view is not None else ()

            def group(carry, inp):
                (x, ck, cv, gi), view = carry[:4], carry[4:]
                (mam, lns), mstates = inp

                def inner(c, l):
                    (mp, ln), st = l
                    y, nst = mamba_mod.decode_step(
                        mp, cfg, rmsnorm(c, ln, cfg.norm_eps), st)
                    return c + y, nst
                x, new_mst = jax.lax.scan(inner, x, ((mam, lns), mstates))
                xa = rmsnorm(x, hp.shared_ln, cfg.norm_eps)
                if kv_view is not None:
                    y, ck, cv, vk, vv = attn_mod.paged_attention_decode_view(
                        hp.shared_attn, cfg, xa, ck, cv, view[0], view[1],
                        gi, pos, block_tables)
                    view = (vk, vv)
                elif block_tables is not None and paged_kernel:
                    y, ck, cv = attn_mod.paged_attention_decode_kernel(
                        hp.shared_attn, cfg, xa, ck, cv, gi, pos,
                        block_tables)
                elif block_tables is not None:
                    y, ck, cv = attn_mod.paged_attention_decode_inplace(
                        hp.shared_attn, cfg, xa, ck, cv, gi, pos,
                        block_tables)
                else:
                    y, ck, cv = attn_mod.attention_decode_inplace(
                        hp.shared_attn, cfg, xa, ck, cv, gi, pos)
                x = x + y
                xm = rmsnorm(x, hp.shared_ln2, cfg.norm_eps)
                x = x + ffn_mod.mlp(hp.shared_mlp, xm)
                return (x, ck, cv, gi + 1) + view, new_mst
            out_carry, new_mst = jax.lax.scan(
                group, (x, ck0, cv0, jnp.int32(0)) + tuple(kv0),
                ((hp.mamba, hp.mamba_ln), cache["mamba"]))
            x, ck, cv = out_carry[0], out_carry[1], out_carry[2]
            new_cache = {"mamba": new_mst, "kv": KVCache(ck, cv)}
            if kv_view is not None:
                new_view = out_carry[4:6]
        else:
            ck0, cv0 = cache.k, cache.v               # (L, b, s, kv, hd)
            kv0 = kv_view if kv_view is not None else ()

            def body(carry, layer):
                (x, ck, cv, li), view = carry[:4], carry[4:]
                with jax.named_scope("attention"):
                    h = rmsnorm(x, layer.ln1, cfg.norm_eps)
                    if kv_view is not None:
                        y, ck, cv, vk, vv = (
                            attn_mod.paged_attention_decode_view(
                                layer.attn, cfg, h, ck, cv, view[0],
                                view[1], li, pos, block_tables))
                        view = (vk, vv)
                    elif block_tables is not None and paged_kernel:
                        y, ck, cv = attn_mod.paged_attention_decode_kernel(
                            layer.attn, cfg, h, ck, cv, li, pos,
                            block_tables)
                    elif block_tables is not None:
                        y, ck, cv = attn_mod.paged_attention_decode_inplace(
                            layer.attn, cfg, h, ck, cv, li, pos,
                            block_tables)
                    else:
                        y, ck, cv = attn_mod.attention_decode_inplace(
                            layer.attn, cfg, h, ck, cv, li, pos)
                x = x + y
                with jax.named_scope("mlp"):
                    z = rmsnorm(x, layer.ln2, cfg.norm_eps)
                    if cfg.n_experts:
                        out, _ = ffn_mod.moe(layer.mlp, cfg, z)
                    else:
                        out = ffn_mod.mlp(layer.mlp, z)
                return (x + out, ck, cv, li + 1) + view, None
            out_carry, _ = jax.lax.scan(
                body, (x, ck0, cv0, jnp.int32(0)) + tuple(kv0),
                params["blocks"])
            x, ck, cv = out_carry[0], out_carry[1], out_carry[2]
            new_cache = KVCache(ck, cv)
            if kv_view is not None:
                new_view = out_carry[4:6]

        with jax.named_scope("head"):
            x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
            logits = jnp.einsum("bd,dv->bv", x[:, -1], params["head"])
        if kv_view is not None:
            return logits, new_cache, new_view
        return logits, new_cache
