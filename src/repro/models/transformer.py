"""Model assembly: blocks, scan-over-layers, the Model API (train fwd / loss /
prefill / decode) for all four architecture families.

Families:
  dense / moe / vlm / audio — pre-norm GQA transformer (+ MoE FFN);
    vlm (chameleon): early-fusion discrete tokens, frontend stubbed to ids;
    audio (musicgen): n_codebooks embeddings summed (EnCodec frontend stub).
  hybrid — Mamba-2 layers and attention in a repeating period of layers
    (:meth:`ModelConfig.hybrid_period`), scanned period by period:
    zamba2's Mamba backbone with one *shared* attention block applied every
    ``attn_every`` layers (one set of attn weights, G call sites), or
    per-layer types (granite-4.0-h): attention layers with their own
    weights, an MLP after every mixer, muP multipliers.
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
    """G periods stacked on the leading axis; a period holds M Mamba
    layers and A attention layers of its own (per-layer types), or calls
    the one shared block (zamba2)."""
    mamba: Any                 # stacked (G, M, ...) Mamba2Params
    mamba_ln: jax.Array        # (G, M, d)
    shared_ln: Any = None      # (d,)
    shared_attn: Any = None    # AttnParams: ONE set, applied G times
    shared_ln2: Any = None     # (d,)
    shared_mlp: Any = None     # MlpParams, shared like the attention
    attn: Any = None           # (G, A, ...) AttnParams, per-layer types
    attn_ln: Any = None        # (G, A, d)
    mlp: Any = None            # (G, P, ...) MlpParams: one after every mixer
    mlp_ln: Any = None         # (G, P, d)


def _at(tree, g, j):
    """Entry (g, j) of every leaf of a tree stacked (G, per period, ...):
    one slice of each leaf, so a weight's single reader (a matmul) reads
    it in place, with no copy of the period."""
    def one(a):
        zero = (jnp.int32(0),) * (a.ndim - 2)
        return jax.lax.dynamic_slice(
            a, (jnp.asarray(g, jnp.int32), jnp.int32(j)) + zero,
            (1, 1) + a.shape[2:])[0, 0]
    return jax.tree_util.tree_map(one, tree)


def _set(tree, g, j, new):
    """``tree`` with entry (g, j) replaced, in place (a carried buffer
    takes a layer-sized update)."""
    def put(a, n):
        zero = (jnp.int32(0),) * n.ndim
        return jax.lax.dynamic_update_slice(
            a, n[None, None].astype(a.dtype),
            (jnp.asarray(g, jnp.int32), jnp.int32(j)) + zero)
    return jax.tree_util.tree_map(put, tree, new)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- the hybrid's period --------------------------------------------------
    def _hybrid_dims(self):
        """(G periods, Mamba layers a period, attention sites a period)."""
        cfg = self.cfg
        period = cfg.hybrid_period()
        g = (cfg.n_layers // len(period) if cfg.layer_types
             else cfg.n_layers // cfg.attn_every)
        sites = 1 if "shared" in period else period.count("attention")
        return g, period.count("mamba"), sites

    def _run_period(self, hp: HybridParams, g, x, mamba_fn, attn_fn):
        """x through period ``g`` (traced) of the stack ``hp``:
        ``mamba_fn(j, p, xn)`` runs the period's Mamba layer j and
        ``attn_fn(a, p, xn)`` its attention site a on the normed input, each
        returning the mixer's output.  Each layer's weights are sliced from
        the stacks where they are read."""
        from .common import rmsnorm
        cfg = self.cfg
        eps, r = cfg.norm_eps, cfg.residual_multiplier

        def add(x, y):
            return x + (y * r if r != 1.0 else y)

        jm = ja = 0
        for li, kind in enumerate(cfg.hybrid_period()):
            if kind == "shared":
                with jax.named_scope("attention"):
                    x = x + attn_fn(0, hp.shared_attn,
                                    rmsnorm(x, hp.shared_ln, eps))
                with jax.named_scope("mlp"):
                    x = x + ffn_mod.mlp(hp.shared_mlp,
                                        rmsnorm(x, hp.shared_ln2, eps))
                continue
            if kind == "mamba":
                with jax.named_scope("mamba"):
                    x = add(x, mamba_fn(
                        jm, _at(hp.mamba, g, jm),
                        rmsnorm(x, _at(hp.mamba_ln, g, jm), eps)))
                jm += 1
            else:
                with jax.named_scope("attention"):
                    x = add(x, attn_fn(
                        ja, _at(hp.attn, g, ja),
                        rmsnorm(x, _at(hp.attn_ln, g, ja), eps)))
                ja += 1
            if hp.mlp is not None:
                with jax.named_scope("mlp"):
                    x = add(x, ffn_mod.mlp(
                        _at(hp.mlp, g, li),
                        rmsnorm(x, _at(hp.mlp_ln, g, li), eps)))
        return x

    def _head(self, params, x):
        """Next-token logits of the final normed hidden states x (..., d)."""
        logits = jnp.einsum("...d,dv->...v", x, params["head"])
        if self.cfg.logits_scaling != 1.0:
            logits = logits / self.cfg.logits_scaling
        return logits

    def ssm_layers(self) -> int:
        """Mamba layers (0 without them)."""
        if self.cfg.family != "hybrid":
            return 0
        g, m, _ = self._hybrid_dims()
        return g * m

    def ssm_state_bytes(self) -> int:
        """Bytes of one slot's recurrent state over every Mamba layer."""
        if not self.ssm_layers():
            return 0
        return self.ssm_layers() * mamba_mod.state_bytes(self.cfg)

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
            g, m, a = self._hybrid_dims()
            d = cfg.d_model

            def stack(key, n, init):
                keys = jax.random.split(key, g * n).reshape(g, n, 2)
                return jax.vmap(jax.vmap(lambda k: init(k, cfg)))(keys)
            mamba = stack(kB, m, mamba_mod.init_mamba2)
            kF1, kF2 = jax.random.split(kF)
            ones = lambda *s: jnp.ones(s, cfg.dtype)  # noqa: E731
            if cfg.layer_types:
                p = len(cfg.hybrid_period())
                params["blocks"] = HybridParams(
                    mamba=mamba, mamba_ln=ones(g, m, d),
                    attn=stack(kF1, a, attn_mod.init_attn),
                    attn_ln=ones(g, a, d),
                    mlp=stack(kF2, p, ffn_mod.init_mlp),
                    mlp_ln=ones(g, p, d))
            else:
                params["blocks"] = HybridParams(
                    mamba=mamba, mamba_ln=ones(g, m, d),
                    shared_ln=ones(d),
                    shared_attn=attn_mod.init_attn(kF1, cfg),
                    shared_ln2=ones(d),
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
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        return x

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

            def group(x, g):
                return self._run_period(
                    hp, g, x,
                    lambda j, mp, xn: mamba_mod.forward(mp, cfg, xn)[0],
                    lambda a, ap, xn: attn_mod.attention(ap, cfg, xn,
                                                         positions)), None
            fn = jax.checkpoint(group) if cfg.remat else group
            x, _ = jax.lax.scan(fn, x, jnp.arange(self._hybrid_dims()[0]))
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
        return self._head(params, x)

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
            kv = attn_mod.init_cache(cfg, batch, max_seq)
            kvstack = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(
                    l, (cfg.attention_layers(),) + l.shape), kv)
            return {"mamba": self.init_prefill_state(batch), "kv": kvstack}
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
        kvstack = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (cfg.attention_layers(),)
                                       + l.shape), pool)
        if cfg.family == "hybrid":
            return {"mamba": self.init_prefill_state(batch), "kv": kvstack}
        return kvstack

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
            g, m, _ = self._hybrid_dims()
            ms = mamba_mod.init_state(cfg, batch)
            return jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (g, m) + l.shape), ms)
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
            g, _, sites = self._hybrid_dims()
            # the dense KV cache (sites, b, max_seq, ...) as (G, sites a
            # period, ...) scanned beside the periods
            kv_g = jax.tree_util.tree_map(
                lambda a: a.reshape((g, sites) + a.shape[1:]), cache["kv"])

            def group(carry, gi):
                x, st, kv = carry

                def mamba_fn(j, mp, xn):
                    nonlocal st
                    y, nst = mamba_mod.forward(mp, cfg, xn, _at(st, gi, j),
                                               lengths=lengths)
                    st = _set(st, gi, j, nst)
                    return y

                def attn_fn(a, ap, xn):
                    nonlocal kv
                    if attend_cache:
                        y, nkv = attn_mod.attention_prefill_cached(
                            ap, cfg, xn, _at(kv, gi, a), start)
                    else:
                        y, nkv = attn_mod.attention_prefill(
                            ap, cfg, xn, _at(kv, gi, a), start, lengths)
                    kv = _set(kv, gi, a, nkv)
                    return y
                x = self._run_period(hp, gi, x, mamba_fn, attn_fn)
                return (x, st, kv), None
            (x, new_mst, new_kv), _ = jax.lax.scan(
                group, (x, cache["mamba"], kv_g), jnp.arange(g))
            new_kv = jax.tree_util.tree_map(
                lambda a: a.reshape((g * sites,) + a.shape[2:]), new_kv)
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
                        layer.attn, cfg, h_in, kv, start, lengths)
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
        return self._head(params, x_last), new_cache

    def prefill_paged(self, params, tokens, kv, bt_row, state, start,
                      lengths, *, first: bool):
        """Prefill one prompt chunk of ONE slot into paged KV pools.

        tokens: (1, s); kv: the engine's pooled KV leaves
        (:meth:`split_paged_cache`; None for ssm); bt_row: the slot's
        (max_blocks,) block-table row; state: batch-1 recurrent staging
        state (:meth:`init_prefill_state`; None for attention-only
        families, and zeros for a hybrid's fresh prompt); start: chunk
        offset (traced ok when ``not first``); lengths: (1,) real token
        count WITHIN this chunk.  Returns
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
            _, _, sites = self._hybrid_dims()
            if state is None:                 # a fresh prompt
                state = self.init_prefill_state(b)

            def group(carry, g):
                x, ck, cv, st = carry

                def mamba_fn(j, mp, xn):
                    nonlocal st
                    y, nst = mamba_mod.forward(mp, cfg, xn, _at(st, g, j),
                                               lengths=lengths)
                    st = _set(st, g, j, nst)
                    return y

                def attn_fn(a, ap, xn):
                    nonlocal ck, cv
                    y, ck, cv = attn_mod.paged_attention_prefill(
                        ap, cfg, xn, ck, cv, g * sites + a, bt_row, start,
                        first=first, lengths=lengths)
                    return y
                x = self._run_period(hp, g, x, mamba_fn, attn_fn)
                return (x, ck, cv, st), None
            (x, ck, cv, new_state), _ = jax.lax.scan(
                group, (x, kv.k, kv.v, state),
                jnp.arange(self._hybrid_dims()[0]))
        else:
            def body(carry, layer):
                x, ck, cv, li = carry
                with jax.named_scope("attention"):
                    h_in = rmsnorm(x, layer.ln1, cfg.norm_eps)
                    y, ck, cv = attn_mod.paged_attention_prefill(
                        layer.attn, cfg, h_in, ck, cv, li, bt_row, start,
                        first=first, lengths=lengths)
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
            logits = self._head(params, x_last)
        return logits, KVCache(ck, cv), new_state

    def gather_paged_view(self, cache, block_tables):
        """The per-slot logical (vk, vv) view of a paged cache's pools —
        gathered once per decode chunk (None for the ssm family, which has
        no KV).  See :func:`repro.models.attention.gather_paged_view`."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return None
        kv, _ = self.split_paged_cache(cache)
        vk, vv = attn_mod.gather_paged_view(kv.k, kv.v, block_tables)
        heads = vk.shape[:3] + (cfg.n_kv_heads, cfg.hd)
        return vk.reshape(heads), vv.reshape(heads)

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
            _, _, sites = self._hybrid_dims()
            # the KV caches and the Mamba state ride in the scan CARRY with
            # token- and layer-sized in-place updates, so neither is copied
            ck0, cv0 = cache["kv"].k, cache["kv"].v   # (sites, ...)
            # the view (when given) rides in the carry NEXT TO the pools —
            # attention reads/updates the view, the pool gets the mirrored
            # token write; without a view the carry keeps its dense shape
            kv0 = kv_view if kv_view is not None else ()

            def group(carry, gi):
                (x, ck, cv, st), view = carry[:4], carry[4:]

                def mamba_fn(j, mp, xn):
                    nonlocal st
                    y, nst = mamba_mod.decode_step(mp, cfg, xn,
                                                   _at(st, gi, j))
                    st = _set(st, gi, j, nst)
                    return y

                def attn_fn(a, ap, xn):
                    nonlocal ck, cv, view
                    li = gi * sites + a
                    if kv_view is not None:
                        y, ck, cv, vk, vv = (
                            attn_mod.paged_attention_decode_view(
                                ap, cfg, xn, ck, cv, view[0], view[1], li,
                                pos, block_tables))
                        view = (vk, vv)
                    elif block_tables is not None and paged_kernel:
                        y, ck, cv = attn_mod.paged_attention_decode_kernel(
                            ap, cfg, xn, ck, cv, li, pos, block_tables)
                    elif block_tables is not None:
                        y, ck, cv = attn_mod.paged_attention_decode_inplace(
                            ap, cfg, xn, ck, cv, li, pos, block_tables)
                    else:
                        y, ck, cv = attn_mod.attention_decode_inplace(
                            ap, cfg, xn, ck, cv, li, pos)
                    return y
                x = self._run_period(hp, gi, x, mamba_fn, attn_fn)
                return (x, ck, cv, st) + view, None
            out_carry, _ = jax.lax.scan(
                group, (x, ck0, cv0, cache["mamba"]) + tuple(kv0),
                jnp.arange(self._hybrid_dims()[0]))
            x, ck, cv, new_mst = out_carry[:4]
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
            logits = self._head(params, x[:, -1])
        if kv_view is not None:
            return logits, new_cache, new_view
        return logits, new_cache
