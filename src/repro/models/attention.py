"""GQA multi-head attention: train/prefill + cached decode, qk_norm, bias.

Sharding intent (enforced by sharding/rules.py): head dims are split over the
'model' mesh axis (TP); with few KV heads (GQA) the KV cache shards batch over
'data' and heads over 'model' up to n_kv_heads, falling back to sequence
sharding for decode (flash-decode style partial-attention + LSE merge is in
serve/decode.py)."""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention, paged_decode
from .common import ModelConfig, apply_rope, init_dense, rmsnorm, rope_freqs


class AttnParams(NamedTuple):
    wq: jax.Array          # (d, nh*hd)
    wk: jax.Array          # (d, nkv*hd)
    wv: jax.Array          # (d, nkv*hd)
    wo: jax.Array          # (nh*hd, d)
    bq: Optional[jax.Array]
    bk: Optional[jax.Array]
    bv: Optional[jax.Array]
    q_norm: Optional[jax.Array]   # (hd,) qk_norm scales
    k_norm: Optional[jax.Array]


def init_attn(key, cfg: ModelConfig) -> AttnParams:
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    zeros = lambda n: jnp.zeros((n,), cfg.dtype)  # noqa: E731
    return AttnParams(
        wq=init_dense(ks[0], d, nh * hd, cfg.dtype),
        wk=init_dense(ks[1], d, nkv * hd, cfg.dtype),
        wv=init_dense(ks[2], d, nkv * hd, cfg.dtype),
        wo=init_dense(ks[3], nh * hd, d, cfg.dtype),
        bq=zeros(nh * hd) if cfg.qkv_bias else None,
        bk=zeros(nkv * hd) if cfg.qkv_bias else None,
        bv=zeros(nkv * hd) if cfg.qkv_bias else None,
        q_norm=jnp.ones((hd,), cfg.dtype) if cfg.qk_norm else None,
        k_norm=jnp.ones((hd,), cfg.dtype) if cfg.qk_norm else None,
    )


def _project_qkv(p: AttnParams, cfg: ModelConfig, x, positions):
    """x: (b, s, d) -> q (b, s, nh, hd), k/v (b, s, nkv, hd), roped."""
    b, s, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p.wq)
    k = jnp.einsum("bsd,dh->bsh", x, p.wk)
    v = jnp.einsum("bsd,dh->bsh", x, p.wv)
    if p.bq is not None:
        q, k, v = q + p.bq, k + p.bk, v + p.bv
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    if p.q_norm is not None:
        q = rmsnorm(q, p.q_norm, cfg.norm_eps)
        k = rmsnorm(k, p.k_norm, cfg.norm_eps)
    if cfg.rope:
        cos, sin = rope_freqs(hd, cfg.rope_theta, positions)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _scaled(scores, cfg: ModelConfig):
    """Attention scores times the configured scale (divided by
    sqrt(head_dim) where none is configured)."""
    mult = getattr(cfg, "attention_multiplier", None)
    if mult is None:
        return scores / jnp.sqrt(float(cfg.hd))
    return scores * mult


NEG_INF = -1e30

# sequences above this use the chunked online-softmax path (flash-equivalent
# memory behaviour in pure XLA: no S x S score tensor is ever materialised)
CHUNKED_THRESHOLD = 1024
KV_CHUNK = 1024


def chunked_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                      kv_chunk: int = KV_CHUNK, scale=None):
    """Online-softmax attention over KV chunks (lax.scan) — the XLA-lowerable
    flash attention used for training/prefill roofline paths; the Pallas
    kernel (kernels/flash_attention.py) is the TPU in-kernel version of the
    same recurrence.

    q: (b, sq, nh, hd); k/v: (b, sk, nkv, hd); GQA via nh % nkv == 0;
    ``scale`` of the scores, 1/sqrt(hd) by default.
    """
    b, sq, nh, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    group = nh // nkv
    if scale is None:
        scale = 1.0 / jnp.sqrt(float(hd))
    # keep q/k/v in their storage dtype; accumulate dots in f32 on the MXU
    # (f32-converting the inputs materialises f32 copies of the whole k/v)
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, sq, nkv, group, hd)
    n_chunks = max(sk // kv_chunk, 1)
    kc = k.reshape(b, n_chunks, kv_chunk, nkv, hd)
    vc = v.reshape(b, n_chunks, kv_chunk, nkv, hd)
    qpos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        acc, m_i, l_i = carry
        j, k_j, v_j = inp                    # (b, kv_chunk, nkv, hd)
        s = jnp.einsum("bsngh,btnh->bsngt", qg, k_j,
                       preferred_element_type=jnp.float32)
        if causal:
            kpos = j * kv_chunk + jnp.arange(kv_chunk)
            mask = kpos[None, :] <= qpos[:, None]       # (sq, kv_chunk)
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p_ = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p_, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bsngt,btnh->bsngh", p_.astype(v_j.dtype), v_j,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    # carry inits derived from qg so the scan carry INHERITS q's sharding —
    # plain jnp.zeros is replicated and makes GSPMD unshard the whole chain
    # (measured: full-batch attention intermediates per partition; see
    # EXPERIMENTS.md section Perf, dbrx iteration 1)
    acc0 = (qg * 0.0).astype(jnp.float32)
    m0 = jnp.max(acc0, axis=-1) + NEG_INF
    l0 = jnp.max(acc0, axis=-1)
    idx = jnp.arange(n_chunks)
    (acc, m_i, l_i), _ = jax.lax.scan(
        step, (acc0, m0, l0),
        (idx, kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4)))
    l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
    out = (acc / l_safe[..., None]).reshape(b, sq, nh, hd)
    return out


def _chunked(s: int) -> bool:
    """Whether the jnp path over ``s`` positions is :func:`chunked_attention`:
    the sequence exceeds ``CHUNKED_THRESHOLD`` and splits into ``KV_CHUNK``
    chunks."""
    return s > CHUNKED_THRESHOLD and s % KV_CHUNK == 0


def _attend_jnp(cfg: ModelConfig, q, k, v):
    """Causal attention in jnp: :func:`chunked_attention` where
    :func:`_chunked`, else dense float32 scores.  Returns float32
    (b, s, nh, hd)."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    if _chunked(s):
        return chunked_attention(q, k, v, causal=True,
                                 scale=cfg.attention_multiplier)
    group = nh // nkv
    qg = q.reshape(b, s, nkv, group, hd)
    scores = _scaled(jnp.einsum("bsngh,btnh->bngst",
                                qg.astype(jnp.float32),
                                k.astype(jnp.float32)), cfg)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bngst,btnh->bsngh", probs,
                      v.astype(jnp.float32)).reshape(b, s, nh, hd)


# platforms whose lowering runs prefill attention through the Pallas flash
# kernel (kernels/flash_attention.py); elsewhere the jnp paths run
FLASH_PLATFORMS = ("tpu",)


def _flash_blocks(cfg: ModelConfig, s: int):
    return flash_attention.block_sizes(s, s, cfg.n_heads // cfg.n_kv_heads)


def _on_one_device(x) -> bool:
    """Whether ``x`` is known to live on one device: neither it nor the
    context names a mesh of several.  A Mosaic kernel is not partitioned
    over a mesh, so a program sharded over one keeps the jnp paths."""
    sharding = getattr(getattr(x, "aval", None), "sharding", None)
    meshes = (getattr(sharding, "mesh", None),
              jax.sharding.get_abstract_mesh())
    return all(m is None or m.size <= 1 for m in meshes)


def prefill_attention(cfg: ModelConfig, s: int, platform: str,
                      n_devices: int = 1) -> str:
    """How :func:`attend` runs over ``s`` positions of a program on
    ``n_devices`` devices of ``platform``: ``"flash_kernel"`` (the Pallas
    kernel), ``"chunked"`` (:func:`chunked_attention`) or ``"dense"``
    (float32 scores)."""
    if (platform in FLASH_PLATFORMS and n_devices == 1
            and _flash_blocks(cfg, s) is not None):
        return "flash_kernel"
    return "chunked" if _chunked(s) else "dense"


def _flash(cfg: ModelConfig, dtype, jnp_path):
    """The kernel path of :func:`attend`; its gradient is ``jnp_path``'s,
    so a training step through it differentiates."""
    @jax.custom_vjp
    def run(q, k, v, lengths):
        b, s, nh, hd = q.shape
        nkv = k.shape[2]

        def heads(t):
            return t.transpose(0, 2, 1, 3).reshape(-1, s, hd)
        out = flash_attention.flash_attention(
            heads(q), heads(k), heads(v), jnp.repeat(lengths, nkv),
            scale=cfg.attention_multiplier)
        return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3).astype(dtype)

    def fwd(q, k, v, lengths):
        return run(q, k, v, lengths), (q, k, v)

    def bwd(res, g):
        return (*jax.vjp(jnp_path, *res)[1](g), None)

    run.defvjp(fwd, bwd)
    return run


def attend(cfg: ModelConfig, q, k, v, lengths=None, *, dtype):
    """Causal self-attention of roped q (b, s, nh, hd) over k/v
    (b, s, nkv, hd); ``lengths`` (b,): each row's real tokens (all ``s``
    by default).  Returns (b, s, nh, hd) in ``dtype``.

    Where the program is lowered for a TPU (:data:`FLASH_PLATFORMS`), is
    not sharded over a mesh, and ``s`` tiles by the kernel's blocks, the
    Pallas flash kernel runs: bf16 on the MXU, float32 statistics, no work
    above the diagonal or past ``lengths``.  Elsewhere the jnp paths run
    (:func:`_attend_jnp`), which ignore ``lengths``: rows past a length
    are padding either way.  :func:`prefill_attention` names the path."""
    b, s = q.shape[:2]

    def jnp_path(q, k, v, lengths=None):
        return _attend_jnp(cfg, q, k, v).astype(dtype)

    if _flash_blocks(cfg, s) is None or not _on_one_device(q):
        return jnp_path(q, k, v)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    kernel = _flash(cfg, dtype, jnp_path)
    return jax.lax.platform_dependent(
        q, k, v, jnp.asarray(lengths, jnp.int32), default=jnp_path,
        **{p: kernel for p in FLASH_PLATFORMS})


def _project_out(p: AttnParams, out):
    """(b, s, nh, hd) attention output -> (b, s, d) through ``wo``."""
    b, s = out.shape[:2]
    return jnp.einsum("bsh,hd->bsd", out.reshape(b, s, -1), p.wo)


def attention(p: AttnParams, cfg: ModelConfig, x, positions, lengths=None):
    """Full self-attention over x (training / prefill without cache)."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    return _project_out(p, attend(cfg, q, k, v, lengths, dtype=x.dtype))


class KVCache(NamedTuple):
    k: jax.Array  # (b, max_seq, nkv, hd)
    v: jax.Array
    # position is tracked by the caller (same for the whole batch slice)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> KVCache:
    shape = (batch, max_seq, cfg.n_kv_heads, cfg.hd)
    return KVCache(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


def attention_prefill(p: AttnParams, cfg: ModelConfig, x, cache: KVCache,
                      start: int = 0, lengths=None):
    """Prefill: run full attention AND fill the cache at [start, start+s);
    ``lengths`` (b,): real tokens of each row within x."""
    b, s, _ = x.shape
    positions = start + jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    new_cache = KVCache(
        jax.lax.dynamic_update_slice(cache.k, k, (0, start, 0, 0)),
        jax.lax.dynamic_update_slice(cache.v, v, (0, start, 0, 0)))
    out = attend(cfg, q, k, v, lengths, dtype=x.dtype)
    return _project_out(p, out), new_cache


def _attend_token(cfg: ModelConfig, q, k_l, v_l, pos, per_slot: bool,
                  x_dtype, wo):
    """The one-token masked-attention tail shared by every decode variant
    (dense, paged, paged-view): q (b, 1, nh, hd) against k_l/v_l
    (b, t, nkv, hd), valid where ``kpos <= pos`` — operation-for-operation
    identical across callers, which is what makes 'paged decode is bitwise
    the dense computation' a property of ONE code path."""
    b = q.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    group = nh // nkv
    qg = q.reshape(b, nkv, group, hd)
    scores = _scaled(jnp.einsum("bngh,btnh->bngt", qg, k_l,
                                preferred_element_type=jnp.float32), cfg)
    t = k_l.shape[1]
    kpos = jnp.arange(t)[None, None, None, :]
    valid = kpos <= (pos[:, None, None, None] if per_slot else pos)
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngt,btnh->bngh", probs.astype(v_l.dtype), v_l,
                     preferred_element_type=jnp.float32)
    out = out.astype(x_dtype).reshape(b, 1, nh * hd)
    return jnp.einsum("bsh,hd->bsd", out, wo)


def _page_slots(pos, bt, block_size: int, n_blocks: int):
    """(page, offset) for per-slot positions against per-slot table rows
    (bt: (b, max_blocks)); sentinel/overflow map to page ``n_blocks`` —
    one past the pool, so scatters through them drop."""
    b, mb = bt.shape
    idx = pos // block_size
    safe = jnp.clip(idx, 0, mb - 1)
    page = jnp.where(idx < mb, bt[jnp.arange(b), safe], n_blocks)
    return page, pos % block_size


def attention_decode_inplace(p: AttnParams, cfg: ModelConfig, x, ck, cv,
                             li, pos):
    """One-token decode against LAYER-STACKED caches carried through the
    layer scan: the cache update is a single token-sized dynamic-update-slice
    on the stacked buffer (aliased in-place by XLA), instead of re-writing
    the whole layer cache through scan outputs — 60 GB/token -> ~100 KB/token
    of cache-write traffic at 500k context (EXPERIMENTS.md Perf, zamba2).

    ck/cv: (L, b, max_seq, nkv, hd); li: layer index; returns (out, ck, cv).

    ``pos`` is a scalar (lock-step batch, every slot at the same position)
    or a (b,) vector (continuous batching: each slot decodes at its own
    position).  The vector path writes the token via a per-slot scatter
    (mode='drop': a slot whose position has run past max_seq writes nothing
    instead of corrupting a neighbour) and masks attention per slot.
    """
    b, _, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    positions = pos[:, None] if per_slot else pos + jnp.zeros((b, 1),
                                                              jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)
    if per_slot:
        slots = jnp.arange(b)
        ck = ck.at[li, slots, pos].set(k[:, 0].astype(ck.dtype), mode="drop")
        cv = cv.at[li, slots, pos].set(v[:, 0].astype(cv.dtype), mode="drop")
    else:
        zero = jnp.zeros((), jnp.int32)
        ck = jax.lax.dynamic_update_slice(ck, k[None].astype(ck.dtype),
                                          (li, zero, pos, zero, zero))
        cv = jax.lax.dynamic_update_slice(cv, v[None].astype(cv.dtype),
                                          (li, zero, pos, zero, zero))
    k_l = jax.lax.dynamic_index_in_dim(ck, li, axis=0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cv, li, axis=0, keepdims=False)
    return _attend_token(cfg, q, k_l, v_l, pos, per_slot, x.dtype,
                         p.wo), ck, cv


# ---------------------------------------------------------------------------
# paged KV: page-mapped variants of prefill/decode (serve.paged owns the
# host-side block pool; the sentinel convention is shared: table entries
# >= n_blocks mean "no page", writes through them drop, reads are masked)
# ---------------------------------------------------------------------------


def pool_rows(cfg: ModelConfig) -> Tuple[int, int]:
    """(rows, lanes) of one position's K (or V) in a page.  Heads narrower
    than a 128-lane vector register are packed ``128 // hd`` to a row
    (kv head ``n`` in row ``n // (128 // hd)``), so the pool keeps the TPU's
    lane-dense layout and the paged kernel reads whole rows; other widths
    keep one head a row, ``(nkv, hd)``."""
    nkv, hd = cfg.n_kv_heads, cfg.hd
    per = 128 // hd if hd < 128 and 128 % hd == 0 else 1
    if per > 1 and nkv % per == 0:
        return nkv // per, hd * per
    return nkv, hd


def _to_rows(cfg: ModelConfig, x):
    """(..., nkv, hd) -> the pool's (..., rows, lanes)."""
    return x.reshape(x.shape[:-2] + pool_rows(cfg))


def _to_heads(cfg: ModelConfig, x):
    """The pool's (..., rows, lanes) -> (..., nkv, hd)."""
    return x.reshape(x.shape[:-2] + (cfg.n_kv_heads, cfg.hd))


def init_paged_kv(cfg: ModelConfig, n_blocks: int, block_size: int
                  ) -> KVCache:
    """A paged KV pool: ``(n_blocks, block_size, rows, lanes)`` pages,
    :func:`pool_rows` of each position."""
    shape = (n_blocks, block_size) + pool_rows(cfg)
    return KVCache(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


def _pages_for_positions(pos, bt_row, block_size: int, n_blocks: int):
    """(page, offset) for a vector of positions against ONE table row.

    Out-of-table positions and sentinel entries both map to page
    ``n_blocks`` — one past the pool — so ``.at[...].set(mode='drop')``
    discards the write, exactly like the dense cache drops writes past
    ``max_seq``."""
    pos = jnp.asarray(pos, jnp.int32)
    max_blocks = bt_row.shape[0]
    idx = pos // block_size
    safe = jnp.clip(idx, 0, max_blocks - 1)
    page = jnp.where(idx < max_blocks, bt_row[safe], n_blocks)
    return page, pos % block_size


def _gather_pages(pool, bt):
    """Gather a per-slot logical view from a page pool.

    pool: (n_blocks, block_size, rows, lanes); bt: (b, max_blocks) int32.
    Returns (b, max_blocks * block_size, rows, lanes).  Sentinel entries clip
    to an arbitrary real page — their positions are beyond every valid
    ``kpos <= pos`` mask, so the values are never attended; clipping keeps
    the gather maskless on the hot path."""
    nb = pool.shape[0]
    g = pool[jnp.clip(bt, 0, nb - 1)]          # (b, mb, bs, nkv, hd)
    return g.reshape(bt.shape[0], -1, *pool.shape[2:])


def _masked_attend(cfg: ModelConfig, q, k_all, v_all, qpos, kpos):
    """f32 masked attention of q (b, sq, nh, hd) against a gathered KV view
    (b, t, nkv, hd); valid where kpos (b|1, t) <= qpos (b, sq).  The same
    einsum/softmax discipline as :func:`attention`'s unchunked path, so a
    paged/cached prefill stays numerically in-family with the dense one."""
    b, sq, nh, hd = q.shape
    nkv = k_all.shape[2]
    group = nh // nkv
    qg = q.reshape(b, sq, nkv, group, hd)
    scores = _scaled(jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32),
                                k_all.astype(jnp.float32)), cfg)
    valid = kpos[:, None, None, None, :] <= qpos[:, None, None, :, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v_all.astype(jnp.float32))
    return out.reshape(b, sq, nh, hd)


def attention_prefill_cached(p: AttnParams, cfg: ModelConfig, x,
                             cache: KVCache, start):
    """Continuation prefill for a CHUNKED prompt against a dense cache:
    write this chunk's k/v at [start, start+s) and attend q against the
    whole cache masked by ``kpos <= qpos`` — earlier chunks' positions are
    already cached, so a prompt split across chunk boundaries sees exactly
    the attention a single-call prefill would.  ``start`` may be traced
    (one executable serves every chunk offset)."""
    b, s, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    # scatter (mode='drop'), not dynamic_update_slice: a tail chunk whose
    # padded bucket overruns max_seq must DROP the out-of-range rows —
    # dynamic_update_slice would clamp the start and silently clobber
    # earlier positions (the same discipline as the paged sentinel)
    new_cache = KVCache(
        cache.k.at[:, positions[0]].set(k.astype(cache.k.dtype),
                                        mode="drop"),
        cache.v.at[:, positions[0]].set(v.astype(cache.v.dtype),
                                        mode="drop"))
    kpos = jnp.arange(new_cache.k.shape[1])[None, :]
    out = _masked_attend(cfg, q, new_cache.k, new_cache.v, positions, kpos)
    out = out.astype(x.dtype).reshape(b, s, -1)
    return jnp.einsum("bsh,hd->bsd", out, p.wo), new_cache


def paged_attention_prefill(p: AttnParams, cfg: ModelConfig, x, ck, cv, li,
                            bt_row, start, *, first: bool, lengths=None):
    """Prefill one prompt chunk into LAYER-STACKED page pools.

    x: (1, s, d); ck/cv: (L, n_blocks, block_size, rows, lanes); bt_row: the
    slot's (max_blocks,) block-table row; start: chunk offset (traced ok).
    k/v are scattered page-by-page (writes through sentinel/overflow
    entries drop — ``mode='drop'``, the dense out-of-range discipline).

    ``first`` (static) selects the attention path: the first chunk attends
    within x exactly like the dense :func:`attention_prefill` (bitwise the
    same computation, which is what keeps a paged engine token-identical to
    the dense oracle for prompts that fit one chunk); continuation chunks
    gather the slot's pages and attend masked by ``kpos <= qpos``.
    ``lengths`` (1,): the chunk's real tokens, which the first chunk's
    kernel path (:func:`attend`) reads to skip the padding."""
    b, s, _ = x.shape
    nb, bs = ck.shape[1], ck.shape[2]
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    page, off = _pages_for_positions(positions[0], bt_row, bs, nb)
    ck = ck.at[li, page, off].set(_to_rows(cfg, k[0]).astype(ck.dtype),
                                  mode="drop")
    cv = cv.at[li, page, off].set(_to_rows(cfg, v[0]).astype(cv.dtype),
                                  mode="drop")
    if first:
        out = attend(cfg, q, k, v, lengths, dtype=x.dtype)
        return _project_out(p, out), ck, cv
    k_l = jax.lax.dynamic_index_in_dim(ck, li, axis=0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cv, li, axis=0, keepdims=False)
    k_all = _to_heads(cfg, _gather_pages(k_l, bt_row[None]))
    v_all = _to_heads(cfg, _gather_pages(v_l, bt_row[None]))
    kpos = jnp.arange(k_all.shape[1])[None, :]
    out = _masked_attend(cfg, q, k_all, v_all, positions, kpos)
    out = out.astype(x.dtype).reshape(b, s, -1)
    return jnp.einsum("bsh,hd->bsd", out, p.wo), ck, cv


def _paged_write_token(p: AttnParams, cfg: ModelConfig, x, ck, cv, li, pos,
                       bt):
    """Project one token per slot and scatter its k/v into layer ``li`` of
    the page pools through the block table (dropping on a sentinel or
    overflow entry: a lane parked at ``max_seq`` writes nothing).
    Returns ``(pos, q, k, v, ck, cv)`` with ``pos`` broadcast to (b,)."""
    b = x.shape[0]
    nb, bs = ck.shape[1], ck.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    q, k, v = _project_qkv(p, cfg, x, pos[:, None])
    page, off = _page_slots(pos, bt, bs, nb)
    ck = ck.at[li, page, off].set(_to_rows(cfg, k[:, 0]).astype(ck.dtype),
                                  mode="drop")
    cv = cv.at[li, page, off].set(_to_rows(cfg, v[:, 0]).astype(cv.dtype),
                                  mode="drop")
    return pos, q, k, v, ck, cv


def paged_attention_decode_inplace(p: AttnParams, cfg: ModelConfig, x, ck,
                                   cv, li, pos, bt):
    """One-token decode against layer-stacked page pools — the paged twin
    of :func:`attention_decode_inplace`'s per-slot path.

    ck/cv: (L, n_blocks, block_size, rows, lanes); pos: (b,) per-slot
    positions; bt: (b, max_blocks) block tables.  The new token is written
    through the table (drop on sentinel/overflow — a retired or
    mid-prefill lane whose position was parked at ``max_seq`` writes
    nothing); the read gathers the slot's pages into a
    ``(b, max_blocks * block_size, nkv, hd)`` view and runs the *identical*
    masked-attention math as the dense path, so paged decode is bitwise
    the dense computation whenever ``max_blocks * block_size == max_seq``.
    """
    pos, q, _, _, ck, cv = _paged_write_token(p, cfg, x, ck, cv, li, pos, bt)
    k_l = jax.lax.dynamic_index_in_dim(ck, li, axis=0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cv, li, axis=0, keepdims=False)
    k_all = _to_heads(cfg, _gather_pages(k_l, bt))
    v_all = _to_heads(cfg, _gather_pages(v_l, bt))
    return _attend_token(cfg, q, k_all, v_all, pos, True, x.dtype,
                         p.wo), ck, cv


def gather_paged_view(ck, cv, bt) -> Tuple[jax.Array, jax.Array]:
    """Materialise the per-slot logical view of layer-stacked page pools.

    ck/cv: (L, n_blocks, block_size, rows, lanes); bt: (b, max_blocks).
    Returns (L, b, max_blocks * block_size, rows, lanes) pairs, each
    slot's pages in logical order — with one head a row, shaped exactly
    like the dense layer-stacked cache (:meth:`Model.gather_paged_view`
    unpacks packed rows to heads).  The decode chunk gathers this ONCE per
    chunk and updates it incrementally per token
    (:func:`paged_attention_decode_view`) instead of re-gathering every
    step/layer — the page indirection is paid per chunk, not per token."""
    nb = ck.shape[1]
    safe = jnp.clip(bt, 0, nb - 1)
    L = ck.shape[0]
    vk = ck[:, safe].reshape(L, bt.shape[0], -1, *ck.shape[3:])
    vv = cv[:, safe].reshape(L, bt.shape[0], -1, *cv.shape[3:])
    return vk, vv


def paged_attention_decode_view(p: AttnParams, cfg: ModelConfig, x, ck, cv,
                                vk, vv, li, pos, bt):
    """One-token decode against a pre-gathered per-slot view.

    The attention + view update are operation-for-operation the dense
    :func:`attention_decode_inplace` per-slot path on (vk, vv) — bitwise
    the dense computation — and the new token is ALSO scattered into the
    page pool (ck, cv) through the block table, so the pool stays the
    source of truth across chunk boundaries.  Writes drop both ways for a
    parked lane (pos past the view / sentinel page)."""
    pos, q, k, v, ck, cv = _paged_write_token(p, cfg, x, ck, cv, li, pos, bt)
    slots = jnp.arange(x.shape[0])
    vk = vk.at[li, slots, pos].set(k[:, 0].astype(vk.dtype), mode="drop")
    vv = vv.at[li, slots, pos].set(v[:, 0].astype(vv.dtype), mode="drop")
    k_l = jax.lax.dynamic_index_in_dim(vk, li, axis=0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(vv, li, axis=0, keepdims=False)
    return _attend_token(cfg, q, k_l, v_l, pos, True, x.dtype,
                         p.wo), ck, cv, vk, vv


def paged_kernel_engages(pool) -> bool:
    """Whether decode attention over the layer-stacked page ``pool`` runs
    the Pallas paged kernel (:func:`paged_attention_decode_kernel`): the
    pool is an array (or a shape with a sharding) on exactly one TPU
    device, in a layout the kernel reads.  Elsewhere — the CPU, or a pool
    sharded over a mesh, where a ``pallas_call`` is not partitioned — the
    decode chunk gathers the per-slot view (:func:`gather_paged_view`)."""
    sharding = getattr(pool, "sharding", None)     # None on a tracer
    if sharding is None:
        return False
    devices = sharding.device_set
    return (len(devices) == 1 and next(iter(devices)).platform == "tpu"
            and paged_decode.supports(pool.shape, pool.dtype))


def paged_attention_decode_kernel(p: AttnParams, cfg: ModelConfig, x, ck,
                                  cv, li, pos, bt):
    """One-token decode against layer-stacked page pools through the Pallas
    paged kernel: each slot reads only its valid pages, straight from the
    pool, and no view is gathered.

    ck/cv: (L, n_blocks, block_size, rows, lanes); pos: (b,) per-slot
    positions; bt: (b, max_blocks).  The new token is scattered into the
    pool through the table as :func:`paged_attention_decode_view` does
    (dropping for a parked lane), then slot ``i`` attends positions
    ``[0, pos[i]]``.  A parked lane (position at the table's capacity:
    free, retired or mid-prefill) attends one page; its logits are
    discarded by the engine but stay finite.  The same math as
    :func:`_attend_token` (float32 scores and softmax, PV in the pool's
    dtype), accumulated blockwise, so it matches the view path to bf16
    rounding, not bitwise."""
    pos, q, _, _, ck, cv = _paged_write_token(p, cfg, x, ck, cv, li, pos, bt)
    lengths = jnp.where(pos < bt.shape[1] * ck.shape[2], pos + 1, 1)
    out = paged_decode.paged_decode_attention(
        q[:, 0].astype(ck.dtype), ck, cv, li, lengths, bt,
        scale=cfg.attention_multiplier)
    out = out.astype(x.dtype).reshape(x.shape[0], 1, -1)
    return jnp.einsum("bsh,hd->bsd", out, p.wo), ck, cv


def attention_decode(p: AttnParams, cfg: ModelConfig, x, cache: KVCache,
                     pos):
    """One-token decode: x (b, 1, d); attends to cache[:pos+1]."""
    b, _, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    positions = pos + jnp.zeros((b, 1), jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)
    ck = jax.lax.dynamic_update_slice(cache.k, k, (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v, (0, pos, 0, 0))
    new_cache = KVCache(ck, cv)
    group = nh // nkv
    qg = q.reshape(b, nkv, group, hd)  # s=1 squeezed
    # bf16-in / f32-accumulate einsums: converting the whole cache to f32
    # materialised seq_len x hd x f32 copies per step (EXPERIMENTS.md Perf,
    # zamba2 iteration 1); preferred_element_type keeps accuracy on the MXU.
    scores = _scaled(jnp.einsum("bngh,btnh->bngt", qg, ck,
                                preferred_element_type=jnp.float32), cfg)
    t = ck.shape[1]
    valid = jnp.arange(t)[None, None, None, :] <= pos
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngt,btnh->bngh", probs.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(b, 1, nh * hd)
    return jnp.einsum("bsh,hd->bsd", out, p.wo), new_cache
