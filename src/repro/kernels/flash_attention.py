"""Hand-written Pallas flash attention (online softmax), causal + GQA.

Layout: q (BH, Sq, D), k/v (BKV, Sk, D) with BH % BKV == 0 (GQA group =
BH // BKV).  Grid (BH, Sq/bq); each step owns one (bq, D) query block and
loops over (bk, D) key/value chunks of the VMEM-resident kv block for its
kv-head, maintaining running max / normaliser / accumulator in VREGs — the
standard online-softmax recurrence, expressed with a ``reduceSeq`` over a
triple accumulator in DPIA vocabulary (DESIGN.md section 5).

Causal masking compares absolute positions, with ``q_offset`` allowing the
query block to live anywhere in the kv sequence (prefill continuation).
Validated against ref.flash_attention in interpret mode.

``interpret`` defaults to None = auto: interpret mode only on CPU hosts
(where there is no Mosaic compiler), native compilation on real
accelerators.  Pass an explicit bool to override (tests pin it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compiler.options import default_interpret

NEG_INF = -1e30
# Every grid step holds its kv-head's whole K and V sequence in VMEM, double
# buffered, inside Mosaic's default 16 MiB scoped limit (the rest is left
# for the q/o blocks and the score tiles).  A KV-tiled grid lifts this.
KV_VMEM_BYTES = 12 * 2 ** 20


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, sk: int, scale: float,
               causal: bool, q_offset: int, bq: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    d = q.shape[-1]
    n_k = sk // bk

    def body(j, carry):
        acc, m_i, l_i = carry
        kj = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)   # (bk, d)
        vj = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jnp.dot(q, kj.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jnp.dot(
            p, vj, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)

    if causal:
        # skip kv chunks strictly above the causal frontier of this q block
        hi_pos = q_offset + (qi + 1) * bq - 1
        n_live = jnp.minimum((hi_pos // bk) + 1, n_k)
    else:
        n_live = n_k
    acc, m_i, l_i = jax.lax.fori_loop(0, n_live, body, (acc0, m0, l0))
    l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "bq", "bk", "interpret", "q_offset", "scale"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()   # True only on CPU platforms
    bh, sq, d = q.shape
    bkv, sk, dv = k.shape
    assert bh % bkv == 0 and dv == d
    group = bh // bkv
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    kv_bytes = 2 * 2 * sk * d * jnp.dtype(k.dtype).itemsize
    if not interpret and kv_bytes > KV_VMEM_BYTES:
        raise ValueError(
            f"flash_attention: K and V blocks of {sk} x {d} "
            f"{jnp.dtype(k.dtype).name} need {kv_bytes} bytes of VMEM "
            f"double-buffered, over the {KV_VMEM_BYTES}-byte budget; the "
            f"kernel keeps a kv-head's whole sequence in VMEM")
    scale_val = float(scale) if scale is not None else float(1.0 / (d ** 0.5))

    kernel = functools.partial(
        _fa_kernel, bk=bk, sk=sk, scale=scale_val, causal=causal,
        q_offset=q_offset, bq=bq)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i, g=group: (h // g, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i, g=group: (h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
