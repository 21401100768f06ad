"""Hand-written Pallas flash attention (online softmax), causal + GQA,
tiled over the key/value sequence.

Layout: q (BH, Sq, D), k/v (BKV, Sk, D) with BH % BKV == 0: the ``group =
BH // BKV`` query heads ``[h * group, (h + 1) * group)`` attend kv head
``h``.  The grid is (kv head, q block, kv block).  A step multiplies the
``(group * bq, D)`` queries of one q block — every head of the group
stacked, so each K and V tile is read once per q block; the queries are
scaled once a block and kept in their own dtype, as ``chunked_attention``
keeps them — against one
``(bk, D)`` key tile on the MXU in the inputs' dtype with float32
accumulation, and folds the scores into a running max, normaliser and
accumulator kept in VMEM scratch across the kv axis (``arbitrary``): the
online-softmax recurrence of ``models.attention.chunked_attention``, with
statistics in float32 and probabilities cast to V's dtype for the PV
product.  The output block is written once, after the q block's last kv
step.

Work that cannot contribute is skipped, fetch and all: a kv tile wholly
above the causal frontier of the q block, or wholly at or past the row's
``lengths`` entry, maps to the last tile the block reads (the pipeline does
not copy a block it already holds) and runs no step; a q block wholly past
its length computes nothing and writes zeros.  ``lengths`` (one entry per
kv head row, scalar-prefetched) is the number of valid key positions;
query rows at or past it are padding whose outputs are finite and unused.
Only tiles that cross the diagonal or the length compute a mask.

``q_offset`` places the queries in the key sequence (query ``i`` sits at
position ``q_offset + i``), for prefill continuation.  Validated against
``ref.flash_attention`` in interpret mode (``tests/test_kernels.py``) and
on the chip (``chip_smoke.py``).

``interpret`` defaults to None: the Mosaic kernel where the program is
lowered for a TPU (also from a CPU host, for a described chip), interpret
mode elsewhere.  Pass an explicit bool to override (tests pin it).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128          # m and l are kept lane-replicated, (rows, LANES)
# Default tiles, sized so a step's scores and tiles fit the scoped VMEM of
# _vmem_limit; chip_smoke.py times them against other tiles at yi-9b's
# (head_dim 128, GQA 8:1) and granite-4.0-h's (64, 4:1) prefill shapes.
# A q block stacks about BLOCK_ROWS query rows across the group's heads.
BLOCK_ROWS = 1024
BLOCK_K = 512


def block_sizes(sq: int, sk: int, group: int) -> Optional[Tuple[int, int]]:
    """The default ``(bq, bk)`` for ``sq`` queries of ``group`` heads
    against ``sk`` keys, or None where the sequences do not tile: ``bq``
    a multiple of 16 (a bfloat16 sublane tile, so stacking the group's
    heads is free) dividing ``sq``, ``bk`` a multiple of 128 lanes dividing
    ``sk``."""
    bq = min(max(BLOCK_ROWS // group, 16), sq)
    bk = min(BLOCK_K, sk)
    if sq % bq or sk % bk or bq % 16 or bk % LANES:
        return None
    return bq, bk


def _lanes(x, n: int):
    """A lane-replicated (rows, LANES) value as (rows, n)."""
    if n == LANES:
        return x
    if n < LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // LANES))


def _last_kv_block(i, length, *, bq: int, bk: int, q_offset: int,
                   causal: bool):
    """Index of the last kv tile q block ``i`` reads; -1 where the block
    lies wholly at or past ``length``."""
    q_first = q_offset + i * bq
    last = (length - 1) // bk
    if causal:
        last = jnp.minimum(last, (q_first + bq - 1) // bk)
    return jnp.where(q_first < length, last, -1)


def _fa_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, q_scr, m_ref, l_ref,
               acc_ref, *, scale: float, causal: bool, q_offset: int, bq: int,
               bk: int):
    h, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    group, _, d = q_ref.shape
    rows = group * bq
    length = len_ref[h]
    last = _last_kv_block(i, length, bq=bq, bk=bk, q_offset=q_offset,
                          causal=causal)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        # the q block, scaled once and kept in q's dtype (as
        # chunked_attention keeps it), for every kv step of the block
        q_scr[...] = (q_ref[...].reshape(rows, d).astype(jnp.float32)
                      * scale).astype(q_scr.dtype)

    def step(masked: bool):
        s = jax.lax.dot_general(q_scr[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            qpos = q_offset + i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = kpos < length
            if causal:
                ok = jnp.logical_and(ok, kpos <= qpos)
            s = jnp.where(ok[None], s.reshape(group, bq, bk),
                          NEG_INF).reshape(rows, bk)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, bk))
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = _lanes(alpha, d) * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    live = j <= last
    edge = j * bk + bk > length
    if causal:
        edge = jnp.logical_or(edge, j * bk + bk - 1 > q_offset + i * bq)

    @pl.when(jnp.logical_and(live, jnp.logical_not(edge)))
    def _():
        step(masked=False)

    @pl.when(jnp.logical_and(live, edge))
    def _():
        step(masked=True)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l_i = l_ref[...]
        l_i = jnp.where(l_i == 0.0, 1.0, l_i)
        o = acc_ref[...] / _lanes(l_i, d)
        o_ref[...] = o.reshape(group, bq, d).astype(o_ref.dtype)


def _vmem_limit(rows: int, bk: int, d: int, itemsize: int) -> int:
    """Scoped VMEM for one step: double-buffered q, k, v and output tiles,
    the scratch, and the float32 scores and probabilities, with room to
    spare; at least Mosaic's default."""
    tiles = 2 * itemsize * (2 * rows * d + 2 * bk * d)
    scratch = 4 * rows * (2 * LANES + d) + itemsize * rows * d
    scores = rows * bk * (4 + 4 + itemsize)
    return int(min(100 * 2 ** 20, max(32 * 2 ** 20,
                                      2 * (tiles + scratch + scores))))


def _call(q, k, v, lengths, *, causal: bool, scale: float, q_offset: int,
          bq: int, bk: int, interpret: bool):
    bkv, group, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // bq, sk // bk
    last = functools.partial(_last_kv_block, bq=bq, bk=bk,
                             q_offset=q_offset, causal=causal)

    def q_map(h, i, j, len_ref):
        # a q block past the length is not computed: hold the last live one
        live_q = jnp.maximum((len_ref[h] - 1 - q_offset) // bq, 0)
        return h, 0, jnp.minimum(i, live_q), 0

    def kv_map(h, i, j, len_ref):
        return h, jnp.maximum(jnp.minimum(j, last(i, len_ref[h])), 0), 0

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               q_offset=q_offset, bq=bq, bk=bk)
    rows = group * bq
    pairs = bkv * group * sq * sk // (2 if causal else 1)
    itemsize = jnp.dtype(q.dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bkv, nq, nk),
            in_specs=[pl.BlockSpec((None, group, bq, d), q_map),
                      pl.BlockSpec((None, bk, d), kv_map),
                      pl.BlockSpec((None, bk, d), kv_map)],
            out_specs=pl.BlockSpec((None, group, bq, d),
                                   lambda h, i, j, len_ref: (h, 0, i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, d), q.dtype),
                            pltpu.VMEM((rows, LANES), jnp.float32),
                            pltpu.VMEM((rows, LANES), jnp.float32),
                            pltpu.VMEM((rows, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(rows, bk, d, itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * d, transcendentals=pairs,
            bytes_accessed=itemsize * (2 * q.size + 2 * k.size * nq)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(lengths, q, k, v)


@functools.partial(jax.jit, static_argnames=(
    "causal", "bq", "bk", "interpret", "q_offset", "scale"))
def flash_attention(q, k, v, lengths=None, *, causal: bool = True,
                    scale: float | None = None, q_offset: int = 0,
                    bq: int | None = None, bk: int | None = None,
                    interpret: bool | None = None):
    """Attention of q (BH, Sq, D) against k, v (BKV, Sk, D), in q's dtype.

    ``lengths``: (BKV,) int32 valid key positions per kv head row (all of
    Sk by default); ``scale`` of the scores, 1/sqrt(D) by default;
    ``bq``/``bk``: the q and kv tiles (:func:`block_sizes` by default)."""
    bh, sq, d = q.shape
    bkv, sk, dv = k.shape
    assert bh % bkv == 0 and dv == d
    group = bh // bkv
    if bq is None or bk is None:
        sizes = block_sizes(sq, sk, group)
        if sizes is None:
            raise ValueError(f"flash_attention: {sq} queries and {sk} keys "
                             f"do not tile; pass bq and bk")
        bq, bk = bq or sizes[0], bk or sizes[1]
    bq, bk = min(bq, sq), min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    if lengths is None:
        lengths = jnp.full((bkv,), sk, jnp.int32)
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    call = functools.partial(_call, causal=causal, scale=scale,
                             q_offset=q_offset, bq=bq, bk=bk)
    args = (q.reshape(bkv, group, sq, d), k, v,
            jnp.asarray(lengths, jnp.int32))
    if interpret is None:
        out = jax.lax.platform_dependent(
            *args, tpu=functools.partial(call, interpret=False),
            default=functools.partial(call, interpret=True))
    else:
        out = call(*args, interpret=interpret)
    return out.reshape(bh, sq, d)
