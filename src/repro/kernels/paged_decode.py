"""Hand-written Pallas paged decode attention over layer-stacked KV pools.

One query token per slot attends to that slot's pages of one layer, read
through its block-table row.  The pools keep the serving layout
``(L, n_blocks, block_size, nkv, hd)`` and stay in HBM; ``layer``,
``lengths`` and the block tables arrive as scalar prefetch.  Grid step
``i`` (slot ``i``) copies only the first ``ceil(lengths[i] / block_size)``
pages of its row into VMEM, a block of pages (at least 128 positions) at a
time, with the next block's copies (the next slot's first, after a slot's
last) started before the current block is computed.

A page holds every kv head of its ``block_size`` positions, so a block of
pages is ``(positions * nkv, hd)`` rows with kv head ``n`` of position
``t`` at row ``t * nkv + n``: each head is the strided row set
``n::nkv``, lane-aligned when ``hd`` is a multiple of 128.  In a bfloat16
block two heads share each 32-bit word (the even head in the low half), so
heads are read in pairs through a 32-bit view and split by shifting.

Per kv head the GQA group's queries run the online-softmax recurrence of
``flash_attention.py`` in float32; the PV product takes probabilities in
the pool's dtype, as the jnp decode path (``models.attention._attend_token``)
does.  Positions past a slot's length are masked; copy slots of a block
that hold no page of this slot keep the finite rows of an earlier copy
(the buffers are zeroed once per call), so a masked row weighs zero.
Validated against the gathered-view path in interpret mode
(``tests/test_paged_decode_kernel.py``).

The mode follows the lowering platform: the Mosaic kernel where the
program is compiled for a TPU (also from a CPU host, for a described
chip), interpret mode elsewhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# a block of pages spans at least this many positions: one lane-width of
# scores per kv head.  chip_smoke.py times 1, 2 and 4 times this block: on
# a v5e 512 positions took 2-11% less kernel time at the benchmark's
# pools, under 1% of a decode chunk
MIN_BLOCK_POSITIONS = 128


def supports(shape, dtype) -> bool:
    """Whether a pool of ``shape`` (L, n_blocks, block_size, nkv, hd) and
    ``dtype`` has the layout the Mosaic kernel reads: lane-aligned heads,
    and for bfloat16 an even number of heads (they are read in pairs)."""
    nkv, hd = shape[3], shape[4]
    dtype = jnp.dtype(dtype)
    if hd % 128:
        return False
    if dtype == jnp.float32:
        return True
    return dtype == jnp.bfloat16 and nkv % 2 == 0


def _pages_per_block(block_size: int) -> int:
    return max(1, -(-MIN_BLOCK_POSITIONS // block_size))


def _kv_heads(ref, nkv: int, rows: int):
    """Kv head n's ``(rows, hd)`` values, for every n, from a block ref of
    ``(rows * nkv, hd)`` rows."""
    if jnp.dtype(ref.dtype).itemsize == 4:
        return [ref[pl.ds(n, rows, stride=nkv), :] for n in range(nkv)]
    words = ref.bitcast(jnp.uint32)          # (rows * nkv / 2, hd)
    heads = []
    for w in range(nkv // 2):
        b = words[pl.ds(w, rows, stride=nkv // 2), :]
        heads.append(pltpu.bitcast(b << 16, jnp.float32).astype(ref.dtype))
        heads.append(pltpu.bitcast(b & jnp.uint32(0xFFFF0000),
                                   jnp.float32).astype(ref.dtype))
    return heads


def _paged_decode_kernel(layer_ref, len_ref, bt_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sems, buf_ref, *, ppb: int,
                         block_size: int, n_blocks: int, max_blocks: int,
                         scale: float):
    i = pl.program_id(0)
    n_slots = pl.num_programs(0)
    nkv, group, hd = q_ref.shape
    rows = ppb * block_size
    layer = layer_ref[0]

    def n_pages(s):
        return (len_ref[s] + block_size - 1) // block_size

    def copies(s, blk, buf):
        """(live, k copy, v copy) for each page of block ``blk`` of slot
        ``s``; a page past the slot's length is not live."""
        out = []
        for p in range(ppb):
            idx = blk * ppb + p
            entry = bt_ref[s * max_blocks + jnp.minimum(idx, max_blocks - 1)]
            page = jnp.clip(entry, 0, n_blocks - 1)
            out.append((idx < n_pages(s),
                        pltpu.make_async_copy(k_hbm.at[layer, page],
                                              k_buf.at[buf, p],
                                              sems.at[0, buf]),
                        pltpu.make_async_copy(v_hbm.at[layer, page],
                                              v_buf.at[buf, p],
                                              sems.at[1, buf])))
        return out

    def start(s, blk, buf):
        for live, kc, vc in copies(s, blk, buf):
            @pl.when(live)
            def _():
                kc.start()
                vc.start()

    def wait(s, blk, buf):
        for live, kc, vc in copies(s, blk, buf):
            @pl.when(live)
            def _():
                kc.wait()
                vc.wait()

    @pl.when(i == 0)
    def _():
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        buf_ref[0] = 0
        start(0, 0, 0)

    length = len_ref[i]
    n_blk = (n_pages(i) + ppb - 1) // ppb
    buf0 = buf_ref[0]

    def body(j, carry):
        buf = (buf0 + j) % 2

        @pl.when(j + 1 < n_blk)
        def _():
            start(i, j + 1, 1 - buf)

        @pl.when(jnp.logical_and(j + 1 == n_blk, i + 1 < n_slots))
        def _():
            start(i + 1, 0, 1 - buf)

        wait(i, j, buf)
        ks = _kv_heads(k_buf.at[buf].reshape(rows * nkv, hd), nkv, rows)
        vs = _kv_heads(v_buf.at[buf].reshape(rows * nkv, hd), nkv, rows)
        kpos = j * rows + jax.lax.broadcasted_iota(jnp.int32, (group, rows),
                                                   1)
        valid = kpos < length
        out = []
        for n in range(nkv):
            m_prev, l_prev, acc_prev = carry[n]
            s = jax.lax.dot_general(
                q_ref[n], ks[n], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_new = alpha * acc_prev + jnp.dot(
                p.astype(vs[n].dtype), vs[n],
                preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc_new))
        return tuple(out)

    init = tuple((jnp.full((group, 1), NEG_INF, jnp.float32),
                  jnp.zeros((group, 1), jnp.float32),
                  jnp.zeros((group, hd), jnp.float32)) for _ in range(nkv))
    carry = jax.lax.fori_loop(0, n_blk, body, init)
    buf_ref[0] = (buf0 + n_blk) % 2
    for n in range(nkv):
        _, l_i, acc = carry[n]
        o_ref[n] = acc / l_i


def _call(q, k_pool, v_pool, layer, lengths, block_tables, *, ppb: int,
          interpret: bool):
    b, nkv, group, hd = q.shape
    _, n_blocks, block_size, _, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    kernel = functools.partial(
        _paged_decode_kernel, ppb=ppb, block_size=block_size,
        n_blocks=n_blocks, max_blocks=max_blocks, scale=1.0 / math.sqrt(hd))
    buf = (2, ppb, block_size, nkv, hd)
    slot = pl.BlockSpec((None, nkv, group, hd), lambda i, *_: (i, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[slot,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=slot,
            scratch_shapes=[pltpu.VMEM(buf, k_pool.dtype),
                            pltpu.VMEM(buf, v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        # sequential: a step prefetches the next slot's first block into
        # the buffer the SMEM word names
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      q, k_pool, v_pool)


@jax.jit
def paged_decode_attention(q, k_pool, v_pool, layer, lengths, block_tables):
    """Decode attention of one token per slot against its valid pages.

    q: (b, nh, hd); k_pool/v_pool: (L, n_blocks, block_size, nkv, hd);
    layer: int32 scalar; lengths: (b,) int32, each at least 1 — slot ``i``
    attends positions ``[0, lengths[i])``; block_tables: (b, max_blocks)
    int32, entries clipped to ``[0, n_blocks)``.  Returns (b, nh, hd)
    float32, query head ``h`` attending kv head ``h // (nh // nkv)``."""
    b, nh, hd = q.shape
    nkv = k_pool.shape[3]
    ppb = _pages_per_block(k_pool.shape[2])
    qg = q.reshape(b, nkv, nh // nkv, hd)
    out = jax.lax.platform_dependent(
        qg, k_pool, v_pool, layer, lengths, block_tables,
        tpu=functools.partial(_call, ppb=ppb, interpret=False),
        default=functools.partial(_call, ppb=ppb, interpret=True))
    return out.reshape(b, nh, hd)
