"""Hand-written Pallas tiled matmul (MXU-aligned BlockSpecs).

Grid (m/bm, n/bn, k/bk) with the k dimension innermost; a float32 VMEM scratch
accumulates partial products across k steps and is flushed to the output block
on the last step — the canonical Mosaic matmul shape.  Validated against
ref.matmul in interpret mode; on real TPU the same kernel compiles natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
           interpret: bool | None = None, out_dtype=None):
    """C = A @ B with (bm, bn, bk) MXU tiling.

    ``interpret=None`` auto-selects: interpret mode only on CPU hosts."""
    if interpret is None:
        from repro.compiler.options import default_interpret
        interpret = default_interpret()
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"shape ({m},{k})x({k},{n}) not divisible by tile ({bm},{bn},{bk})"
    out_dtype = out_dtype or a.dtype
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
