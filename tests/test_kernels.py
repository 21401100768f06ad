"""Per-kernel allclose sweeps: Pallas kernels (interpret=True) and
DPIA-generated kernels vs the ref.py oracles, across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dpia_blas, ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.rmsnorm import rmsnorm
from repro.core.dpia import interp


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 64, 64, 64),
    (256, 128, 64, 64, 64, 128),
    (64, 256, 128, 64, 128, 64),
    (128, 128, 128, 128, 128, 128),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_pallas_matmul(rng, m, k, n, bm, bn, bk, dtype):
    a = jnp.asarray(rng.randn(m, k), dtype)
    b = jnp.asarray(rng.randn(k, n), dtype)
    got = matmul(a, b, bm=bm, bn=bn, bk=bk, out_dtype="float32")
    want = ref.matmul(a, b, out_dtype="float32")
    tol = 1e-4 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("rows,d,br", [(64, 128, 16), (100, 64, 32),
                                       (8, 512, 8)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_pallas_rmsnorm(rng, rows, d, br, dtype):
    x = jnp.asarray(rng.randn(rows, d), dtype)
    w = jnp.asarray(rng.randn(d), dtype)
    got = rmsnorm(x, w, block_rows=br)
    want = ref.rmsnorm(x, w)
    tol = 1e-4 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(got, "float32"),
                               np.asarray(want, "float32"),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bh,bkv,s,d,bq,bk_,opts", [
    pytest.param(4, 4, 128, 64, 64, 64, {}, id="4-4-128-64-64-64"),  # MHA
    pytest.param(8, 2, 256, 64, 64, 128, {},
                 id="8-2-256-64-64-128"),                          # GQA 4:1
    pytest.param(4, 1, 128, 32, 128, 32, {}, id="4-1-128-32-128-32"),  # MQA
    # GQA 8:1 at head_dim 128 (yi-9b), up to four kv tiles a q block
    pytest.param(32, 4, 512, 128, 64, 128, {}, id="gqa8-hd128-tiled"),
    # GQA 4:1 at head_dim 64 with granite's score scale
    pytest.param(16, 4, 512, 64, 128, 128, {"scale": 1 / 64},
                 id="gqa4-hd64-scale"),
    # a real length short of the bucket: a q block and kv tiles past it
    # are skipped; rows past it are not compared
    pytest.param(16, 2, 512, 128, 128, 128, {"length": 300},
                 id="length-300-of-512"),
    pytest.param(16, 2, 512, 128, 64, 128, {"dtype": "bfloat16"},
                 id="bf16"),
    # the default tiles (block_sizes): 128 queries of 8 heads, 512 keys
    pytest.param(16, 2, 1024, 128, None, None, {"dtype": "bfloat16"},
                 id="bf16-default-blocks"),
])
@pytest.mark.parametrize("causal", [True, False])
def test_pallas_flash_attention(rng, bh, bkv, s, d, bq, bk_, opts, causal):
    dtype = opts.get("dtype", "float32")
    q = jnp.asarray(rng.randn(bh, s, d) * 0.3, dtype)
    k = jnp.asarray(rng.randn(bkv, s, d) * 0.3, dtype)
    v = jnp.asarray(rng.randn(bkv, s, d), dtype)
    n = opts.get("length", s)
    lengths = jnp.full((bkv,), n, jnp.int32) if n < s else None
    got = flash_attention(q, k, v, lengths, causal=causal,
                          scale=opts.get("scale"), bq=bq, bk=bk_)
    assert got.dtype == q.dtype
    f32 = lambda t: t[:, :n].astype("float32")  # noqa: E731
    want = ref.flash_attention(f32(q), f32(k), f32(v), causal=causal,
                               scale=opts.get("scale"))
    tol = 2e-3 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(want),
                               rtol=tol, atol=tol)


def test_pallas_flash_decode_offset(rng):
    q = jnp.asarray(rng.randn(4, 1, 64), "float32") * 0.3
    k = jnp.asarray(rng.randn(2, 256, 64), "float32") * 0.3
    v = jnp.asarray(rng.randn(2, 256, 64), "float32")
    got = flash_attention(q, k, v, causal=True, q_offset=255, bq=1, bk=64)
    want = ref.flash_attention(q, k, v, causal=True, q_offset=255)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


DPIA_CASES = [
    ("scal", lambda n: dpia_blas.strategy_scal(n, block=n // 4),
     lambda rng, n: (jnp.float32(2.5), jnp.asarray(rng.randn(n), "float32"))),
    ("asum", lambda n: dpia_blas.strategy_asum(n, block=n // 4),
     lambda rng, n: (jnp.asarray(rng.randn(n), "float32"),)),
    ("dot", lambda n: dpia_blas.strategy_dot(n, block=n // 4),
     lambda rng, n: (jnp.asarray(rng.randn(n), "float32"),
                     jnp.asarray(rng.randn(n), "float32"))),
]


@pytest.mark.parametrize("name,builder,mk", DPIA_CASES)
@pytest.mark.parametrize("n", [256, 1024])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_dpia_blas_sweep(rng, name, builder, mk, n, backend):
    expr, argv = builder(n)
    args = mk(rng, n)
    want = interp.interp(expr, {v.name: a for v, a in zip(argv, args)})
    from repro import compiler
    fn = compiler.Program(expr, argv).check().lower().compile(backend)
    got = fn(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("m,n,rb", [(64, 128, 16), (256, 64, 64)])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_dpia_gemv_sweep(rng, m, n, rb, backend):
    expr, argv = dpia_blas.strategy_gemv(m, n, row_block=rb)
    a = jnp.asarray(rng.randn(m, n), "float32")
    x = jnp.asarray(rng.randn(n), "float32")
    from repro import compiler
    fn = compiler.Program(expr, argv).check().lower().compile(backend)
    np.testing.assert_allclose(np.asarray(fn(a, x)), np.asarray(a @ x),
                               rtol=2e-3, atol=2e-3)


def test_ops_dispatcher(rng):
    """The public ops API routes impls and agrees with refs."""
    x = jnp.asarray(rng.randn(4096), "float32")
    y = jnp.asarray(rng.randn(4096), "float32")
    for impl in ("xla", "dpia-jnp"):
        np.testing.assert_allclose(np.asarray(ops.dot(x, y, impl=impl)),
                                   np.asarray(ref.dot(x, y)), rtol=1e-3)
        np.testing.assert_allclose(np.asarray(ops.asum(x, impl=impl)),
                                   np.asarray(ref.asum(x)), rtol=1e-3)
