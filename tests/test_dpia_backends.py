"""Backend equivalence: Pallas (interpret) and hoisting vs the jnp backend and
the functional oracle; mesh backend in a subprocess (needs >1 device)."""
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpia import hoist, interp, phrases as P, stage1, stage2
from repro.core.dpia import stage3_jnp, stage3_pallas
from repro.core.dpia.types import Arr, Num
from repro.kernels import dpia_blas


def both_backends(expr, argv, args, rtol=2e-3):
    want = interp.interp(expr, {v.name: a for v, a in zip(argv, args)})
    for backend in ("jnp", "pallas"):
        from repro import compiler
        fn = compiler.Program(expr, argv).check().lower().compile(backend)
        got = fn(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=rtol, atol=rtol,
                                   err_msg=f"backend={backend}")


class TestPallasBackend:
    def test_grid_dot(self, rng):
        expr, argv = dpia_blas.strategy_dot(1024, block=128)
        args = (jnp.asarray(rng.randn(1024), "float32"),
                jnp.asarray(rng.randn(1024), "float32"))
        both_backends(expr, argv, args)

    def test_grid_scal(self, rng):
        expr, argv = dpia_blas.strategy_scal(512, block=64)
        args = (jnp.float32(3.5), jnp.asarray(rng.randn(512), "float32"))
        both_backends(expr, argv, args)

    def test_grid_matmul(self, rng):
        expr, argv = dpia_blas.strategy_matmul(64, 64, 32, bm=16, bk=32)
        args = (jnp.asarray(rng.randn(64, 64), "float32"),
                jnp.asarray(rng.randn(64, 32), "float32"))
        both_backends(expr, argv, args)

    def test_rmsnorm(self, rng):
        expr, argv = dpia_blas.strategy_rmsnorm(16, 64, row_block=4)
        args = (jnp.asarray(rng.randn(16, 64), "float32"),
                jnp.asarray(rng.randn(64), "float32"))
        both_backends(expr, argv, args)

    def test_vectorised_scal(self, rng):
        """asVector strategy (paper section 6.2/6.3) through both backends."""
        alpha = P.var_exp("alpha", Num())
        xs = P.var_exp("xs", Arr(256, Num()))
        e = P.AsScalar(P.Join(P.Map(
            lambda blk: P.mul(alpha, blk),
            P.Split(4, P.AsVector(8, xs)), level=P.GRID(0))))
        args = (jnp.float32(1.5), jnp.asarray(rng.randn(256), "float32"))
        both_backends(e, [alpha, xs], args)


class TestPallasPlacement:
    """Views compile to indices, the grid-level split to BlockSpecs."""

    @staticmethod
    def _spy(monkeypatch):
        from jax.experimental import pallas as pl
        calls = []
        real = pl.pallas_call

        def spy(kernel, **kw):
            calls.append(kw)
            return real(kernel, **kw)
        monkeypatch.setattr(stage3_pallas.pl, "pallas_call", spy)
        return calls

    def test_grid_split_becomes_blockspec(self, rng, monkeypatch):
        calls = self._spy(monkeypatch)
        expr, argv = dpia_blas.strategy_dot(8192, block=2048)
        args = (jnp.asarray(rng.randn(8192), "float32"),
                jnp.asarray(rng.randn(8192), "float32"))
        both_backends(expr, argv, args)
        (kw,) = calls
        assert [s.block_shape for s in kw["in_specs"]] == [(2048,), (2048,)]
        # one partial per grid step, stored element by element: SMEM
        assert kw["out_specs"][0].memory_space == stage3_pallas.pltpu.SMEM

    def test_untileable_block_stays_whole(self, rng, monkeypatch):
        calls = self._spy(monkeypatch)
        expr, argv = dpia_blas.strategy_scal(512, block=64)
        both_backends(expr, argv,
                      (jnp.float32(3.5), jnp.asarray(rng.randn(512),
                                                     "float32")))
        (kw,) = calls
        # a 64-element 1-D block is not a whole (8, 128) tile
        assert kw["in_specs"][1].block_shape == (512,)

    def test_oversized_operand_read_by_dma(self, rng, monkeypatch):
        calls = self._spy(monkeypatch)
        monkeypatch.setattr(stage3_pallas, "VMEM_OPERAND_BYTES", 64 * 1024)
        expr, argv = dpia_blas.strategy_matmul(64, 256, 128, bm=16, bk=128)
        args = (jnp.asarray(rng.randn(64, 256), "float32"),
                jnp.asarray(rng.randn(256, 128), "float32"))
        both_backends(expr, argv, args)
        (kw,) = calls
        a_spec, b_spec = kw["in_specs"]
        assert a_spec.block_shape == (16, 256)
        assert b_spec.memory_space == stage3_pallas.pl.ANY
        assert any(getattr(s, "shape", None) == (128, 128)
                   for s in kw["scratch_shapes"])


    def test_vector_slices_within_grid_blocks(self, rng):
        """scal's vector strategy writes lane-width slices inside each grid
        block: joinAcc of a block index and a slice."""
        from repro import compiler
        fn = compiler.Program.from_kernel(
            "scal", n=1024, params={"block": 256, "vector": 128}
        ).check().lower().compile("pallas")
        x = jnp.asarray(rng.randn(1024), "float32")
        np.testing.assert_allclose(np.asarray(fn(jnp.float32(2.5), x)),
                                   2.5 * np.asarray(x), rtol=1e-6)

    @pytest.mark.parametrize("kernel,params,shape,args", [
        ("dot", {"block": 1024, "leaf": "seq"}, {"n": 8192},
         [(8192,), (8192,)]),
        ("scal", {"block": 1024, "vector": 128}, {"n": 8192},
         [(), (8192,)]),
        ("matmul", {"bm": 128, "bk": 64}, {"m": 256, "k": 256, "n": 256},
         [(256, 256), (256, 256)]),
    ], ids=["dot-seq-leaf", "scal-lane-vector", "matmul-narrow-k"])
    def test_untileable_strategy_refused_for_the_chip(self, kernel, params,
                                                      shape, args):
        """Accesses Mosaic cannot tile raise, naming the op and its params,
        instead of failing inside Mosaic; interpret mode still runs them."""
        from repro import compiler
        from repro.autotune import space
        prog = compiler.Program.from_kernel(kernel, params=params, **shape)
        sds = [jax.ShapeDtypeStruct(s, jnp.float32) for s in args]
        fn = prog.check().lower().compile("pallas", interpret=False,
                                          jit=False)
        name = re.escape(f"{kernel}[{space.params_key(params)}]")
        with pytest.raises(NotImplementedError, match=name):
            jax.eval_shape(fn, *sds)
        jax.eval_shape(prog.compile("pallas", interpret=True, jit=False),
                       *sds)

    def test_tuner_keeps_only_lowering_candidates_for_the_chip(self,
                                                               tuning_cache):
        from repro import autotune
        from repro.autotune import measure, space
        full = autotune.tune("dot", backend="pallas", measure=False,
                             interpret=True, force=True, cache=tuning_cache,
                             n=8192)
        chip = autotune.tune("dot", backend="pallas", measure=False,
                             interpret=False, force=True,
                             cache=tuning_cache, n=8192)
        assert 0 < chip.n_candidates < full.n_candidates
        assert measure.lowers_for_chip(
            space.candidate_from_params("dot", chip.params, n=8192))


class TestHoist:
    def test_paper_64_example_semantics(self, rng):
        """Section 6.4: hoisting multiplies extents and preserves semantics."""
        xs = P.var_exp("xs", Arr(64, Num()))
        out = P.var_acc("out", Arr(16, Num()))
        prog = P.ParFor(16, Num(), out, lambda i, o: P.New(
            Arr(4, Num()),
            lambda tmp: P.SeqC(
                P.For(4, lambda j: P.Assign(
                    P.IdxAcc(P.AccPart(tmp), j),
                    P.IdxE(P.IdxE(P.Split(4, xs), i), j))),
                P.Assign(o, P.FullReduce("add", P.ExpPart(tmp)))),
            space=P.HBM))
        hoisted = hoist.hoist(prog)
        # structure: top-level New of the multiplied extent
        assert isinstance(hoisted, P.New)
        assert hoisted.d == Arr(16, Arr(4, Num()))
        env = {"xs": jnp.asarray(rng.randn(64), "float32")}
        s1 = stage3_jnp.exec_comm(prog, env, {"out": jnp.zeros(16)})
        s2 = stage3_jnp.exec_comm(hoisted, env, {"out": jnp.zeros(16)})
        np.testing.assert_allclose(s1["out"], s2["out"], rtol=1e-5)

    def test_reg_news_not_hoisted(self):
        out = P.var_acc("out", Arr(8, Num()))
        xs = P.var_exp("xs", Arr(8, Num()))
        prog = P.ParFor(8, Num(), out, lambda i, o: P.New(
            Num(), lambda v: P.SeqC(
                P.Assign(P.AccPart(v), P.IdxE(xs, i)),
                P.Assign(o, P.ExpPart(v))), space=P.REG))
        assert hoist.hoist(prog) is prog  # no hoistable items -> unchanged


MESH_TEST = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core.dpia import interp, stage3_shardmap
from repro.kernels import dpia_blas
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
expr, argv = dpia_blas.mesh_dot(8 * 64, "data", 8, block=64)
rng = np.random.RandomState(0)
ax = jnp.asarray(rng.randn(512), "float32")
ay = jnp.asarray(rng.randn(512), "float32")
want = interp.interp(expr, {"xs": ax, "ys": ay})
fn = jax.jit(stage3_shardmap.compile_expr_shardmap(expr, argv, mesh))
got = fn(ax, ay)
np.testing.assert_allclose(got, want, rtol=1e-4)
hlo = jax.jit(fn).lower(ax, ay).compile().as_text()
# count all-reduce *instructions* (opcode position), not raw substrings:
# XLA names the instruction %all-reduce.N, which a plain count double-counts
import re
n_ar = len(re.findall(r"=\s*\S+\s+all-reduce(?:-start)?\(", hlo))
assert n_ar == 1, f"strategy dictates exactly ONE all-reduce, found {n_ar}"
print("MESH_OK")
"""


@pytest.mark.slow
def test_mesh_backend_subprocess():
    """Distributed dot: correct result AND exactly the collective schedule the
    strategy dictates (one all-reduce) — strategy preservation at mesh level."""
    # JAX_PLATFORMS=cpu: this is a *host-platform* multi-device test; without
    # it, images with libtpu installed try (and stall on) TPU init and lower
    # the collective asynchronously, breaking the schedule assertion below.
    r = subprocess.run([sys.executable, "-c", MESH_TEST],
                       capture_output=True, text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert "MESH_OK" in r.stdout, r.stdout + r.stderr
