"""repro.compiler — backend registry, options scoping, the staged Program
API, and the deprecation shims it replaces.

Covers the acceptance gate: all six benchmark ops (scal/asum/dot/matmul/
rmsnorm/softmax) run through ``Program.check().lower().compile(backend)``
for both jnp and pallas backends and match the interpreter oracle.
"""
import threading
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler
from repro.core.dpia import interp, phrases as P
from repro.core.dpia.check import RaceError
from repro.core.dpia.types import AccT, Arr, Num
from repro.kernels import dpia_blas, ops, ref


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

class TestBackendRegistry:
    def test_builtins_registered(self):
        names = compiler.backend_names()
        assert {"jnp", "pallas", "shardmap"} <= set(names)

    def test_lookup_and_aliases(self):
        assert compiler.get_backend("jnp").name == "jnp"
        # the seed's impl-string spellings resolve as aliases
        assert compiler.get_backend("dpia-pallas").name == "pallas"
        b = compiler.get_backend("pallas")
        assert compiler.get_backend(b) is b  # pass-through

    def test_unknown_name_lists_registry(self):
        with pytest.raises(ValueError, match="jnp"):
            compiler.get_backend("not-a-backend")

    def test_ops_impls_derived_from_registry(self):
        impls = compiler.ops_impls()
        assert impls == ("xla", "pallas", "dpia-jnp", "dpia-pallas",
                         "dpia-shardmap")
        # shardmap's mesh requirement is satisfiable from the options /
        # process mesh context, so it IS an op-layer impl (repro.mesh)

    def test_register_custom_backend(self):
        def compile_interp(expr, arg_vars, **kw):
            names = [v.name for v in arg_vars]

            def fn(*args):
                return interp.interp(expr, dict(zip(names, args)))
            return fn

        backend = compiler.Backend(
            name="interp-test", compile=compile_interp,
            description="oracle semantics as a backend")
        compiler.register_backend(backend)
        try:
            # duplicate registration is refused without overwrite=True
            with pytest.raises(ValueError, match="already registered"):
                compiler.register_backend(backend)
            prog = compiler.Program.from_kernel("dot", n=64)
            fn = prog.check().lower().compile("interp-test", jit=False)
            rng = np.random.RandomState(0)
            x = jnp.asarray(rng.randn(64), "float32")
            y = jnp.asarray(rng.randn(64), "float32")
            np.testing.assert_allclose(np.asarray(fn(x, y)),
                                       np.asarray(ref.dot(x, y)), rtol=1e-4)
        finally:
            compiler.unregister_backend("interp-test")
        with pytest.raises(ValueError):
            compiler.get_backend("interp-test")


# ---------------------------------------------------------------------------
# options: explicit, scoped, thread-local
# ---------------------------------------------------------------------------

class TestOptions:
    def test_defaults(self):
        opts = compiler.current_options()
        assert opts.backend == "xla"
        assert opts.interpret is True

    def test_interpret_env_is_cpu_only(self, monkeypatch):
        """REPRO_INTERPRET=1 may pick interpret mode on the CPU only; on an
        accelerator it is an error, never a silent interpreter."""
        import jax
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        assert compiler.default_interpret() is True
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="CPU hosts only"):
            compiler.default_interpret()
        monkeypatch.setenv("REPRO_INTERPRET", "0")
        assert compiler.default_interpret() is False

    def test_aot_artefact_must_record_interpret(self, rng, tmp_path):
        from repro.compiler import executors
        from repro.ft import artefacts
        store = executors.ExecutorCache()
        prog = compiler.Program.from_kernel("dot", n=256)
        store.put("dot-key", prog.compile("pallas"), meta={"interpret": True})
        assert store.save_aot(str(tmp_path)) == 1
        (name,) = [n for n in __import__("os").listdir(tmp_path)
                   if n.endswith(".json")]
        path = str(tmp_path / name)
        doc = artefacts.load_json(path)
        doc.pop("checksum", None)
        doc.pop("interpret")
        artefacts.save_json(path, doc)
        assert executors.ExecutorCache().load_aot(str(tmp_path)) == 0

    def test_validation(self):
        with pytest.raises(ValueError, match="valid backends"):
            compiler.CompileOptions(backend="garbage")
        with pytest.raises(ValueError, match="valid backends"):
            with compiler.options(backend="garbage"):
                pass  # pragma: no cover

    def test_scoping_and_nesting(self):
        assert compiler.current_options().backend == "xla"
        with compiler.options(backend="dpia-jnp"):
            assert compiler.current_options().backend == "dpia-jnp"
            with compiler.options(autotune=False):
                inner = compiler.current_options()
                # inner scope inherits the outer backend
                assert inner.backend == "dpia-jnp"
                assert inner.autotune is False
            assert compiler.current_options().backend == "dpia-jnp"
        assert compiler.current_options().backend == "xla"

    def test_thread_locality(self):
        seen = {}

        def probe():
            seen["other"] = compiler.current_options().backend
            with compiler.options(backend="dpia-pallas"):
                seen["scoped"] = compiler.current_options().backend

        with compiler.options(backend="dpia-jnp"):
            t = threading.Thread(target=probe)
            t.start()
            t.join()
            # this thread's scope survives the other thread's scope
            assert compiler.current_options().backend == "dpia-jnp"
        # the other thread saw the process default, not our scope...
        assert seen["other"] == "xla"
        # ...and its own scope worked
        assert seen["scoped"] == "dpia-pallas"

    def test_dpia_backend_mapping(self):
        assert compiler.CompileOptions(backend="dpia-pallas").dpia_backend \
            == "pallas"
        assert compiler.CompileOptions(backend="xla").dpia_backend == "jnp"


# ---------------------------------------------------------------------------
# the staged Program pipeline
# ---------------------------------------------------------------------------

# (kernel, shape kwargs, args builder, oracle)
_SIX_OPS = [
    ("scal", dict(n=256),
     lambda r: (jnp.float32(1.7), jnp.asarray(r.randn(256), "float32")),
     lambda alpha, x: ref.scal(alpha, x)),
    ("asum", dict(n=256),
     lambda r: (jnp.asarray(r.randn(256), "float32"),),
     lambda x: ref.asum(x)),
    ("dot", dict(n=256),
     lambda r: (jnp.asarray(r.randn(256), "float32"),
                jnp.asarray(r.randn(256), "float32")),
     lambda x, y: ref.dot(x, y)),
    ("matmul", dict(m=32, k=64, n=16),
     lambda r: (jnp.asarray(r.randn(32, 64), "float32"),
                jnp.asarray(r.randn(64, 16), "float32")),
     lambda a, b: ref.matmul(a, b)),
    ("rmsnorm", dict(rows=16, d=64),
     lambda r: (jnp.asarray(r.randn(16, 64), "float32"),
                jnp.asarray(r.randn(64), "float32")),
     lambda x, w: ref.rmsnorm(x, w)),
    ("softmax", dict(rows=16, d=64),
     lambda r: (jnp.asarray(r.randn(16, 64), "float32"),),
     lambda x: ref.softmax(x)),
]


class TestProgram:
    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize(
        "kernel,shape,mkargs,oracle", _SIX_OPS,
        ids=[k for k, _, _, _ in _SIX_OPS])
    def test_six_ops_staged_pipeline(self, rng, backend, kernel, shape,
                                     mkargs, oracle):
        """Acceptance: every benchmark op through check->lower->compile on
        both backends, numerics matching the reference oracle."""
        prog = compiler.Program.from_kernel(kernel, **shape)
        fn = prog.check().lower().compile(backend)
        args = mkargs(rng)
        np.testing.assert_allclose(
            np.asarray(fn(*args), "float32"),
            np.asarray(oracle(*args), "float32"), rtol=1e-4, atol=1e-4)

    def test_staged_pipeline_matches_interpreter_oracle(self, rng):
        """The compiled strategy equals the *functional reading* (interp)."""
        prog = compiler.Program.from_kernel("dot", n=128)
        x = jnp.asarray(rng.randn(128), "float32")
        y = jnp.asarray(rng.randn(128), "float32")
        want = interp.interp(prog.expr, {"xs": x, "ys": y})
        for backend in ("jnp", "pallas"):
            got = prog.check().lower().compile(backend)(x, y)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4)

    def test_lower_with_rewrite_callable(self, rng):
        from repro.core.dpia import strategies
        expr, argv = dpia_blas.naive_dot(256)
        prog = compiler.Program(expr, argv, name="dot-naive")

        def strategy(e):
            fused = strategies.fuse_map_into_reduce(e)
            return strategies.blocked_reduce(
                fused, 64, partial_level=P.GRID(0),
                combine=lambda x, a: P.add(a, x))

        lowered = prog.lower(strategy)
        assert lowered is not prog  # rewrites produce a new Program
        fn = lowered.check().compile("jnp")
        x = jnp.asarray(rng.randn(256), "float32")
        y = jnp.asarray(rng.randn(256), "float32")
        np.testing.assert_allclose(np.asarray(fn(x, y)),
                                   np.asarray(ref.dot(x, y)), rtol=1e-4)

    def test_lower_with_params_dict(self, rng):
        prog = compiler.Program.from_kernel("dot", n=256)
        tuned = prog.lower({"block": 64, "leaf": "vpu"})
        fn = tuned.check().compile("jnp")
        x = jnp.asarray(rng.randn(256), "float32")
        y = jnp.asarray(rng.randn(256), "float32")
        np.testing.assert_allclose(np.asarray(fn(x, y)),
                                   np.asarray(ref.dot(x, y)), rtol=1e-4)

    def test_lower_autotune_strategy(self, rng, tuning_cache):
        prog = compiler.Program.from_kernel("dot", n=256)
        with compiler.options(tuning_cache=tuning_cache):
            tuned = prog.lower("autotune")
        fn = tuned.check().compile("jnp")
        x = jnp.asarray(rng.randn(256), "float32")
        y = jnp.asarray(rng.randn(256), "float32")
        np.testing.assert_allclose(np.asarray(fn(x, y)),
                                   np.asarray(ref.dot(x, y)), rtol=1e-4)

    def test_check_rejects_racy_term(self):
        """The paper's section 3.3 example: every parfor iteration writes
        the same acceptor — Program.check() must reject it."""
        b = P.var_acc("b", Num())
        es = P.var_exp("es", Arr(8, Num()))
        out = P.Var("out#", AccT(Arr(8, Num())))
        racy = P.ParFor(8, Num(), out,
                        lambda i, o: P.Assign(b, P.IdxE(es, i)))
        prog = compiler.Program.from_imperative(racy, [es], out)
        with pytest.raises(RaceError):
            prog.check()

    def test_imperative_only_program_guards(self):
        """Imperative-only Programs reject rewrites and lowered-blind
        backends with clear errors instead of crashing on expr=None."""
        es = P.var_exp("es", Arr(8, Num()))
        out = P.Var("out#", AccT(Arr(8, Num())))
        ok = P.ParFor(8, Num(), out,
                      lambda i, o: P.Assign(o, P.IdxE(es, i)))
        prog = compiler.Program.from_imperative(ok, [es], out)
        with pytest.raises(ValueError, match="imperative-only"):
            prog.lower(lambda e: e)
        with pytest.raises(ValueError, match="imperative-only"):
            prog.compile("shardmap", mesh=object())
        # backends that accept the staged translation still work
        fn = prog.check().compile("jnp", jit=False)
        x = jnp.asarray(np.arange(8), "float32")
        np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))

    def test_imperative_view_and_show(self):
        prog = compiler.Program.from_kernel("dot", n=128)
        cmd = prog.imperative
        assert cmd is not None
        assert "parfor" in prog.show()

    def test_shardmap_backend_requires_mesh(self):
        prog = compiler.Program.from_kernel("dot", n=64)
        with pytest.raises(TypeError, match="mesh"):
            prog.compile("shardmap")

    def test_tune_accepts_program(self, tuning_cache):
        from repro import autotune
        prog = compiler.Program.from_kernel("dot", n=256)
        res = autotune.tune(prog, cache=tuning_cache, measure=False)
        assert res.kernel == "dot"
        assert res.params  # a concrete strategy was chosen
        res2 = autotune.tune(prog, cache=tuning_cache, measure=False)
        assert res2.source == "cache"


# ---------------------------------------------------------------------------
# ops dispatch through the table + options
# ---------------------------------------------------------------------------

class TestOpsDispatch:
    def test_scoped_backend_drives_ops(self, rng):
        x = jnp.asarray(rng.randn(256), "float32")
        y = jnp.asarray(rng.randn(256), "float32")
        with compiler.options(backend="dpia-jnp", autotune=False):
            got = ops.dot(x, y)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.dot(x, y)), rtol=1e-4)

    def test_explicit_options_object(self, rng):
        x = jnp.asarray(rng.randn(16, 64), "float32")
        w = jnp.asarray(rng.randn(64), "float32")
        opts = compiler.CompileOptions(backend="dpia-jnp", autotune=False)
        got = ops.rmsnorm(x, w, options=opts)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.rmsnorm(x, w)),
                                   rtol=1e-4, atol=1e-4)

    def test_unknown_impl_raises_value_error(self, rng):
        x = jnp.asarray(rng.randn(8), "float32")
        with pytest.raises(ValueError, match="valid backends"):
            ops.dot(x, x, impl="bogus")

    def test_softmax_dpia_path(self, rng):
        x = jnp.asarray(rng.randn(16, 64), "float32")
        got = ops.softmax(x, impl="dpia-jnp",
                          options=compiler.CompileOptions(autotune=False))
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.softmax(x)),
                                   rtol=1e-4, atol=1e-5)

    def test_user_registered_backend_drives_ops(self, rng):
        """A registered Stage III backend is usable as a dpia-<name> impl
        end to end, exactly as the registry contract advertises."""
        def compile_interp(expr, arg_vars, **kw):
            names = [v.name for v in arg_vars]

            def fn(*args):
                return interp.interp(expr, dict(zip(names, args)))
            return fn

        compiler.register_backend(compiler.Backend(
            name="interp-ops-test", compile=compile_interp))
        try:
            assert "dpia-interp-ops-test" in compiler.ops_impls()
            opts = compiler.CompileOptions(
                backend="dpia-interp-ops-test", autotune=False, jit=False)
            x = jnp.asarray(rng.randn(128), "float32")
            y = jnp.asarray(rng.randn(128), "float32")
            got = ops.dot(x, y, options=opts)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(ref.dot(x, y)), rtol=1e-4)
        finally:
            compiler.unregister_backend("interp-ops-test")
            ops.clear_caches()

    def test_program_cache_keyed_by_jit(self, rng):
        """options(jit=False) must not be served a cached jitted kernel."""
        ops.clear_caches()
        x = jnp.asarray(rng.randn(128), "float32")
        y = jnp.asarray(rng.randn(128), "float32")
        base = compiler.CompileOptions(backend="dpia-jnp", autotune=False)
        ops.dot(x, y, options=base)                       # jit=True entry
        n_jitted = len(compiler.executor_cache())
        ops.dot(x, y, options=base.replace(jit=False))    # must not collide
        assert len(compiler.executor_cache()) == 2 * n_jitted
        ops.clear_caches()

    def test_tuned_lookup_failure_warns_once(self, rng, monkeypatch):
        import repro.autotune as autotune
        ops.clear_caches()

        def boom(*a, **kw):
            raise RuntimeError("synthetic tuner failure")
        monkeypatch.setattr(autotune, "get_tuned", boom)
        x = jnp.asarray(rng.randn(128), "float32")
        y = jnp.asarray(rng.randn(128), "float32")
        opts = compiler.CompileOptions(backend="dpia-jnp", autotune=True)
        with pytest.warns(RuntimeWarning, match="synthetic tuner failure"):
            got = ops.dot(x, y, options=opts)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.dot(x, y)), rtol=1e-4)
        # one-shot: the second call must not warn again
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            ops.dot(x, y, options=opts)
        ops.clear_caches()


# ---------------------------------------------------------------------------
# deprecation shims: warn, validate, and match the new path bit-for-bit
# ---------------------------------------------------------------------------

class TestDeprecationShims:
    def test_set_default_impl_warns_and_works(self, rng):
        x = jnp.asarray(rng.randn(128), "float32")
        y = jnp.asarray(rng.randn(128), "float32")
        with pytest.warns(DeprecationWarning, match="set_default_impl"):
            ops.set_default_impl("dpia-jnp")
        try:
            via_shim = ops.dot(x, y)
        finally:
            with pytest.warns(DeprecationWarning):
                ops.set_default_impl("xla")
        with compiler.options(backend="dpia-jnp"):
            via_options = ops.dot(x, y)
        np.testing.assert_array_equal(np.asarray(via_shim),
                                      np.asarray(via_options))

    def test_set_default_impl_rejects_bad_impl(self):
        # ValueError (not assert): survives python -O and names the registry
        with pytest.warns(DeprecationWarning):
            with pytest.raises(ValueError, match="valid backends"):
                ops.set_default_impl("garbage")
        # the bad call must not have clobbered the default
        assert compiler.current_options().backend == "xla"

    def test_set_autotune_warns_and_scopes(self, tuning_cache):
        with pytest.warns(DeprecationWarning, match="set_autotune"):
            ops.set_autotune(False, cache=tuning_cache)
        try:
            assert ops.autotune_enabled() is False
            assert compiler.current_options().tuning_cache is tuning_cache
        finally:
            with pytest.warns(DeprecationWarning):
                ops.set_autotune(True, cache=None)
        assert ops.autotune_enabled() is True

    def test_compile_op_warns_and_matches_program(self, rng):
        expr, argv = dpia_blas.strategy_dot(256, 64)
        with pytest.warns(DeprecationWarning, match="compile_op"):
            shim_fn = dpia_blas.compile_op(expr, argv, backend="jnp")
        prog_fn = (compiler.Program(expr, argv).check().lower()
                   .compile("jnp", jit=False))
        x = jnp.asarray(rng.randn(256), "float32")
        y = jnp.asarray(rng.randn(256), "float32")
        np.testing.assert_array_equal(np.asarray(shim_fn(x, y)),
                                      np.asarray(prog_fn(x, y)))

    def test_compile_op_unknown_backend(self):
        expr, argv = dpia_blas.strategy_dot(64, 64)
        with pytest.warns(DeprecationWarning):
            with pytest.raises(ValueError, match="registered backends"):
                dpia_blas.compile_op(expr, argv, backend="opencl")
