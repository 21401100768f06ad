"""repro.mesh: descriptors + MeshStrategy, mesh-keyed tuning cache
(regression for the hardcoded mesh="single" keys), collective-aware cost
ranking, mesh resolution through compiler.options, and — in forced-8-device
subprocesses — shardmap op dispatch oracle equality and ShardedEngine
token-identity / zero-recompile acceptance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import autotune, compiler
from repro import mesh as mesh_mod
from repro.autotune import cost
from repro.kernels import dpia_blas, ops
from repro.launch import mesh as mesh_mod_launch
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# descriptors + MeshStrategy (no devices needed)
# ---------------------------------------------------------------------------

class TestDescriptor:
    def test_none_is_single(self):
        assert mesh_mod.descriptor(None) == "single"
        assert mesh_mod.parse_descriptor("single") == {}
        assert mesh_mod.parse_descriptor("") == {}

    def test_mesh_object_round_trip(self):
        m = make_mesh((1,), ("data",))
        d = mesh_mod.descriptor(m)
        assert d == "data=1"
        assert mesh_mod.parse_descriptor(d) == {"data": 1}

    def test_string_passthrough_and_order(self):
        d = "pod=2,data=16,model=16"
        assert mesh_mod.descriptor(d) == d
        assert mesh_mod.parse_descriptor(d) == {"pod": 2, "data": 16,
                                                "model": 16}

    def test_malformed_descriptor_raises(self):
        with pytest.raises(ValueError, match="malformed"):
            mesh_mod.parse_descriptor("data8")

    def test_non_mesh_raises(self):
        with pytest.raises(TypeError, match="Mesh"):
            mesh_mod.descriptor(42)


class TestMeshStrategy:
    def test_validate_ok(self):
        s = mesh_mod.MeshStrategy("data", op="reduce", extent=512)
        assert s.validate({"data": 8}) is s
        assert s.shards({"data": 8}) == 8
        assert s.describe() == "reduce[mesh(data)]"

    def test_validate_missing_axis(self):
        with pytest.raises(ValueError, match="not in mesh"):
            mesh_mod.MeshStrategy("model").validate({"data": 8})

    def test_validate_indivisible_extent(self):
        with pytest.raises(ValueError, match="not divisible"):
            mesh_mod.MeshStrategy("data", extent=100).validate({"data": 8})

    def test_bad_op_rejected(self):
        with pytest.raises(ValueError, match="map.*reduce"):
            mesh_mod.MeshStrategy("data", op="scan")

    def test_params_round_trip(self):
        s = mesh_mod.MeshStrategy("data", op="map", extent=64)
        assert s.params() == {"mesh_axis": "data"}
        back = mesh_mod.MeshStrategy.from_params(s.params(), extent=64)
        assert back.axis == "data"
        assert mesh_mod.MeshStrategy.from_params({"block": 128}) is None


class TestMeshSpace:
    def test_space_only_dividing_axes(self):
        cands = mesh_mod.mesh_space("dot", {"data": 8, "model": 3}, n=1024)
        assert cands, "1024 % 8 == 0 must yield candidates"
        assert all(c.params_dict["mesh_axis"] == "data" for c in cands)

    def test_space_empty_when_nothing_divides(self):
        assert mesh_mod.mesh_space("dot", {"data": 7}, n=64) == []
        assert mesh_mod.mesh_space("dot", {}, n=64) == []

    def test_default_params_and_rebuild(self):
        axes = {"data": 8}
        p = mesh_mod.default_mesh_params("matmul", axes, m=64, k=32, n=16)
        assert p["mesh_axis"] == "data"
        cand = mesh_mod.mesh_candidate_from_params("matmul", p, axes,
                                                   m=64, k=32, n=16)
        expr, argv = cand.build()
        assert len(argv) == 2

    def test_default_params_raises_unshardable(self):
        with pytest.raises(ValueError, match="no mesh axis"):
            mesh_mod.default_mesh_params("dot", {"data": 7}, n=64)

    def test_rebuild_requires_mesh_axis(self):
        with pytest.raises(ValueError, match="mesh_axis"):
            mesh_mod.mesh_candidate_from_params("dot", {"block": 128},
                                                {"data": 8}, n=1024)


# ---------------------------------------------------------------------------
# mesh-keyed tuning cache (satellite: no more hardcoded mesh="single")
# ---------------------------------------------------------------------------

class TestMeshKeyedTuning:
    def test_keys_differ_between_single_and_mesh(self, tuning_cache):
        r1 = autotune.tune("dot", n=1024, mesh="single", measure=False,
                           cache=tuning_cache)
        r2 = autotune.tune("dot", n=1024, mesh="data=8", backend="shardmap",
                           measure=False, cache=tuning_cache)
        assert r1.key != r2.key
        assert r1.key.endswith("|single")
        assert r2.key.endswith("|data=8")
        assert r2.params["mesh_axis"] == "data"
        # both entries live side by side in the persistent cache
        keys = tuning_cache.keys()
        assert r1.key in keys and r2.key in keys

    def test_mesh_params_round_trip_through_cache(self, tuning_cache):
        r1 = autotune.tune("rmsnorm", rows=64, d=32, mesh="data=8",
                           backend="shardmap", measure=False,
                           cache=tuning_cache)
        r2 = autotune.tune("rmsnorm", rows=64, d=32, mesh="data=8",
                           backend="shardmap", measure=False,
                           cache=tuning_cache)
        assert r2.source == "cache"
        assert r2.params == r1.params
        # and the descriptor itself survives in the cache record
        rec = tuning_cache.get(r1.key)
        assert rec["mesh"] == "data=8"

    def test_same_backend_different_mesh_not_shared(self, tuning_cache):
        """The regression: jnp-backend tunings on different meshes must not
        silently share one cache entry."""
        r1 = autotune.tune("dot", n=2048, measure=False, cache=tuning_cache)
        r2 = autotune.tune("dot", n=2048, mesh="data=8", measure=False,
                           cache=tuning_cache)
        assert r1.key != r2.key

    def test_descriptor_only_measure_degrades_to_analytic(self, tuning_cache):
        """measure=True with only a descriptor (no concrete mesh in scope)
        cannot compile shardmap candidates — the search must settle on a
        stable analytic record instead of failing or retrying forever."""
        r = autotune.tune("dot", n=1024, backend="shardmap", mesh="data=8",
                          measure=True, cache=tuning_cache)
        assert r.source == "analytic"
        r2 = autotune.tune("dot", n=1024, backend="shardmap", mesh="data=8",
                           measure=True, cache=tuning_cache)
        assert r2.source == "cache"  # the analytic record is the answer

    def test_ops_tuned_lookup_uses_context_descriptor(self, tuning_cache):
        """kernels.ops._tuned must key by the active mesh descriptor."""
        opts = compiler.CompileOptions(backend="dpia-jnp",
                                       tuning_cache=tuning_cache)
        ops.clear_caches()
        params = ops._tuned("dot", "jnp", opts, n=1024)
        assert params is not None
        assert any(k.endswith("|single") for k in tuning_cache.keys())


# ---------------------------------------------------------------------------
# collective-aware cost ranking
# ---------------------------------------------------------------------------

class TestCollectiveCost:
    def test_big_problem_prefers_mesh(self):
        e_mesh, _ = mesh_mod.mesh_dot(1 << 20, "data", 8)
        e_one, _ = dpia_blas.strategy_dot(1 << 20)
        assert (cost.predicted_seconds(e_mesh)
                < cost.predicted_seconds(e_one))

    def test_small_problem_refuses_mesh(self):
        e_mesh, _ = mesh_mod.mesh_dot(512, "data", 8)
        e_one, _ = dpia_blas.strategy_dot(512, block=512)
        assert (cost.predicted_seconds(e_mesh)
                > cost.predicted_seconds(e_one))

    def test_mesh_reduce_charges_collective(self):
        e_mesh, _ = mesh_mod.mesh_dot(1024, "data", 8)
        est = cost.estimate(e_mesh)
        assert est.collective_steps > 0 and est.ici_bytes > 0
        # a sharded map alone (softmax) needs no collective
        e_map, _ = mesh_mod.mesh_softmax(64, 32, axis="data", shards=8)
        assert cost.estimate(e_map).collective_steps == 0

    def test_collective_terms_survive_add_and_scale(self):
        a = cost.CostEstimate(ici_bytes=8.0, collective_steps=2.0)
        b = (a + a).scaled(2.0)
        assert b.ici_bytes == 32.0 and b.collective_steps == 8.0
        assert b.seconds() > cost.CostEstimate().seconds()


# ---------------------------------------------------------------------------
# mesh resolution through options / dispatch fallback (single device)
# ---------------------------------------------------------------------------

class TestMeshResolution:
    def test_options_carry_mesh_to_shardmap_compile(self, rng):
        """Program.compile('shardmap') resolves the mesh from the active
        options scope — on a 1-device mesh, right here in-process."""
        m1 = make_mesh((1,), ("data",))
        expr, argv = mesh_mod.mesh_dot(64, "data", 1)
        x = jnp.asarray(rng.randn(64), "float32")
        y = jnp.asarray(rng.randn(64), "float32")
        with compiler.options(mesh=m1):
            fn = compiler.Program(expr, argv).compile("shardmap")
        np.testing.assert_allclose(np.asarray(fn(x, y)),
                                   float(jnp.dot(x, y)), rtol=1e-5)

    def test_shardmap_impl_is_valid_options_backend(self):
        opts = compiler.CompileOptions(backend="dpia-shardmap")
        assert opts.dpia_backend == "shardmap"
        assert opts.mesh_descriptor() == "single"

    def test_no_mesh_falls_back_with_warning(self, rng, tuning_cache):
        ops.clear_caches()
        x = jnp.asarray(rng.randn(256), "float32")
        y = jnp.asarray(rng.randn(256), "float32")
        with pytest.warns(RuntimeWarning, match="no mesh"):
            got = ops.dot(x, y, impl="dpia-shardmap",
                          options=compiler.CompileOptions(
                              backend="dpia-shardmap",
                              tuning_cache=tuning_cache))
        np.testing.assert_allclose(np.asarray(got), float(jnp.dot(x, y)),
                                   rtol=1e-4)

    def test_sharded_engine_requires_mesh(self):
        from repro.serve.engine import ShardedEngine
        with pytest.raises(ValueError, match="needs a mesh"):
            ShardedEngine(object(), {}, mesh=None)

    def test_sharded_engine_rejects_explicit_axes(self):
        """jax.make_mesh makes Explicit axes; the engine's NamedSharding
        placement needs Auto ones, and says so before any jit."""
        from jax.sharding import AxisType
        from repro.serve.engine import ShardedEngine
        m = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Explicit,))
        with pytest.raises(ValueError, match="Auto axes.*make_mesh"):
            ShardedEngine(object(), {}, mesh=m)
        assert not mesh_mod_launch.has_explicit_axes(make_mesh((1,),
                                                               ("data",)))


# ---------------------------------------------------------------------------
# forced-8-device acceptance (subprocesses; see conftest.forced_devices)
# ---------------------------------------------------------------------------

SHARD_OPS = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compiler
from repro.kernels import ops
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(1024), "float32")
y = jnp.asarray(rng.randn(1024), "float32")
X = jnp.asarray(rng.randn(16, 64), "float32")
w = jnp.asarray(rng.randn(64), "float32")
A = jnp.asarray(rng.randn(32, 48), "float32")
B = jnp.asarray(rng.randn(48, 24), "float32")

with compiler.options(backend="dpia-shardmap", mesh=mesh):
    pairs = [
        ("dot", ops.dot(x, y), ops.dot(x, y, impl="xla")),
        ("asum", ops.asum(x), ops.asum(x, impl="xla")),
        ("scal", ops.scal(2.5, x), ops.scal(2.5, x, impl="xla")),
        ("matmul", ops.matmul(A, B), ops.matmul(A, B, impl="xla")),
        ("rmsnorm", ops.rmsnorm(X, w), ops.rmsnorm(X, w, impl="xla")),
        ("softmax", ops.softmax(X), ops.softmax(X, impl="xla")),
    ]
for name, got, want in pairs:
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3, err_msg=name)

# every one of the six went through a mesh-keyed shardmap executor
mesh_keys = [k for k in compiler.executor_cache().keys()
             if "|shardmap|data=8|" in k]
assert len(mesh_keys) == 6, mesh_keys

# the all-reduce in the lowered dot is dictated by the strategy: exactly one
from repro import mesh as mesh_mod
expr, argv = mesh_mod.mesh_dot(1024, "data", 8)
fn = compiler.Program(expr, argv).compile("shardmap", mesh=mesh)
import re
hlo = jax.jit(fn).lower(x, y).compile().as_text()
n_ar = len(re.findall(r"=\s*\S+\s+all-reduce(?:-start)?\(", hlo))
assert n_ar == 1, f"expected ONE all-reduce, found {n_ar}"

# mesh executors never reach the AOT store (they cannot be rebuilt without
# a mesh) and a store containing only single-device programs loads cleanly
import tempfile
d = tempfile.mkdtemp()
store = compiler.executor_cache()
n_written = store.save_aot(d)
fresh = compiler.ExecutorCache()
assert fresh.load_aot(d) == n_written
assert not any("|shardmap|" in k for k in fresh.keys()), fresh.keys()

# measured refinement DOES run for the mesh space when the concrete mesh
# matches the descriptor
from repro import autotune
r = autotune.tune("dot", n=1024, backend="shardmap", mesh=mesh,
                  measure=True, top_k=2, iters=2, force=True)
assert r.source == "measured", r.source
assert r.params.get("mesh_axis") == "data", r.params
print("MESH_OPS_OK")
"""


ENGINE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve.engine import ContinuousEngine, ShardedEngine, Request
from repro.launch.mesh import make_mesh

cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=128, max_seq=64)
model = Model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

def reqs():
    rng = np.random.RandomState(1)
    spec = [(3, 7, 0.0, 0), (9, 5, 0.8, 4), (5, 12, 0.0, 0),
            (12, 3, 1.2, 0), (4, 9, 0.0, 0)]
    return [Request(jnp.asarray(rng.randint(0, 128, (l,)), jnp.int32),
                    max_new_tokens=m, temperature=t, top_k=k)
            for l, m, t, k in spec]

mesh = make_mesh((8,), ("data",))
key = jax.random.PRNGKey(7)
cont = ContinuousEngine(model, params, max_seq=64, slots=8, chunk=4)
want = cont.run(reqs(), key=key)
sh = ShardedEngine(model, params, max_seq=64, slots=8, chunk=4, mesh=mesh)
got = sh.run(reqs(), key=key)
assert got == want, (got, want)

# zero recompiles after warm-up: more traffic, same single chunk compile
n0 = sh.decode_cache_misses()
assert sh.run(reqs(), key=key) == want
assert sh.decode_cache_misses() == n0 == 1, (n0, sh.decode_cache_misses())

# the decode state really is sharded over the mesh
assert len(sh.tokens.sharding.device_set) == 8, sh.tokens.sharding
print("SHARDED_ENGINE_OK")
"""


def test_shardmap_ops_match_oracle_subprocess(forced_devices):
    """Acceptance: all six tuned ops dispatch through dpia-shardmap on a
    forced-8-device CPU mesh and match the single-device oracle, with
    mesh-keyed executors and the strategy-dictated single all-reduce."""
    r = forced_devices(SHARD_OPS)
    assert "MESH_OPS_OK" in r.stdout, r.stdout + r.stderr


def test_sharded_engine_token_identical_subprocess(forced_devices):
    """Acceptance: ShardedEngine decode is token-identical to
    ContinuousEngine on a 1-axis mesh and reports zero recompiles after
    warm-up."""
    r = forced_devices(ENGINE)
    assert "SHARDED_ENGINE_OK" in r.stdout, r.stdout + r.stderr
