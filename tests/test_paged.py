"""Paged KV-cache + chunked prefill tests: block-pool accounting, paged ==
dense token identity across model families, chunked == single-call prefill
identity, recompile discipline, and the KV-layout planner."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve.engine import BatchedEngine, ContinuousEngine, Request
from repro.serve.paged import (BlockPool, blocks_for, dense_kv_bytes,
                               paged_kv_bytes, table_row)


def tiny_cfg(**kw):
    base = dict(name="paged-t", family="dense", n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab=128, dtype="float32",
                remat=False, max_seq=64)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def dense_model():
    cfg = tiny_cfg()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def mixed_requests(cfg, n=6, key=None):
    key = key if key is not None else jax.random.PRNGKey(5)
    temps = [0.0, 0.9, 0.0, 1.3, 0.7, 0.0]
    top_ks = [0, 5, 0, 0, 3, 0]
    return [Request(
        prompt=jax.random.randint(jax.random.fold_in(key, 100 + i),
                                  (5 + 3 * i,), 0, cfg.vocab),
        max_new_tokens=4 + 3 * i, temperature=temps[i % 6],
        top_k=top_ks[i % 6]) for i in range(n)]


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_blocks_for(self):
        assert blocks_for(1, 16) == 1
        assert blocks_for(16, 16) == 1
        assert blocks_for(17, 16) == 2
        assert blocks_for(0, 16) == 1          # a slot always holds a page

    def test_alloc_free_roundtrip(self):
        pool = BlockPool(8, 16)
        a = pool.alloc("a", 3)
        b = pool.alloc("b", 2)
        assert a == [0, 1, 2] and b == [3, 4]  # deterministic ascending
        assert pool.free_blocks == 3 and pool.used_blocks == 5
        assert pool.free("a") == 3
        assert pool.free_blocks == 6
        # freed pages are reused first (LIFO), still deterministic
        assert pool.alloc("c", 1) == [0]
        assert pool.free("b") == 2 and pool.free("c") == 1
        assert pool.free_blocks == 8

    def test_exhaustion_raises_and_free_is_idempotent(self):
        pool = BlockPool(2, 16)
        pool.alloc("a", 2)
        assert not pool.can_alloc(1)
        with pytest.raises(ValueError):
            pool.alloc("b", 1)
        assert pool.free("a") == 2
        assert pool.free("a") == 0             # double-free: no-op

    def test_table_row_sentinel_padding(self):
        assert table_row([4, 7], 4, sentinel=9) == [4, 7, 9, 9]
        with pytest.raises(ValueError):
            table_row([1, 2, 3], 2, sentinel=9)

    def test_byte_accounting_family_aware(self):
        cfg = tiny_cfg()
        dense = dense_kv_bytes(cfg, slots=4, max_seq=64)
        assert dense == 2 * 2 * 4 * 64 * 2 * 8 * 4  # 2kv*L*slots*seq*nkv*hd*4B
        assert paged_kv_bytes(cfg, n_blocks=8, block_size=16) < dense
        ssm = tiny_cfg(family="ssm", name="paged-ssm")
        assert dense_kv_bytes(ssm, 4, 64) == 0   # no KV cache at all


# ---------------------------------------------------------------------------
# paged engine == dense oracle, all families
# ---------------------------------------------------------------------------

class TestPagedVsDense:
    def test_token_identical_dense_family(self, dense_model):
        """Mixed lengths/budgets/temperatures, fewer slots than requests:
        the paged engine must be token-identical to the dense oracle, with
        one decode compile and zero recompiles across reuse."""
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(7)
        reqs = mixed_requests(cfg)
        oracle = BatchedEngine(model, params, max_seq=64,
                               chunk=4).run(reqs, key=key)
        paged = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                                 min_bucket=8, kv_layout="paged",
                                 block_size=16)
        assert paged.run(reqs, key=key) == oracle
        assert paged.run(reqs, key=key) == oracle      # engine reuse
        assert paged.decode_cache_misses() == 1

    def test_token_identical_reordered_traffic(self, dense_model):
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(3)
        reqs = [r for r in mixed_requests(cfg) if r.temperature == 0.0]
        paged = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                                 min_bucket=8, kv_layout="paged")
        a = paged.run(reqs, key=key)
        b = paged.run(list(reversed(reqs)), key=key)
        assert a == list(reversed(b))

    @pytest.mark.parametrize("name", ["rwkv6-1.6b", "zamba2-2.7b"])
    def test_token_identical_recurrent_families(self, name):
        """ssm (no KV at all) and hybrid (paged shared-attention KV +
        slot-indexed mamba state) both stay token-identical."""
        from repro.configs import smoke_config
        cfg = smoke_config(name)
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(9)
        reqs = [Request(
            prompt=jax.random.randint(jax.random.fold_in(key, 40 + i),
                                      (3 + 4 * i,), 0, cfg.vocab),
            max_new_tokens=4 + 2 * i,
            temperature=(0.8 if i == 1 else 0.0)) for i in range(3)]
        oracle = BatchedEngine(model, params, max_seq=64,
                               chunk=4).run(reqs, key=key)
        paged = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                                 min_bucket=8, kv_layout="paged",
                                 block_size=16)
        assert paged.run(reqs, key=key) == oracle
        assert paged.decode_cache_misses() == 1

    def test_no_block_leak_across_cycles(self, dense_model):
        """Free-block count returns to initial after N admit/retire cycles,
        and the staging/bookkeeping dicts drain."""
        cfg, model, params = dense_model
        paged = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                                 min_bucket=8, kv_layout="paged")
        init_free = paged.pool.free_blocks
        for k in range(3):
            paged.run(mixed_requests(cfg, n=4), key=jax.random.PRNGKey(k))
        assert paged.pool.free_blocks == init_free
        assert paged.pool.used_blocks == 0
        assert paged._staging == {} and paged._admit_logits == {}
        assert paged._requests == {} and paged.sched.outputs == {}

    def test_block_starved_pool_defers_but_stays_identical(self, dense_model):
        """A pool that can only hold one request span at a time serialises
        admissions (FIFO, no head-of-line skipping) — throughput policy,
        never tokens."""
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(7)
        reqs = mixed_requests(cfg, n=4)
        oracle = BatchedEngine(model, params, max_seq=64,
                               chunk=4).run(reqs, key=key)
        starved = ContinuousEngine(model, params, max_seq=64, slots=2,
                                   chunk=4, min_bucket=8, kv_layout="paged",
                                   block_size=16, kv_blocks=4)
        assert starved.run(reqs, key=key) == oracle
        assert starved.pool.free_blocks == 4

    def test_oversized_request_rejected_up_front(self, dense_model):
        cfg, model, params = dense_model
        paged = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                                 min_bucket=8, kv_layout="paged",
                                 block_size=16, kv_blocks=2)
        with pytest.raises(ValueError, match="KV blocks"):
            paged.submit(Request(prompt=jnp.arange(40) % cfg.vocab,
                                 max_new_tokens=8))

    def test_block_size_must_divide_max_seq(self, dense_model):
        cfg, model, params = dense_model
        with pytest.raises(ValueError, match="divide"):
            ContinuousEngine(model, params, max_seq=60, slots=2,
                             kv_layout="paged", block_size=16)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def _long_reqs(self, cfg, key):
        return [Request(prompt=jax.random.randint(
                    jax.random.fold_in(key, 70 + i), (29 + 12 * i,), 0,
                    cfg.vocab),
                        max_new_tokens=5,
                        temperature=(1.1 if i == 1 else 0.0),
                        top_k=(4 if i == 1 else 0)) for i in range(2)]

    @pytest.mark.parametrize("kv_layout", ["dense", "paged"])
    def test_chunked_equals_single_call(self, dense_model, kv_layout):
        """Prompts longer than ``prefill_chunk`` are split across chunk
        boundaries; tokens must match the single-call oracle exactly."""
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(13)
        reqs = self._long_reqs(cfg, key)
        oracle = BatchedEngine(model, params, max_seq=64,
                               chunk=4).run(reqs, key=key)
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               min_bucket=8, kv_layout=kv_layout,
                               prefill_chunk=16)
        assert eng.buckets[-1] == 16           # big buckets are GONE
        assert eng.run(reqs, key=key) == oracle

    @pytest.mark.parametrize("name", ["rwkv6-1.6b", "zamba2-2.7b"])
    def test_chunked_recurrent_families(self, name):
        """Chunked prefill carries the recurrent state (conv tail, wkv/ssm
        state) across chunk boundaries bitwise."""
        from repro.configs import smoke_config
        cfg = smoke_config(name)
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(21)
        reqs = [Request(prompt=jax.random.randint(
                    jax.random.fold_in(key, 3), (27,), 0, cfg.vocab),
                        max_new_tokens=6)]
        oracle = BatchedEngine(model, params, max_seq=64,
                               chunk=4).run(reqs, key=key)
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               min_bucket=8, kv_layout="paged",
                               prefill_chunk=8)
        assert eng.run(reqs, key=key) == oracle

    def test_tail_bucket_overrun_does_not_corrupt(self, dense_model):
        """A tail chunk whose padded bucket overruns max_seq (non-power-of-
        two max_seq: 97-token prompt, chunks 64+64-padded into a 100-wide
        cache) must DROP the out-of-range rows — regression test for the
        dynamic_update_slice clamp that silently clobbered positions
        36..63."""
        cfg, model, params = dense_model
        key = jax.random.PRNGKey(17)
        reqs = [Request(prompt=jax.random.randint(jax.random.PRNGKey(4),
                                                  (97,), 0, cfg.vocab),
                        max_new_tokens=3)]
        oracle = BatchedEngine(model, params, max_seq=100,
                               chunk=4).run(reqs, key=key)
        eng = ContinuousEngine(model, params, max_seq=100, slots=2, chunk=4,
                               min_bucket=16, prefill_chunk=64)
        assert eng.run(reqs, key=key) == oracle

    def test_zero_recompiles_after_chunked_warmup(self, dense_model):
        """One warm pass over short + long prompts closes the executable
        set: further long-prompt traffic hits the caches exactly."""
        cfg, model, params = dense_model
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               min_bucket=8, kv_layout="paged",
                               prefill_chunk=16)
        key = jax.random.PRNGKey(0)
        # the executable set is (bucket x first/continuation): warm every
        # bucket in both roles (21 = 16-chunk + 5-tail covers 8-cont)
        warm = [Request(prompt=jnp.arange(n) % cfg.vocab, max_new_tokens=3)
                for n in (5, 12, 21, 29, 47)]
        eng.run(warm, key=key)
        decode0 = eng.decode_cache_misses()
        prefill0 = eng.prefill_cache_size()
        traffic = [Request(prompt=jnp.arange(7 * i + 3) % cfg.vocab,
                           max_new_tokens=2 + i, temperature=0.3 * i)
                   for i in range(1, 8)]
        eng.run(traffic, key=jax.random.PRNGKey(1))
        assert eng.decode_cache_misses() == decode0 == 1
        assert eng.prefill_cache_size() == prefill0


# ---------------------------------------------------------------------------
# model level: paged decode is bitwise the dense computation
# ---------------------------------------------------------------------------

class TestPagedModelLevel:
    def test_paged_decode_bitwise_equals_dense(self, dense_model):
        cfg, model, params = dense_model
        max_seq, bs = 64, 16
        p = jax.random.randint(jax.random.PRNGKey(2), (10,), 0, cfg.vocab)
        lg_d, dense = model.prefill(params, p[None],
                                    model.init_cache(1, max_seq))
        paged_cache = model.init_paged_cache(1, max_seq, n_blocks=6,
                                             block_size=bs)
        kv, st = model.split_paged_cache(paged_cache)
        bt_row = jnp.arange(4, dtype=jnp.int32)
        lg_p, kv, st = model.prefill_paged(params, p[None], kv, bt_row,
                                           model.init_prefill_state(1),
                                           0, jnp.asarray([10]), first=True)
        np.testing.assert_array_equal(np.asarray(lg_d), np.asarray(lg_p))
        cache_p = model.merge_paged_cache(kv, st)
        tok = jnp.argmax(lg_d, -1)[:, None]
        pos = jnp.asarray([10], jnp.int32)
        for _ in range(3):
            ld, dense = model.decode_step(params, tok, dense, pos)
            lp, cache_p = model.decode_step(params, tok, cache_p, pos,
                                            block_tables=bt_row[None])
            np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
            tok = jnp.argmax(ld, -1)[:, None]
            pos = pos + 1

    def test_out_of_table_write_drops(self, dense_model):
        """A lane parked past max_seq maps to the sentinel page: the pool
        is untouched (the paged twin of the dense mode='drop' scatter)."""
        cfg, model, params = dense_model
        paged_cache = model.init_paged_cache(1, 64, n_blocks=4,
                                             block_size=16)
        kv, _ = model.split_paged_cache(paged_cache)
        before = np.asarray(kv.k).copy()
        tok = jnp.zeros((1, 1), jnp.int32)
        bt = jnp.full((1, 4), 4, jnp.int32)    # all-sentinel table
        _, cache2 = model.decode_step(params, tok, paged_cache,
                                      jnp.asarray([64], jnp.int32),
                                      block_tables=bt)
        kv2, _ = model.split_paged_cache(cache2)
        np.testing.assert_array_equal(before, np.asarray(kv2.k))


# ---------------------------------------------------------------------------
# the KV-layout planner + per-platform HW presets
# ---------------------------------------------------------------------------

class TestKvLayoutPlanner:
    def test_presets_exist_per_platform(self):
        from repro.autotune import PEAKS, hw_model
        v5e = hw_model("TPU v5 lite")
        assert (v5e.peak_flops, v5e.hbm_bw, v5e.hbm_capacity) == \
            (197e12, 819e9, 16e9)
        assert hw_model("cpu").hbm_bw < v5e.hbm_bw
        with pytest.raises(ValueError, match="no published peaks"):
            hw_model("no-such-platform")
        assert hw_model() is PEAKS["cpu"]

    def test_paged_shrinks_resident_never_traffic(self):
        from repro.autotune import kv_layout_cost
        kw = dict(slots=8, max_seq=4096, kv_heads=8, head_dim=128, layers=32,
                  dtype_bytes=2, block_size=16, expected_seq=512)
        dense = kv_layout_cost("dense", **kw)
        paged = kv_layout_cost("paged", **kw)
        assert paged.resident_bytes < dense.resident_bytes / 2
        assert paged.step_hbm_bytes >= dense.step_hbm_bytes

    def test_picks_dense_small_paged_huge(self, dense_model, tmp_path):
        from repro import autotune
        cfg, _, _ = dense_model
        cpath = str(tmp_path / "plan.json")
        small = autotune.pick_kv_layout(cfg, slots=2, max_seq=64,
                                        platform="TPU v5 lite", cache=cpath)
        assert small["layout"] == "dense"
        big_cfg = tiny_cfg(name="paged-big", n_layers=32, d_model=4096,
                           n_heads=32, n_kv_heads=8, max_seq=131072)
        big = autotune.pick_kv_layout(big_cfg, slots=256, max_seq=131072,
                                      expected_seq=4096,
                                      platform="TPU v5 lite", cache=cpath)
        assert big["layout"] == "paged"
        assert big["dense_bytes"] > big["paged_bytes"]

    def test_decision_is_cached(self, dense_model, tmp_path):
        from repro import autotune
        cfg, _, _ = dense_model
        cpath = str(tmp_path / "tune.json")
        a = autotune.pick_kv_layout(cfg, slots=2, max_seq=64,
                                    platform="TPU v5 lite", cache=cpath)
        b = autotune.pick_kv_layout(cfg, slots=2, max_seq=64,
                                    platform="TPU v5 lite", cache=cpath)
        assert a == b
        cache = autotune.TuningCache(cpath)
        assert any(k.startswith("kv_layout|") for k in cache.keys())

    def test_auto_engine_resolves_layout(self, dense_model, tmp_path):
        cfg, model, params = dense_model
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               min_bucket=8, kv_layout="auto",
                               tuning_cache=str(tmp_path / "t.json"),
                               aot=False)
        assert eng.kv_layout in ("dense", "paged")


# ---------------------------------------------------------------------------
# layout as a cache-key dimension
# ---------------------------------------------------------------------------

class TestLayoutKeys:
    def test_executor_key_carries_layout(self):
        from repro.compiler import executors
        k_dense = executors.make_key("matmul", {"m": 8, "k": 8, "n": 8},
                                     "jnp")
        k_paged = executors.make_key("matmul", {"m": 8, "k": 8, "n": 8},
                                     "jnp", layout="paged")
        assert k_dense != k_paged and "|paged|" in k_paged

    def test_tuning_key_layout_only_when_non_default(self):
        from repro.autotune import cache as cache_mod
        base = cache_mod.make_key("dot", {"n": 64})
        assert "layout" not in base            # pre-paged keys unchanged
        paged = cache_mod.make_key("dot", {"n": 64}, layout="paged")
        assert paged == base + "|layout=paged"

    def test_options_validate_kv_layout(self):
        from repro import compiler
        with compiler.options(kv_layout="paged") as o:
            assert o.kv_layout == "paged"
        with pytest.raises(ValueError):
            compiler.CompileOptions(kv_layout="ragged")
