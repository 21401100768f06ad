"""Integration: train loop end-to-end (loss decreases, resume bit-exact,
NaN-step skipped), serving engine, strategy rewrites (property-based)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.ckpt.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.ft.resilience import TrainLoop
from repro.launch.mesh import make_mesh
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.train.step import make_train_state, make_train_step, state_specs


def tiny_cfg(**kw):
    base = dict(name="tiny", family="dense", n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab=64, dtype="float32",
                remat=False, max_seq=32)
    base.update(kw)
    return ModelConfig(**base)


def build(cfg, steps=50, microbatches=1):
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    state = make_train_state(model, jax.random.PRNGKey(0))
    st_spec = state_specs(state, mesh, cfg)
    _, jit_with, _ = make_train_step(model, mesh, base_lr=1e-2, warmup=5,
                                     total_steps=steps,
                                     microbatches=microbatches,
                                     donate=False)  # tests reuse states
    step = jit_with(st_spec)

    def wrapped(state, batch):
        return step(state, {k: jnp.asarray(v) for k, v in batch.items()})
    return model, state, wrapped


class TestTraining:
    def test_loss_decreases(self, tmp_path):
        cfg = tiny_cfg()
        model, state, step = build(cfg, steps=60)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=4))
        losses = []
        loop = TrainLoop(step, CheckpointManager(str(tmp_path)), data,
                         ckpt_every=1000)
        loop.run(state, num_steps=60,
                 on_metrics=lambda s, m: losses.append(float(m["loss"])))
        assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.2, \
            f"not learning: {losses[:3]} -> {losses[-3:]}"

    def test_microbatch_accumulation_close_to_full_batch(self):
        cfg = tiny_cfg()
        model, state, step1 = build(cfg, microbatches=1)
        _, _, step2 = build(cfg, microbatches=2)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=4))
        batch, _ = next(data.iterator())
        s1, m1 = step1(state, batch)
        s2, m2 = step2(state, batch)
        # same data, same init -> losses match; grads close (bf16 accumulate)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-3)
        np.testing.assert_allclose(float(m1["grad_norm"]),
                                   float(m2["grad_norm"]), rtol=0.05)

    def test_resume_bit_exact(self, tmp_path):
        """20 straight steps == 10 steps + checkpoint + restore + 10 steps."""
        cfg = tiny_cfg()
        model, state0, step = build(cfg, steps=20)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=4))

        # path A: straight through
        mgrA = CheckpointManager(str(tmp_path / "a"), async_save=False)
        loopA = TrainLoop(step, mgrA, data, ckpt_every=1000)
        stateA = loopA.run(state0, num_steps=20)

        # path B: stop at 10 (checkpointed), then resume to 20
        mgrB = CheckpointManager(str(tmp_path / "b"), async_save=False)
        loopB = TrainLoop(step, mgrB, data, ckpt_every=10)
        stateB_mid = loopB.run(state0, num_steps=10)
        loopB2 = TrainLoop(step, mgrB, data, ckpt_every=10)
        stateB = loopB2.run(state0, num_steps=20)  # auto-restores step 10

        wa = jax.tree_util.tree_leaves(stateA["params"])
        wb = jax.tree_util.tree_leaves(stateB["params"])
        for a, b in zip(wa, wb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_nan_guard_skips_update(self, tmp_path):
        cfg = tiny_cfg()
        model, state, step = build(cfg)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=4))
        calls = {"n": 0}

        def poisoned(state, batch):
            calls["n"] += 1
            new_state, m = step(state, batch)
            if calls["n"] == 3:
                m = dict(m, loss=jnp.float32(float("nan")))
            return new_state, m

        loop = TrainLoop(poisoned, CheckpointManager(str(tmp_path)), data,
                         ckpt_every=1000)
        loop.run(state, num_steps=6)
        assert loop.skipped_steps == 1


class TestServing:
    def test_batched_engine_runs(self):
        from repro.serve.engine import BatchedEngine, Request
        cfg = tiny_cfg()
        model = Model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        engine = BatchedEngine(model, params, max_seq=32)
        reqs = [Request(prompt=jnp.arange(5) % cfg.vocab, max_new_tokens=6),
                Request(prompt=jnp.arange(8) % cfg.vocab, max_new_tokens=6)]
        outs = engine.run(reqs)
        assert len(outs) == 2 and all(len(o) == 6 for o in outs)
        assert all(0 <= t < cfg.vocab for o in outs for t in o)


# ---------------------------------------------------------------------------
# strategy rewrites preserve semantics (property-based)
# ---------------------------------------------------------------------------

from repro.core.dpia import interp, phrases as P, strategies  # noqa: E402
from repro.core.dpia.types import Arr, Num  # noqa: E402


@settings(max_examples=20, deadline=None)
@given(n=st.sampled_from([8, 12, 16, 24]),
       b=st.sampled_from([2, 4]),
       seed=st.integers(0, 2 ** 16))
def test_split_join_rewrite_preserves_semantics(n, b, seed):
    if n % b:
        return
    rng = np.random.RandomState(seed)
    xs = P.var_exp("xs", Arr(n, Num()))
    m = P.Map(lambda x: P.add(P.mul(x, x), P.lit(1.0)), xs)
    rewritten = strategies.split_join(m, b)
    env = {"xs": jnp.asarray(rng.randn(n), "float32")}
    np.testing.assert_allclose(np.asarray(interp.interp(m, env)),
                               np.asarray(interp.interp(rewritten, env)),
                               rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(n=st.sampled_from([8, 16, 32]), b=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 2 ** 16))
def test_blocked_reduce_rewrite_preserves_semantics(n, b, seed):
    rng = np.random.RandomState(seed)
    xs = P.var_exp("xs", Arr(n, Num()))
    r = P.Reduce(lambda x, a: P.add(a, x), P.lit(0.0), xs)
    rewritten = strategies.blocked_reduce(r, b)
    env = {"xs": jnp.asarray(rng.randn(n), "float32")}
    np.testing.assert_allclose(np.asarray(interp.interp(r, env)),
                               np.asarray(interp.interp(rewritten, env)),
                               rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_fuse_and_vectorize_preserve_semantics(seed):
    rng = np.random.RandomState(seed)
    n = 32
    xs = P.var_exp("xs", Arr(n, Num()))
    r = P.Reduce(lambda x, a: P.add(a, x), P.lit(0.0),
                 P.Map(lambda x: P.mul(x, x), xs))
    env = {"xs": jnp.asarray(rng.randn(n), "float32")}
    fused = strategies.fuse_map_into_reduce(r)
    np.testing.assert_allclose(np.asarray(interp.interp(r, env)),
                               np.asarray(interp.interp(fused, env)),
                               rtol=1e-4)
    m = P.Map(lambda x: P.mul(x, P.lit(3.0)), xs)
    vec = strategies.vectorize(m, 8)
    np.testing.assert_allclose(np.asarray(interp.interp(m, env)),
                               np.asarray(interp.interp(vec, env)),
                               rtol=1e-5)
