"""Prefill attention through the Pallas flash kernel, forced on the CPU.

On a TPU ``models.attention.attend`` takes the kernel wherever the
sequence tiles; on the CPU it keeps the jnp paths.  These tests add the
CPU to ``attention.FLASH_PLATFORMS``, so the kernel runs in interpret mode,
and hold it to the jnp paths it replaces: ``attention()`` at yi-9b's and
granite-4.0-h's head shapes, and the first token a paged prefill samples
(with the engine's ``attn`` attribute and its counter)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.models import attention
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve.engine import ContinuousEngine, Request

# yi-9b: GQA 8:1 at head_dim 128, roped; granite-4.0-h: 4:1 at 64, NoPE,
# scores scaled by 1/64
YI = dict(n_heads=32, n_kv_heads=4, head_dim=128)
GRANITE = dict(n_heads=32, n_kv_heads=8, head_dim=64,
               attention_multiplier=1 / 64, rope=False)


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    monkeypatch.setattr(attention, "FLASH_PLATFORMS", ("tpu", "cpu"))


def _cfg(dtype="bfloat16", **kw):
    base = dict(name="flash-tiny", family="dense", n_layers=2, d_model=256,
                n_heads=4, n_kv_heads=2, head_dim=128, d_ff=512, vocab=256,
                max_seq=1024, dtype=dtype)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("widths,s,path,length", [
    (YI, 512, "dense", None),
    (YI, 2048, "chunked", 1900),
    (GRANITE, 1024, "dense", 1000),
    (GRANITE, 2048, "chunked", None),
], ids=["yi-dense", "yi-chunked", "granite-dense", "granite-chunked"])
def test_attention_kernel_matches_the_jnp_paths(monkeypatch, widths, s,
                                                path, length):
    cfg = _cfg(**widths)
    p = attention.init_attn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, s, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.arange(s)[None]
    lengths = None if length is None else jnp.asarray([length], jnp.int32)
    assert attention.prefill_attention(cfg, s, "cpu") == path
    want = jax.jit(lambda p, x: attention.attention(p, cfg, x, pos))(p, x)
    monkeypatch.setattr(attention, "FLASH_PLATFORMS", ("tpu", "cpu"))
    assert attention.prefill_attention(cfg, s, "cpu") == "flash_kernel"
    assert attention.prefill_attention(cfg, s, "cpu", 4) == path
    got = jax.jit(lambda p, x: attention.attention(p, cfg, x, pos,
                                                   lengths))(p, x)
    assert got.dtype == want.dtype == jnp.bfloat16
    n = length or s
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = np.abs(want[:, :n]).max()
    assert np.abs(got[:, :n] - want[:, :n]).max() <= 2e-2 * scale


def test_the_kernel_path_differentiates_as_the_jnp_path(kernel_on_cpu):
    cfg = _cfg(dtype="float32")
    p = attention.init_attn(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, cfg.d_model))

    def loss(p):
        return (attention.attention(p, cfg, x, jnp.arange(256)[None])
                ** 2).sum()
    got = jax.grad(loss)(p)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "FLASH_PLATFORMS", ("tpu",))
        want = jax.grad(loss)(p)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def _granite_tiny():
    return _cfg(dtype="float32", family="hybrid", n_layers=4, d_model=128,
                n_heads=4, n_kv_heads=2, head_dim=64, ssm_state=16,
                layer_types=("mamba", "attention") * 2, mamba_n_heads=4,
                mamba_d_head=32, mamba_conv_bias=True, mamba_chunk=16,
                embedding_multiplier=12.0, residual_multiplier=0.22,
                attention_multiplier=1 / 64, logits_scaling=8.0, rope=False)


def _serve(model, params):
    """Paged prefill of prompts in the 128, 256 and 512 buckets, one
    token each; the tokens and the prefill chunks' ``attn`` attributes."""
    obs.metrics_reset()
    obs.clear_trace()
    obs.enable()
    try:
        eng = ContinuousEngine(model, params, max_seq=512, slots=2,
                               kv_layout="paged", min_bucket=128)
        reqs = [Request(prompt=(np.arange(n) * 7 % 256).astype(np.int32),
                        max_new_tokens=1) for n in (100, 200, 300, 130)]
        out = eng.run(reqs)
        spans = [e["args"].get("attn") for e in obs.trace_events()
                 if e.get("name") == "serve.prefill_chunk"]
        return out, spans
    finally:
        obs.disable()
        obs.clear_trace()


@pytest.mark.parametrize("family", ["dense", "granite"])
def test_paged_prefill_through_the_kernel_samples_the_same_first_token(
        monkeypatch, family):
    cfg = _granite_tiny() if family == "granite" else _cfg(dtype="float32")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    want, spans = _serve(model, params)
    assert spans == ["dense"] * 4
    assert obs.counter("serve.prefill_attn.flash_kernel").value == 0
    monkeypatch.setattr(attention, "FLASH_PLATFORMS", ("tpu", "cpu"))
    got, spans = _serve(model, params)
    assert spans == ["flash_kernel"] * 4
    assert obs.counter("serve.prefill_attn.flash_kernel").value == 4
    assert got == want
