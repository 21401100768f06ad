"""The Pallas paged decode kernel (interpret mode here) against the
gathered-view path it replaces on one TPU: the kernel alone against
``_attend_token`` over ``gather_paged_view``, the decode function and the
fused chunk against the view path, and the ``attn`` span attribute and
the counter that say which path a chunk ran."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels.paged_decode import paged_decode_attention
from repro.models import attention
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.serve.engine import ContinuousEngine, Request, decode_attention

BS, HD = 16, 128


def _pools(key, layers, n_blocks, nkv, dtype=jnp.bfloat16):
    kk, kv = jax.random.split(key)
    shape = (layers, n_blocks, BS, nkv, HD)
    return (jax.random.normal(kk, shape, jnp.float32).astype(dtype),
            jax.random.normal(kv, shape, jnp.float32).astype(dtype))


def _view_attention(q, kp, vp, layer, lengths, bt):
    """``_attend_token`` over the gathered view, with an identity ``wo`` in
    float32: the jnp decode math the kernel replaces."""
    b, nh, hd = q.shape
    cfg = types.SimpleNamespace(n_heads=nh, n_kv_heads=kp.shape[3], hd=hd)
    vk, vv = attention.gather_paged_view(kp, vp, bt)
    out = attention._attend_token(cfg, q[:, None], vk[layer], vv[layer],
                                  lengths - 1, True, jnp.float32,
                                  jnp.eye(nh * hd, dtype=jnp.float32))
    return out.reshape(b, nh, hd)


@pytest.mark.parametrize("nh,nkv", [(8, 2), (16, 2)], ids=["gqa4", "gqa8"])
def test_kernel_matches_attend_token_over_the_view(nh, nkv):
    layers, n_blocks, max_blocks = 3, 64, 8             # capacity 128
    kp, vp = _pools(jax.random.PRNGKey(0), layers, n_blocks, nkv)
    q = jax.random.normal(jax.random.PRNGKey(1), (7, nh, HD),
                          jnp.float32).astype(jnp.bfloat16)
    bt = jax.random.permutation(jax.random.PRNGKey(2), n_blocks)[
        :7 * max_blocks].reshape(7, max_blocks).astype(jnp.int32)
    bt = bt.at[6, 3:].set(n_blocks)                     # sentinel-padded tail
    lengths = jnp.asarray([1, 15, 16, 17, 70, max_blocks * BS, 40],
                          jnp.int32)
    for layer in (0, 2):
        out = paged_decode_attention(q, kp, vp, jnp.int32(layer), lengths, bt)
        ref = _view_attention(q, kp, vp, layer, lengths, bt)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)


def test_kernel_float32_pool_and_block_boundaries():
    """A float32 pool is read row by row (no pairing); lengths on and
    around the 128-position block edge."""
    kp, vp = _pools(jax.random.PRNGKey(3), 2, 40, 2, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(4), (4, 4, HD), jnp.float32)
    bt = jnp.arange(40, dtype=jnp.int32)[:36].reshape(4, 9)
    lengths = jnp.asarray([127, 128, 129, 144], jnp.int32)
    out = paged_decode_attention(q, kp, vp, jnp.int32(1), lengths, bt)
    ref = _view_attention(q, kp, vp, 1, lengths, bt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kernel_reads_heads_of_64_packed_two_a_row(dtype):
    """granite-4.0-h's heads of 64 lanes, two to a 128-lane pool row, with
    its score scale of 1/64 (not 1/sqrt(64)): each head is read from its
    half of the row."""
    nh, nkv, hd, layers, n_blocks, max_blocks = 8, 4, 64, 2, 48, 6
    cfg = types.SimpleNamespace(n_heads=nh, n_kv_heads=nkv, hd=hd,
                                attention_multiplier=1 / 64)
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(11), 3)
    shape = (layers, n_blocks, BS, nkv, hd)
    kp = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
    vp = jax.random.normal(kv, shape, jnp.float32).astype(dtype)
    q = jax.random.normal(kq, (5, nh, hd), jnp.float32).astype(dtype)
    bt = jax.random.permutation(jax.random.PRNGKey(12), n_blocks)[
        :5 * max_blocks].reshape(5, max_blocks).astype(jnp.int32)
    lengths = jnp.asarray([1, 16, 17, 90, max_blocks * BS], jnp.int32)
    packed = lambda a: a.reshape(a.shape[:3] + (nkv // 2, 2 * hd))  # noqa
    out = paged_decode_attention(q, packed(kp), packed(vp), jnp.int32(1),
                                 lengths, bt, scale=1 / 64)
    vk, vv = attention.gather_paged_view(kp, vp, bt)
    ref = attention._attend_token(cfg, q[:, None], vk[1], vv[1],
                                  lengths - 1, True, jnp.float32,
                                  jnp.eye(nh * hd, dtype=jnp.float32))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref).reshape(5, nh, hd),
                               atol=tol, rtol=tol)


def test_heads_of_128_take_the_kernel_as_it_was():
    """The query goes to the kernel as (b, nkv, group, hd) and its output
    comes back so: no lanes are placed or read back around the kernel, for
    a row of one head or of two (the kernel slices a row's heads)."""
    kp, vp = _pools(jax.random.PRNGKey(13), 1, 16, 2)
    q = jnp.zeros((3, 8, HD), jnp.bfloat16)
    bt = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    args = (q, kp, vp, jnp.int32(0), jnp.ones((3,), jnp.int32), bt)
    jaxpr = jax.make_jaxpr(paged_decode_attention)(*args)
    inner = jaxpr.eqns[0].params["jaxpr"].jaxpr
    prims = {e.primitive.name for e in inner.eqns}
    assert not prims & {"pad", "concatenate", "slice"}, prims
    # heads of 64, two to a row (float32: one row, read unpaired)
    packed = jax.make_jaxpr(paged_decode_attention)(
        q[..., :64].astype(jnp.float32),
        kp.reshape(1, 16, BS, 1, 2 * HD)[..., :HD].astype(jnp.float32),
        vp.reshape(1, 16, BS, 1, 2 * HD)[..., :HD].astype(jnp.float32),
        *args[3:])
    prims = {e.primitive.name
             for e in packed.eqns[0].params["jaxpr"].jaxpr.eqns}
    assert not prims & {"pad", "concatenate", "slice"}, prims


def _tiny(**kw):
    base = dict(name="pdk-t", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                dtype="float32", remat=False, max_seq=64)
    base.update(kw)
    return ModelConfig(**base)


def test_decode_function_matches_the_view_path_and_parks():
    """Both decode functions write the same pools; live lanes attend alike
    and a parked lane (position at capacity) stays finite."""
    cfg = _tiny(d_model=512, n_heads=4, n_kv_heads=2, dtype="bfloat16")
    p = attention.init_attn(jax.random.PRNGKey(5), cfg)
    layers, n_blocks, max_blocks = 2, 16, 4              # capacity 64
    ck, cv = _pools(jax.random.PRNGKey(6), layers, n_blocks, 2)
    bt = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    pos = jnp.asarray([0, 37, max_blocks * BS], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 1, 512),
                          jnp.float32).astype(jnp.bfloat16)
    li = jnp.int32(1)
    vk, vv = attention.gather_paged_view(ck, cv, bt)
    y_v, ck_v, cv_v, _, _ = attention.paged_attention_decode_view(
        p, cfg, x, ck, cv, vk, vv, li, pos, bt)
    y_k, ck_k, cv_k = attention.paged_attention_decode_kernel(
        p, cfg, x, ck, cv, li, pos, bt)
    np.testing.assert_array_equal(np.asarray(ck_k), np.asarray(ck_v))
    np.testing.assert_array_equal(np.asarray(cv_k), np.asarray(cv_v))
    assert bool(jnp.isfinite(y_k).all())
    np.testing.assert_allclose(np.asarray(y_k[:2], np.float32),
                               np.asarray(y_v[:2], np.float32),
                               atol=5e-2, rtol=5e-2)


@pytest.fixture
def kernel_forced(monkeypatch):
    """Engage the kernel on the CPU (interpret mode) by patching the
    predicate the engine consults."""
    monkeypatch.setattr(attention, "paged_kernel_engages", lambda pool: True)


def _admitted_engine(model, params):
    eng = ContinuousEngine(model, params, max_seq=64, slots=3, chunk=4,
                           kv_layout="paged", block_size=BS)
    for i in range(2):
        eng.submit(Request(prompt=(jnp.arange(9 + 14 * i) * 5) % 128,
                           max_new_tokens=30))
    eng.step_chunk()                    # admits both; one lane stays free
    return eng


def _granite_tiny():
    """granite-4.0-h's layer kinds and multipliers at CPU widths, with
    heads of 64 packed two to a pool row."""
    return _tiny(family="hybrid", n_layers=4, d_model=128, n_heads=4,
                 n_kv_heads=2, head_dim=64, ssm_state=16,
                 layer_types=("mamba", "attention") * 2, mamba_n_heads=4,
                 mamba_d_head=32, mamba_conv_bias=True, mamba_chunk=16,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=1 / 64, logits_scaling=8.0,
                 rope=False)


@pytest.mark.parametrize("family", ["dense", "hybrid", "granite"])
def test_chunk_logits_match_the_view_path(family, monkeypatch):
    """The fused chunk on the kernel path (predicate patched) against the
    view path from the same state: the same tokens and pools, and each
    step's logits allclose."""
    if family == "dense":
        cfg = _tiny()
    elif family == "granite":
        cfg = _granite_tiny()
        assert attention.pool_rows(cfg) == (1, 128)
    else:
        from repro.configs import smoke_config
        cfg = smoke_config("zamba2-2.7b")
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _admitted_engine(model, params)
    args = (eng.params, eng.cache, eng.tokens, eng.pos, eng.keys, eng.temps,
            eng.top_ks, eng.block_tables)
    copy = lambda: jax.tree_util.tree_map(jnp.copy, args)  # noqa: E731
    assert decode_attention(model, eng.cache, eng.block_tables) == "view"
    c_v, t_v, _, _, toks_v, bad_v = eng._chunk_fn(*copy())
    monkeypatch.setattr(attention, "paged_kernel_engages", lambda pool: True)
    assert decode_attention(model, eng.cache,
                            eng.block_tables) == "paged_kernel"
    c_k, t_k, _, _, toks_k, bad_k = eng._chunk_fn(*copy(),
                                                  paged_kernel=True)
    # a free lane's tokens are discarded: only busy lanes must agree
    live = np.asarray([not (s.free or s.prefilling)
                       for s in eng.sched.slots])
    assert live.sum() == 2
    np.testing.assert_array_equal(np.asarray(toks_k)[live],
                                  np.asarray(toks_v)[live])
    assert not bool(bad_k.any()) and not bool(bad_v.any())
    # the pools (a free lane's recurrent state follows its own tokens)
    for a, b in zip(model.split_paged_cache(c_k)[0],
                    model.split_paged_cache(c_v)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # logits, step by step, from the same state
    params, cache, tokens, pos = copy()[:4]
    bt = eng.block_tables
    cache_k = cache
    for _ in range(3):
        view = model.gather_paged_view(cache, bt)
        lv, cache, _ = model.decode_step(params, tokens[:, None], cache, pos,
                                         block_tables=bt, kv_view=view)
        lk, cache_k = model.decode_step(params, tokens[:, None], cache_k,
                                        pos, block_tables=bt,
                                        paged_kernel=True)
        np.testing.assert_allclose(np.asarray(lk)[live],
                                   np.asarray(lv)[live], atol=1e-4,
                                   rtol=1e-4)
        assert bool(jnp.isfinite(lk).all())
        tokens = jnp.argmax(lv, -1).astype(jnp.int32)
        pos = jnp.minimum(pos + 1, eng.max_seq)


# ---------------------------------------------------------------------------
# what says the kernel engaged (when it engages on a TPU pool, and that the
# chunk compiles with it, is compiled for a described v5e in
# test_tpu_compile.py)
# ---------------------------------------------------------------------------

def test_attn_attribute_and_counter_once_per_chunk(kernel_forced):
    model = Model(_tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                           kv_layout="paged", block_size=BS)
    name = "serve.decode_attn.paged_kernel"
    before = obs.metrics_snapshot().get(name, {}).get("value", 0)
    obs.enable()
    obs.clear_trace()
    try:
        for i in range(3):
            eng.submit(Request(prompt=jnp.arange(5 + 4 * i) % 128,
                               max_new_tokens=6))
        while not eng.sched.idle:
            eng.step_chunk()
        chunks = [s for s in obs.spans() if s.name == "serve.decode_chunk"]
    finally:
        obs.disable()
        obs.clear_trace()
    assert chunks and all(s.args["attn"] == "paged_kernel" for s in chunks)
    after = obs.metrics_snapshot()[name]["value"]
    assert after - before == len(chunks)


def test_attn_attribute_reads_view_and_dense_off_the_kernel():
    model = Model(_tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    seen = {}
    for layout in ("paged", "dense"):
        eng = ContinuousEngine(model, params, max_seq=64, slots=2, chunk=4,
                               kv_layout=layout, block_size=BS)
        obs.enable()
        obs.clear_trace()
        try:
            eng.submit(Request(prompt=jnp.arange(6), max_new_tokens=5))
            while not eng.sched.idle:
                eng.step_chunk()
            seen[layout] = {s.args["attn"] for s in obs.spans()
                            if s.name == "serve.decode_chunk"}
        finally:
            obs.disable()
            obs.clear_trace()
    assert seen == {"paged": {"view"}, "dense": {"dense"}}
