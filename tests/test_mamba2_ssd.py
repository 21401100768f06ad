"""The Mamba-2 mixer's chunked SSD prefill against the recurrence it
replaces, in float32 on the CPU: across chunk sizes and a carried initial
state, on right-padded buckets (the state is bitwise the unpadded
prompt's), across continuation chunks of a long prompt, and into the
one-token decode step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import mamba2
from repro.models.common import ModelConfig

CFG = ModelConfig(name="ssd-t", family="hybrid", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab=64, ssm_state=8,
                  mamba_n_heads=4, mamba_d_head=16, mamba_conv_bias=True,
                  mamba_chunk=16, dtype="float32")


def _sequential(xh, dt, a, b, c, h0):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t b_t^T, y_t = h_t c_t."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (h * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, c_t,
                             precision=jax.lax.Precision.HIGHEST)
    h, ys = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (xh, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), h


def _inputs(seed, b=2, s=70, h=3, p=4, n=5):
    r = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)  # noqa
    return (f(b, s, h, p), jnp.asarray(r.uniform(1e-3, 0.5, (b, s, h)),
                                       jnp.float32),
            -jnp.asarray(r.uniform(1.0, 16.0, h), jnp.float32),
            f(b, s, n), f(b, s, n), f(b, h, p, n))


@pytest.mark.parametrize("chunk", [1, 8, 16, 64, 256])
def test_chunked_ssd_equals_the_recurrence(chunk):
    xh, dt, a, b, c, h0 = _inputs(0)
    y, h = mamba2.ssd(xh, dt, a, b, c, h0, chunk)
    y_ref, h_ref = _sequential(xh, dt, a, b, c, h0)
    # float32 sums in another order: a few ulps of the largest value
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.abs(y_ref).max()))
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.abs(h_ref).max()))


def test_zero_steps_leave_the_state_bit_for_bit():
    xh, dt, a, b, c, h0 = _inputs(1, s=20)
    _, h = mamba2.ssd(xh, dt, a, b, c, h0, 8)
    def pad(t):
        return jnp.pad(t, [(0, 0), (0, 44)] + [(0, 0)] * (t.ndim - 2))
    _, h_pad = mamba2.ssd(pad(xh), pad(dt), a, pad(b), pad(c), h0, 8)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_pad))


def _layer(seed=0):
    p = mamba2.init_mamba2(jax.random.PRNGKey(seed), CFG)
    r = np.random.default_rng(seed)
    # decays that remember, and a conv bias that is not zero
    return p._replace(
        a_log=jnp.asarray(np.log(r.uniform(1, 16, p.a_log.shape)),
                          jnp.float32),
        dt_bias=jnp.asarray(r.uniform(-5, -2, p.dt_bias.shape), jnp.float32),
        conv_b=jnp.asarray(r.normal(size=p.conv_b.shape) * 0.3, jnp.float32))


def _x(seed, b, s):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, CFG.d_model))


def test_right_padded_bucket_gives_the_unpadded_state():
    """Bitwise at the SSD (above); here the in-projection of a 64-row
    bucket rounds apart from that of 37 rows by an ulp or so."""
    p = _layer()
    x = _x(1, 1, 37)
    y, st = mamba2.forward(p, CFG, x)
    y_pad, st_pad = mamba2.forward(p, CFG, jnp.pad(x, ((0, 0), (0, 27),
                                                       (0, 0))),
                                   lengths=jnp.asarray([37]))
    for a, b in zip(st, st_pad):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y_pad[:, :37]), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_continuation_chunks_carry_the_state():
    p = _layer()
    x = _x(2, 2, 75)
    y, st = mamba2.forward(p, CFG, x)
    y1, st1 = mamba2.forward(p, CFG, x[:, :40])
    y2, st2 = mamba2.forward(p, CFG, x[:, 40:], st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y), rtol=2e-5, atol=2e-5)
    for a, b in zip(st2, st):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_decode_steps_continue_the_prefill():
    p = _layer()
    x = _x(3, 2, 30)
    y, _ = mamba2.forward(p, CFG, x)
    _, st = mamba2.forward(p, CFG, x[:, :26])
    for t in range(26, 30):
        y_t, st = mamba2.decode_step(p, CFG, x[:, t:t + 1], st)
        np.testing.assert_allclose(np.asarray(y_t[:, 0]),
                                   np.asarray(y[:, t]), rtol=2e-5,
                                   atol=2e-5)


def test_conv_bias_is_applied():
    p = _layer()
    x = _x(4, 1, 9)
    y, _ = mamba2.forward(p, CFG, x)
    y0, _ = mamba2.forward(p._replace(conv_b=p.conv_b * 0), CFG, x)
    assert float(jnp.abs(y - y0).max()) > 1e-3
    with pytest.raises(NotImplementedError):
        mamba2.dims(dataclasses.replace(CFG, mamba_n_groups=2))
