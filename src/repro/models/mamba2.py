"""Mamba-2 (SSD) mixer: the sequence-mixing layer of the hybrids (zamba2,
granite-4.0-h).

In-proj -> (gate z | x B C | dt), causal depthwise conv (with an optional
bias) and SiLU over (x B C), the selective state-space recurrence with a
scalar decay per head,

    h_t = exp(dt_t * A) h_{t-1} + (dt_t x_t) B_t^T,    y_t = h_t C_t + D x_t,

then the gated RMSNorm norm(y * silu(z)) over the inner width and the
out-proj.  dt_t = softplus(dt_raw + dt_bias), A = -exp(A_log).  B and C
are shared by the heads (one group).

A sequence (prefill, training) runs the chunked SSD form
(:func:`ssd`): within each chunk of ``cfg.mamba_chunk`` positions the
recurrence is the quadratic form C L B^T X as matmuls, and the state
passes from chunk to chunk in a ``lax.scan``.  One token (decode) is the
recurrence itself (:func:`decode_step`), its state in float32.

State per layer: conv tail (b, d_conv-1, din + 2N) and SSM state
(b, nheads, d_head, N) — constant-size.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, init_dense


class Mamba2Params(NamedTuple):
    # the in-proj in two: (z | x B C), and dt, whose width (a head count)
    # would make the whole a matrix the TPU lays out transposed and copies
    w_in: jax.Array       # (d, 2*din + 2N)
    w_dt: jax.Array       # (d, nheads)
    conv_w: jax.Array     # (d_conv, din + 2N)
    a_log: jax.Array      # (nheads,)
    dt_bias: jax.Array    # (nheads,)
    d_skip: jax.Array     # (nheads,)
    norm_w: jax.Array     # (din,)
    w_out: jax.Array      # (din, d)
    conv_b: Optional[jax.Array] = None    # (din + 2N,) when mamba_conv_bias


def dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(inner width, heads, state size)."""
    if cfg.mamba_n_groups != 1:
        raise NotImplementedError(
            f"Mamba-2 with {cfg.mamba_n_groups} groups of B and C; one "
            f"group is implemented")
    din = cfg.mamba_d_inner
    return din, din // cfg.mamba_d_head, cfg.ssm_state


class Mamba2State(NamedTuple):
    conv: jax.Array   # (b, d_conv-1, din + 2N)
    ssm: jax.Array    # (b, nheads, d_head, N) float32


def init_mamba2(key, cfg: ModelConfig) -> Mamba2Params:
    d = cfg.d_model
    din, nheads, n = dims(cfg)
    ks = jax.random.split(key, 3)
    conv = din + 2 * n
    w_in = init_dense(ks[0], d, 2 * din + 2 * n + nheads, cfg.dtype)
    return Mamba2Params(
        w_in=w_in[:, :2 * din + 2 * n], w_dt=w_in[:, 2 * din + 2 * n:],
        conv_w=(jax.random.normal(ks[1], (cfg.mamba_d_conv, conv)) * 0.1
                ).astype(cfg.dtype),
        a_log=jnp.zeros((nheads,), jnp.float32),
        dt_bias=jnp.zeros((nheads,), jnp.float32),
        d_skip=jnp.ones((nheads,), jnp.float32),
        norm_w=jnp.ones((din,), cfg.dtype),
        w_out=init_dense(ks[2], din, d, cfg.dtype),
        conv_b=(jnp.zeros((conv,), cfg.dtype) if cfg.mamba_conv_bias
                else None),
    )


def init_state(cfg: ModelConfig, batch: int) -> Mamba2State:
    din, nheads, n = dims(cfg)
    return Mamba2State(
        conv=jnp.zeros((batch, cfg.mamba_d_conv - 1, din + 2 * n),
                       cfg.dtype),
        ssm=jnp.zeros((batch, nheads, cfg.mamba_d_head, n), jnp.float32))


def state_bytes(cfg: ModelConfig) -> int:
    """Bytes of one slot's state in one Mamba layer."""
    din, nheads, n = dims(cfg)
    conv = (cfg.mamba_d_conv - 1) * (din + 2 * n) * jnp.dtype(
        cfg.dtype).itemsize
    return conv + nheads * cfg.mamba_d_head * n * 4


def _in_proj(p: Mamba2Params, cfg: ModelConfig, x):
    """(z, x B C, dt_raw) of x (b, s, d)."""
    din, _, n = dims(cfg)
    z, xbc = jnp.split(jnp.einsum("bsd,de->bse", x, p.w_in), [din], axis=-1)
    return z, xbc, jnp.einsum("bsd,dh->bsh", x, p.w_dt)


def _conv(p: Mamba2Params, xbc_ext, s: int):
    """SiLU of the causal depthwise conv over ``xbc_ext`` (b, d_conv-1+s,
    c), whose first d_conv-1 positions are the carried tail."""
    k = p.conv_w.shape[0]
    conv = sum(p.conv_w[i][None, None, :]
               * jax.lax.dynamic_slice_in_dim(xbc_ext, i, s, axis=1)
               for i in range(k))
    if p.conv_b is not None:
        conv = conv + p.conv_b
    return jax.nn.silu(conv)


def _gate_out(p: Mamba2Params, cfg: ModelConfig, y, z, dtype):
    """Gated RMSNorm norm(y * silu(z)) * w over the inner width, then the
    out-proj.  y (b, s, din) float32."""
    g = y.astype(dtype) * jax.nn.silu(z)
    g32 = g.astype(jnp.float32)
    var = jnp.mean(g32 * g32, axis=-1, keepdims=True)
    g = (g32 * jax.lax.rsqrt(var + cfg.norm_eps)).astype(dtype) * p.norm_w
    return jnp.einsum("bse,ed->bsd", g, p.w_out)


def _segsum(a):
    """a (..., T) -> (..., T, T) with [i, j] = a[j+1] + ... + a[i] for
    j <= i and -inf above the diagonal; summed from masked copies, not as
    a difference of two cumulative sums, so long chunks lose nothing to
    cancellation."""
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., None, :], a.shape + (t,))   # [.., i, j]=a[j]
    x = jnp.swapaxes(x, -1, -2)                              # [.., i, j]=a[i]
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), x, -jnp.inf)


def ssd(xh, dt, a, b_in, c_in, h0, chunk: int):
    """The chunked SSD form of the recurrence over a sequence.

    xh (b, s, h, p), dt (b, s, h), b_in/c_in (b, s, n), all float32;
    a (h,) the decay rates (negative); h0 (b, h, p, n) the state before
    the sequence.  A position with dt 0 neither decays nor feeds the
    state.  Returns (y (b, s, h, p) without the D skip, final state)."""
    bsz, s, nh, hp = xh.shape
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:   # trailing dt-0 positions: the state passes them unchanged
        xh, dt, b_in, c_in = (jnp.pad(t, [(0, 0), (0, pad)]
                                      + [(0, 0)] * (t.ndim - 2))
                              for t in (xh, dt, b_in, c_in))

    def chunks(t):   # (b, nc*q, ...) -> (nc, b, q, ...)
        return jnp.moveaxis(t.reshape((bsz, nc, q) + t.shape[2:]), 1, 0)

    hi = jax.lax.Precision.HIGHEST

    def step(h, inp):
        x_c, dt_c, b_c, c_c = inp                 # (b, q, h, p), (b, q, h)..
        la = (dt_c * a).transpose(0, 2, 1)        # (b, h, q) log decays
        cum = jnp.cumsum(la, axis=-1)
        xdt = x_c * dt_c[..., None]               # (b, q, h, p)
        # within the chunk: y_i = sum_{j<=i} (C_i.B_j) exp(cum_i-cum_j) X_j
        decay = jnp.exp(_segsum(la))              # (b, h, q, q)
        cb = jnp.einsum("bin,bjn->bij", c_c, b_c, precision=hi)
        w = cb[:, None] * decay
        y = jnp.einsum("bhij,bjhp->bihp", w, xdt, precision=hi)
        # from the state entering the chunk: y_i += exp(cum_i) C_i h
        y = y + jnp.einsum("bin,bhpn,bhi->bihp", c_c, h, jnp.exp(cum),
                           precision=hi)
        # the state leaving it
        tail = decay[..., -1, :]                  # (b, h, q)
        h = (h * jnp.exp(cum[..., -1])[..., None, None]
             + jnp.einsum("bjn,bhj,bjhp->bhpn", b_c, tail, xdt,
                          precision=hi))
        return h, y

    h, ys = jax.lax.scan(step, h0, (chunks(xh), chunks(dt), chunks(b_in),
                                    chunks(c_in)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, nc * q, nh, hp)[:, :s]
    return y, h


def forward(p: Mamba2Params, cfg: ModelConfig, x,
            state: Mamba2State = None, lengths=None):
    """Sequence forward (prefill, training) through the chunked SSD;
    returns (y, final_state).

    ``state`` is the state before ``x`` (a continuation chunk of a long
    prompt starts from the slot's state); None starts from zeros.
    ``lengths`` ((b,) int32, optional) marks the real length of each row
    of a RIGHT-padded batch: padded positions take dt = 0 and the conv
    tail is read at the last real positions, so the returned state is
    bitwise the state of the unpadded sequence."""
    b, s, d = x.shape
    din, nheads, n = dims(cfg)
    k = cfg.mamba_d_conv
    z, xbc, dt_raw = _in_proj(p, cfg, x)

    # zero states derived from the activations INHERIT their sharding —
    # plain jnp.zeros is replicated and makes GSPMD unshard the scan chain
    if state is None:
        conv_state = jnp.broadcast_to(xbc[:, :1, :] * 0,
                                      (b, k - 1, xbc.shape[-1]))
    else:
        conv_state = state.conv
    xbc_ext = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
    conv = _conv(p, xbc_ext, s)
    if lengths is None:
        new_conv_tail = xbc_ext[:, s:, :]
    else:
        # row i's real tokens sit at ext positions [k-1, k-1 + lengths[i]),
        # so its tail starts at ext position lengths[i]
        idx = (jnp.asarray(lengths, jnp.int32)[:, None]
               + jnp.arange(k - 1)[None, :])
        new_conv_tail = jnp.take_along_axis(xbc_ext, idx[:, :, None], axis=1)

    xs_, b_in, c_in = jnp.split(conv, [din, din + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p.dt_bias)
    if lengths is not None:
        real = jnp.arange(s)[None, :] < jnp.asarray(lengths,
                                                    jnp.int32)[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    a = -jnp.exp(p.a_log)
    xh = xs_.reshape(b, s, nheads, cfg.mamba_d_head).astype(jnp.float32)
    if state is None:
        h0 = (xh[:, 0, :, :, None] * b_in.astype(jnp.float32)[
            :, 0, None, None, :]) * 0
    else:
        h0 = state.ssm
    y, h = ssd(xh, dt, a, b_in.astype(jnp.float32),
               c_in.astype(jnp.float32), h0, cfg.mamba_chunk)
    y = y + p.d_skip[None, None, :, None] * xh
    out = _gate_out(p, cfg, y.reshape(b, s, din), z, x.dtype)
    return out, Mamba2State(new_conv_tail, h)


def decode_step(p: Mamba2Params, cfg: ModelConfig, x, state: Mamba2State):
    """One token per row, x (b, 1, d): one step of the recurrence, the SSM
    state in float32.  Returns (y, new_state)."""
    b = x.shape[0]
    din, nheads, n = dims(cfg)
    z, xbc, dt_raw = _in_proj(p, cfg, x)
    xbc_ext = jnp.concatenate([state.conv.astype(xbc.dtype), xbc], axis=1)
    conv = _conv(p, xbc_ext, 1)
    xs_, b_in, c_in = jnp.split(conv[:, 0], [din, din + n], axis=-1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p.dt_bias)
    da = jnp.exp(dt * -jnp.exp(p.a_log))                      # (b, h)
    xh = xs_.reshape(b, nheads, cfg.mamba_d_head).astype(jnp.float32)
    h = (state.ssm * da[..., None, None]
         + (dt[..., None] * xh)[..., None]
         * b_in.astype(jnp.float32)[:, None, None, :])
    y = jnp.einsum("bhpn,bn->bhp", h, c_in.astype(jnp.float32))
    y = y + p.d_skip[None, :, None] * xh
    out = _gate_out(p, cfg, y.reshape(b, 1, din), z, x.dtype)
    return out, Mamba2State(xbc_ext[:, 1:], h)
