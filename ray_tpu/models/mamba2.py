"""Mamba-2 mixer (state-space duality, Dao & Gu 2024) as the `M` layers of a
hybrid stack run it (models/transformer.py: `mamba_block`).

One definition of the recurrence serves a prompt and a decode step.  Per
head, with a state h of (head_dim x state) in float32:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

`ssd` computes it in chunks: inside a chunk by matrix products (the decay
between two positions of a chunk is exp of a difference of cumulative
sums), between chunks by the carried state.  A decode step is a chunk of
one token: its products have a contracted length of 1 and XLA lowers them
to the elementwise update.  A position whose dt is 0 leaves the state as it
was (exp(0) = 1, dt x B = 0): that is how padding past a prompt's real
length, and a slot that is not live, are kept out of the state.

The state a sequence carries between calls is `(ssm, tail)`: `ssm`
(B, H, P, N) float32 and `tail` (B, K - 1, C) in the activations' type, the
last K - 1 inputs of the causal depthwise convolution of width K.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    num_heads: int = 128
    head_dim: int = 64
    state: int = 128            # ssm_state_size N
    groups: int = 8             # n_groups: B and C are shared by H / G heads
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    # The type a sequence's SSM state is HELD in between calls (a slot's
    # row, a checkpoint); the recurrence itself runs in float32 whatever
    # this says.  Another type is a precision control's.
    state_dtype: str = "float32"

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_width(self) -> int:                # xBC: x, B and C side by side
        return self.inner + 2 * self.groups * self.state

    @property
    def in_width(self) -> int:                  # [z | xBC | dt]
        return self.inner + self.conv_width + self.num_heads

    def param_count(self, hidden: int) -> int:
        return (hidden * self.in_width + self.conv_width * (self.conv_kernel + 1)
                + 3 * self.num_heads + self.inner + self.inner * hidden)

    def state_bytes(self, act_bytes: int = 2) -> int:
        """One sequence's recurrent state in one layer."""
        return (self.num_heads * self.head_dim * self.state
                * jnp.dtype(self.state_dtype).itemsize
                + (self.conv_kernel - 1) * self.conv_width * act_bytes)


def zero_state(dims: Mamba2Dims, batch: int, dtype):
    """One layer's state of `batch` sequences that have read nothing."""
    return {"ssm": jnp.zeros((batch, dims.num_heads, dims.head_dim, dims.state),
                             jnp.dtype(dims.state_dtype)),
            "tail": jnp.zeros((batch, dims.conv_kernel - 1, dims.conv_width),
                              dtype)}


def ssd(x, dt, A, Bm, Cm, h0, chunk: int, every: int = 0):
    """The recurrence over S positions from state h0.

    x (B, S, H, P); dt (B, S, H) float32, already positive (0 = skip the
    position); A (H,) float32, negative; Bm, Cm (B, S, G, N); h0
    (B, H, P, N) float32.  Returns y (B, S, H, P) float32 without the `D x`
    term, the state after position S, and with `every` (a multiple of
    `chunk`) the states after every `every` positions (B, S // every, H, P,
    N)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    nc = (S + pad) // Q
    f32 = jnp.float32
    x = x.reshape(B, nc, Q, G, R, P)
    Bm, Cm = Bm.reshape(B, nc, Q, G, N), Cm.reshape(B, nc, Q, G, N)
    # Per head and chunk, positions last: (B, nc, G, R, Q).
    dt = jnp.moveaxis(dt.reshape(B, nc, Q, G, R), 2, -1)
    cum = jnp.cumsum(dt * A.reshape(G, R, 1), axis=-1)  # log decay, inclusive
    total = cum[..., -1]                                # (B, nc, G, R)
    xdt = (x.astype(f32) * jnp.moveaxis(dt, -1, 2)[..., None]).astype(x.dtype)

    # Inside each chunk: y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cm, Bm, preferred_element_type=f32)
    seg = cum[..., :, None] - cum[..., None, :]         # (B, nc, G, R, i, j)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg, -jnp.inf))
    m = (decay * cb[:, :, :, None]).astype(x.dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xdt, preferred_element_type=f32)

    # What each chunk adds to the state, decayed to the chunk's end.
    to_end = jnp.moveaxis(jnp.exp(total[..., None] - cum), -1, 2)
    xend = (xdt.astype(f32) * to_end[..., None]).astype(x.dtype)
    adds = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xend, Bm,
                      preferred_element_type=f32)

    # Between chunks: the carried state, as it enters each chunk.
    def carry(h, c):
        keep, add = c
        return h * keep[..., None, None] + add, h
    last, enter = jax.lax.scan(
        carry, h0.reshape(B, G, R, P, N),
        (jnp.moveaxis(jnp.exp(total), 1, 0), jnp.moveaxis(adds, 1, 0)))
    enter = jnp.moveaxis(enter, 0, 1)                   # (B, nc, G, R, P, N)

    # The entering state's part of each output: exp(cum_i) C_i . h
    y_state = jnp.einsum("bcign,bcgrpn->bcigrp", Cm.astype(f32), enter,
                         preferred_element_type=f32)
    y = y + y_state * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    y = y.reshape(B, nc * Q, H, P)[:, :S]
    if not every:
        return y, last.reshape(B, H, P, N), None
    # The state after `every` positions is the one entering the chunk there,
    # or `last` where the rows end on a boundary.
    step, n_kept = every // Q, S // every
    kept = enter[:, step::step][:, :n_kept]
    if kept.shape[1] < n_kept:
        kept = jnp.concatenate([kept, last[:, None]], axis=1)
    return (y, last.reshape(B, H, P, N), kept.reshape(B, n_kept, H, P, N))


def _conv(lp, xbc, tail, dims: Mamba2Dims):
    """Causal depthwise convolution with bias, then silu: xbc (B, S, C) after
    the `tail` (B, K - 1, C) that precedes it.  Returns the activations and
    the extended input (tail first), from which a later tail is cut."""
    K = dims.conv_kernel
    ext = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    S = xbc.shape[1]
    w = lp["conv_w"].astype(jnp.float32)                # (K, C)
    acc = lp["conv_b"].astype(jnp.float32)
    for k in range(K):
        acc = acc + ext[:, k:k + S].astype(jnp.float32) * w[k]
    return jax.nn.silu(acc).astype(xbc.dtype), ext


def tail_after(ext, prev, K: int, length=None, live=None):
    """The convolution's carried tail after the last real row.  `ext` (B,
    K - 1 + S, C) is the input with the previous tail `prev` first, so row
    t + K - 1 of it is input row t and the K - 1 rows before row `n` start
    at ext row n.  `length`: the real rows (all S without); slots that are
    not `live` (B,) keep `prev`.  In `prev`'s type."""
    if length is None:
        tail = ext[:, ext.shape[1] - (K - 1):]
    else:
        tail = jax.lax.dynamic_slice_in_dim(ext, length, K - 1, axis=1)
    if live is not None:
        tail = jnp.where(live[:, None, None], tail, prev.astype(ext.dtype))
    return tail.astype(prev.dtype)


def tails_every(ext, prev, K: int, every: int):
    """The tails after every `every` rows of `ext` (as `tail_after` has it):
    (B, S // every, K - 1, C) in `prev`'s type."""
    B, S = ext.shape[0], ext.shape[1] - (K - 1)
    tails = jnp.stack([ext[:, b:b + K - 1]
                       for b in range(every, S + 1, every)], axis=1) \
        if S >= every else jnp.zeros((B, 0, K - 1, ext.shape[-1]), ext.dtype)
    return tails.astype(prev.dtype)


def mixer(lp, u, state, dims: Mamba2Dims, length=None, live=None,
          every: int = 0):
    """The Mamba-2 mixer on normalised rows u (B, S, E) from `state`.

    `length` (a scalar, for B = 1): only the first `length` rows are real;
    the state returned is the one after them.  `live` (B,) bool: rows of the
    batch that are not live keep their state.  `every`: also return the
    state after every `every` rows, as {"ssm": (B, S // every, H, P, N),
    "tail": (B, S // every, K - 1, C)}.
    Returns (y (B, S, E), state', checkpoints or None)."""
    B, S, _ = u.shape
    H, P, G, N, K = (dims.num_heads, dims.head_dim, dims.groups, dims.state,
                     dims.conv_kernel)
    dt_ = u.dtype
    f32 = jnp.float32
    proj = jnp.einsum("bse,ef->bsf", u, lp["w_in"].astype(dt_))
    z, xbc, dt = jnp.split(proj, [dims.inner, dims.inner + dims.conv_width], -1)
    xbc, ext = _conv(lp, xbc, state["tail"], dims)
    x, Bm, Cm = jnp.split(xbc, [dims.inner, dims.inner + G * N], -1)
    x = x.reshape(B, S, H, P)
    Bm, Cm = Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    real = jnp.ones((B, S), bool)
    if length is not None:
        real = real & (jnp.arange(S)[None] < length)
    if live is not None:
        real = real & live[:, None]
    dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(lp["A_log"].astype(f32))
    held = state["ssm"].dtype
    y, ssm, kept = ssd(x, dt, A, Bm, Cm, state["ssm"].astype(f32),
                       dims.chunk, every)
    y = y + x.astype(f32) * lp["D"].astype(f32)[:, None]
    # Gate, then norm over each group's share of the inner width.
    y = y.reshape(B, S, dims.inner) * jax.nn.silu(z.astype(f32))
    yg = y.reshape(B, S, G, dims.inner // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + dims.norm_eps)
    y = (yg.reshape(B, S, dims.inner) * lp["norm"].astype(f32)).astype(dt_)
    out = jnp.einsum("bsf,fe->bse", y, lp["w_out"].astype(dt_))

    new = {"ssm": ssm.astype(held),
           "tail": tail_after(ext, state["tail"], K, length, live)}
    ckpt = None
    if every:
        ckpt = {"ssm": kept.astype(held),
                "tail": tails_every(ext, state["tail"], K, every)}
    return out, new, ckpt


def init_layer(key, hidden: int, dims: Mamba2Dims, dtype):
    """Seeded weights of one layer: projections normal / sqrt(fan_in); dt
    bias the inverse softplus of steps log-uniform in [0.001, 0.1], A in
    [1, 16], D 1, as the published initialisation has them."""
    ks = jax.random.split(key, 6)
    H = dims.num_heads

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)
    step = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32,
                                      jnp.log(0.001), jnp.log(0.1)))
    return {"w_in": dense(ks[0], (hidden, dims.in_width), hidden),
            "conv_w": dense(ks[1], (dims.conv_kernel, dims.conv_width),
                            dims.conv_kernel),
            "conv_b": (0.1 * jax.random.normal(
                ks[3], (dims.conv_width,), jnp.float32)).astype(dtype),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((H,), jnp.float32),
            "norm": jnp.ones((dims.inner,), jnp.float32),
            "w_out": dense(ks[5], (dims.inner, hidden), dims.inner)}
