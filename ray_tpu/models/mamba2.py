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
length, and a slot that is not live, are kept out of the state.  Where the
shapes allow (`step_path`) a decode step goes through `mamba_step` in
`ssd`'s place: a kernel that reads and writes the state of the live slots
and touches no other.

The state a sequence carries between calls is `(ssm, tail)`: `ssm`
(B, H, P, N) float32 and `tail` (B, K - 1, C) in the activations' type, the
last K - 1 inputs of the causal depthwise convolution of width K.
"""

from __future__ import annotations

import dataclasses
import functools

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


# ------------------------------------------------------------ one row -----
# A decode step is one row a slot, and most of what it moves is the state:
# 2 MiB a slot a layer at Granite's sizes, read and written.  `ssd` keeps a
# slot that is not live out of the state by its dt of 0 and so rewrites its
# row as it was; `mamba_step` moves the live slots' rows alone.

def step_path(dims: Mamba2Dims, rows: int = 1, length=None, live=None,
              every: int = 0) -> str:
    """Which form a call's recurrence takes, read from its shapes: "pallas"
    (`mamba_step`) for a decode step (one row a slot, `live` given) on a TPU
    over a float32 state of whole 128-lane rows; "ssd" for everything else:
    every prefill (`length`, more rows, `every`), any other state."""
    ok = (jax.default_backend() == "tpu" and rows == 1 and live is not None
          and length is None and not every and dims.state % 128 == 0
          and dims.head_dim % 8 == 0 and dims.state_dtype == "float32"
          and _head_block(dims.num_heads, dims.head_dim, dims.state,
                          dims.groups) > 0)
    return "pallas" if ok else "ssd"


# The state of a block of heads is double-buffered in and out of the
# kernel: four buffers of this many bytes at most.
_BLOCK_BYTES = 2 << 20


def _head_block(H: int, P: int, N: int, G: int) -> int:
    """The heads (of H, P x N each, G groups) a grid step takes: the most
    whose state fits `_BLOCK_BYTES`, a multiple of 8 (or all of them) that
    holds whole groups or lies inside one; 0 where no such block exists."""
    per = H // G
    fits = [b for b in range(1, H + 1)
            if H % b == 0 and (b % 8 == 0 or b == H)
            and (b % per == 0 or per % b == 0)
            and b * P * N * 4 <= _BLOCK_BYTES]
    return max(fits, default=0)


def live_order(live):
    """(order (B,) int32, n_live) of a step's `live` (B,) column: the live
    slots' indices first, in order, then the last live one again in every
    position after them (slot 0 where nobody is live), so that a kernel
    whose grid walks the positions is told no new block past `n_live`."""
    live = live.astype(bool)
    n = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n - 1, 0)]
    return jnp.where(jnp.arange(live.shape[0]) < n, order, last), n


def reference_step(xdt, dec, Bm, Cm, h):
    """One step of the recurrence in XLA, every slot's: h' = dec h +
    xdt B^T, y = h' C.  xdt (B, H, P), dec (B, H), Bm, Cm (B, G, N), h (B,
    H, P, N), all float32 -> (y (B, H, P), h')."""
    B, H, P, N = h.shape
    G = Bm.shape[1]
    h = h.reshape(B, G, H // G, P, N)
    new = dec.reshape(B, G, H // G, 1, 1) * h \
        + xdt.reshape(B, G, H // G, P, 1) * Bm[:, :, None, None, :]
    y = jnp.sum(new * Cm[:, :, None, None, :], axis=-1)
    return y.reshape(B, H, P), new.reshape(B, H, P, N)


def _step_kernel(order_ref, at_ref, dec_ref, x_ref, b_ref, c_ref, h_ref,
                 y_ref, h_out, *, per_group: int):
    """One block of heads of the slot in position `i` of `order`: its state
    read once, advanced, written once (to the buffer it came from), and the
    step's output.  A position past the live ones names the block before it
    again, so nothing is copied for it, and its body is skipped.  All in
    float32 on the vector unit; what has to change axes goes through a
    transpose: a head's dt x lies along the lanes and multiplies the
    state's ROWS, so it is spread over 128 sublanes and turned; h' C is
    summed over the state's lanes turned, which leaves it along the lanes
    y lies on."""
    from jax.experimental import pallas as pl
    hb, i = pl.program_id(0), pl.program_id(1)
    n_live = at_ref[0]
    Hb, P, N = h_ref.shape[-3:]
    state, out = (r.at[(0,) * (len(r.shape) - 3)] for r in (h_ref, h_out))

    @pl.when(i < n_live)
    def _():
        slot = order_ref[i]
        for j in range(Hb):
            b, c = b_ref[0, j // per_group], c_ref[0, j // per_group]
            rows = jnp.broadcast_to(x_ref[0, j:j + 1, :], (128, P)).T
            new = dec_ref[slot, hb * Hb + j] * state[j] \
                + jnp.tile(rows, (1, N // 128)) * b             # (P, N)
            out[j] = new
            t = new * c
            acc = t[:, :128]
            for k in range(1, N // 128):
                acc = acc + t[:, k * 128:(k + 1) * 128]
            y_ref[0, j:j + 1, :] = jnp.sum(acc.T, axis=0, keepdims=True)

    @pl.when((n_live == 0) & (i == 0))
    def _():
        # Nobody is live: the one block that is resident goes back as it
        # came (the results' buffers are written whatever the body did).
        out[...] = state[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_step(xdt, dec, Bm, Cm, ssm, order, n_live, repeat=None,
               interpret: bool = False):
    """`reference_step` for the live slots alone, in place: grid (blocks of
    heads, positions of `order`), the slot innermost; trace name
    `mamba_step`.  `ssm` is a layer's (B, H, P, N) or, with `repeat` (a
    traced scalar), the scanned period's whole stacked leaf (repeats, B, H,
    P, N), of which the kernel reads and writes repeat `repeat`'s live rows
    where they lie.  `order`, `n_live`: `live_order`'s.  -> (y (B, H, P)
    float32, written for the live slots ONLY, ssm')."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, H, P = xdt.shape
    G, N = Bm.shape[1:]
    Hb = _head_block(H, P, N, G)
    per = H // G
    Gb = max(1, Hb // per)
    f32 = jnp.float32
    at = jnp.stack([n_live.astype(jnp.int32),
                    jnp.asarray(0 if repeat is None else repeat, jnp.int32)])
    lead = () if repeat is None else (1,)

    def rows(hb, i, order, at):             # x, y: (B, H, P)
        return order[i], hb, 0

    def groups(hb, i, order, at):           # B, C: (B, G, 1, N)
        return order[i], hb * Hb // (per * Gb), 0, 0

    def states(hb, i, order, at):
        return (*((at[1],) if lead else ()), order[i], hb, 0, 0)
    y, ssm = pl.pallas_call(
        functools.partial(_step_kernel, per_group=min(per, Hb)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(H // Hb, B),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),    # dec
                      pl.BlockSpec((1, Hb, P), rows),           # dt x
                      pl.BlockSpec((1, Gb, 1, N), groups),      # B
                      pl.BlockSpec((1, Gb, 1, N), groups),      # C
                      pl.BlockSpec((*lead, 1, Hb, P, N), states)],
            out_specs=[pl.BlockSpec((1, Hb, P), rows),
                       pl.BlockSpec((*lead, 1, Hb, P, N), states)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, P), f32),
                   jax.ShapeDtypeStruct(ssm.shape, f32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * _BLOCK_BYTES + (8 << 20)),
        name="mamba_step", interpret=interpret,
    )(order, at, dec, xdt.astype(f32), Bm.astype(f32)[:, :, None],
      Cm.astype(f32)[:, :, None], ssm)
    return y, ssm


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
          every: int = 0, order=None, repeat=None):
    """The Mamba-2 mixer on normalised rows u (B, S, E) from `state`.

    `length` (a scalar, for B = 1): only the first `length` rows are real;
    the state returned is the one after them.  `live` (B,) bool: rows of the
    batch that are not live keep their state.  `every`: also return the
    state after every `every` rows, as {"ssm": (B, S // every, H, P, N),
    "tail": (B, S // every, K - 1, C)}.
    Where `step_path` says "pallas" the live slots' SSM state alone is
    moved: `order` = `live_order(live)` if the caller has it (one for all
    the layers of a step), and with `repeat` (traced) `state["ssm"]` is a
    scanned period's whole stacked leaf (`mamba_step`), which comes back
    whole.
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
    if step_path(dims, S, length, live, every) == "pallas":
        y, ssm = mamba_step(x[:, 0].astype(f32) * dt[:, 0, :, None],
                            jnp.exp(dt[:, 0] * A), Bm[:, 0], Cm[:, 0],
                            state["ssm"],
                            *(live_order(live) if order is None else order),
                            repeat)
        # (A slot that is not live has no output: what lies there is not
        # the kernel's.)
        y, kept = jnp.where(live[:, None, None], y, 0.0)[:, None], None
    else:
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
