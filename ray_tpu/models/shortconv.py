"""Gated short convolution (the `C` operator of a pattern, models/
transformer.py: `conv_block`; LFM2's `conv` layers).

On normalised rows h (.., E):  `(B, C, u) = split3(h W_in)`, `z = B * u`,
`c_t = sum_j w[j] * z_{t - K + 1 + j}` (depthwise, causal, per channel, no
bias, no activation), `y = (C * c) W_out`.

One definition serves a prompt and a decode step: the K - 1 rows of z that
precede the first row come in as the carried `tail`, and the tail after the
last real row goes out.  That tail is all the state a sequence carries:
{"tail": (B, K - 1, E)} in the activations' type, next to Mamba-2's
{"ssm", "tail"} (models/mamba2.py, whose tail is cut the same way, by the
same two functions).  Rows past `length` and slots that are not `live` move
no state.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .mamba2 import tail_after, tails_every


@dataclasses.dataclass(frozen=True)
class ShortConvDims:
    kernel: int = 3             # conv_L_cache: the taps, the newest row's last
    # Rows a state checkpoint's spacing is counted in, as Mamba-2's scan
    # chunk is (llm/engine.py: a checkpoint every `_CKPT_CHUNKS` chunks).
    # The operator itself has no chunk: a tail can be cut at any row.
    chunk: int = 128

    def param_count(self, hidden: int) -> int:
        return hidden * 3 * hidden + self.kernel * hidden + hidden * hidden

    def state_bytes(self, hidden: int, act_bytes: int = 2) -> int:
        """One sequence's carried tail in one layer."""
        return (self.kernel - 1) * hidden * act_bytes


def zero_state(dims: ShortConvDims, hidden: int, batch: int, dtype):
    """One layer's state of `batch` sequences that have read nothing."""
    return {"tail": jnp.zeros((batch, dims.kernel - 1, hidden), dtype)}


def mixer(lp, h, state, dims: ShortConvDims, length=None, live=None,
          every: int = 0):
    """The operator on normalised rows h (B, S, E) from `state`; `length`,
    `live` and `every` as `mamba2.mixer` takes them.  Returns (y (B, S, E),
    state', the tails after every `every` rows {"tail": (B, S // every,
    K - 1, E)} or None)."""
    S, K, dt = h.shape[1], dims.kernel, h.dtype
    proj = jnp.einsum("bse,ef->bsf", h, lp["w_in"].astype(dt))
    gate_in, gate_out, u = jnp.split(proj, 3, axis=-1)
    z = gate_in * u
    ext = jnp.concatenate([state["tail"].astype(dt), z], axis=1)
    w = lp["conv_w"].astype(jnp.float32)                # (K, E)
    acc = ext[:, :S].astype(jnp.float32) * w[0]
    for j in range(1, K):
        acc = acc + ext[:, j:j + S].astype(jnp.float32) * w[j]
    y = jnp.einsum("bse,ef->bsf", gate_out * acc.astype(dt),
                   lp["w_out"].astype(dt))
    new = {"tail": tail_after(ext, state["tail"], K, length, live)}
    kept = {"tail": tails_every(ext, state["tail"], K, every)} \
        if every else None
    return y, new, kept


def init_layer(key, hidden: int, dims: ShortConvDims, dtype):
    """Seeded weights of one layer, normal / sqrt(fan_in)."""
    ks = jax.random.split(key, 3)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)
    return {"w_in": dense(ks[0], (hidden, 3 * hidden), hidden),
            "conv_w": dense(ks[1], (dims.kernel, hidden), dims.kernel),
            "w_out": dense(ks[2], (hidden, hidden), hidden)}
