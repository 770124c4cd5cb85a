"""Routed-expert layer (the `E` blocks of a pattern, models/
transformer.py: `routed_block`), dropless, told which experts it holds.

The router scores ALL experts of the model: `s = sigmoid(x W_r)` in float32;
the `top_k` experts with the largest `s + bias` are chosen, their weights
are their `s` normalised to sum 1 over the chosen and scaled.  ONE layer in
three published forms, told apart by its sizes (`RoutedDims`):

- latent, ungated, with a shared expert (Nemotron-H): the experts work in a
  latent space, `u = x W_down`, expert e gives `relu(u W1_e)^2 W2_e`, the
  weighted sum goes back through `W_up`; one shared expert works on x
  itself and is added.
- on the hidden width, gated, no shared expert (LFM2: `latent` 0,
  `shared_width` 0, `gated`): expert e gives `(silu(x W1_e) * (x W3_e))
  W2_e`; W1 and W3 lie side by side in `w1` (k, 2 x width), so a layer is
  two grouped products in either form.
- on the hidden width, gated, WITH a shared expert that is gated too
  (DeepSeek-V3's layer, as Moonlight has it: `latent` 0, `gated`,
  `shared_width` > 0): the routed experts as LFM2's; the shared expert one
  SwiGLU of `shared_width` on every row, `(silu(x Ws1) * (x Ws3)) Ws2`, its
  Ws1 and Ws3 side by side in `ws1` as the routed experts' are.

This process holds experts [held_from, held_from + held) of every layer (a
chip of an expert-parallel group holds its share) and computes THEIR part of
the weighted sum for the tokens routed to them; what the experts held
elsewhere would add is left out, and nothing stands in for the exchange.
The chosen weights are normalised over all chosen experts, held or not.
No token is dropped: the (token, expert) rows are sorted by expert and each
projection is ONE grouped product over the sorted rows (on a TPU the
`megablox` grouped matmul, whose grid covers only the row tiles of experts
that have rows, so a decode step reads the weights of the experts its batch
touched and no others; elsewhere `jax.lax.ragged_dot`).  No [N, K, E]
one-hot is built.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RoutedDims:
    experts: int = 512          # the router's outputs: every expert of the model
    held: int = 128             # experts whose weights this process holds
    held_from: int = 0          # the first of them
    top_k: int = 22
    latent: int = 1024          # 0: the experts act on the hidden width
    width: int = 2688           # an expert's hidden width
    shared_width: int = 5376    # 0: no shared expert
    scale: float = 5.0          # routed_scaling_factor
    gated: bool = False         # silu(x W1) * (x W3), not relu(x W1)^2

    def expert_params(self, hidden: int) -> int:
        return (3 if self.gated else 2) * (self.latent or hidden) * self.width

    def shared_params(self, hidden: int) -> int:
        """A layer's parameters outside its routed experts."""
        return (hidden * self.experts + self.experts + 2 * hidden * self.latent
                + (3 if self.gated else 2) * hidden * self.shared_width)


def scores(lp, x):
    """x (..., E) -> every expert's score (..., X) in float32."""
    return jax.nn.sigmoid(jnp.einsum(
        "...e,ex->...x", x, lp["router"].astype(x.dtype),
        preferred_element_type=jnp.float32))


def route(lp, x, dims: RoutedDims):
    """x (..., E) -> the chosen experts idx (..., K) int32 and their weights
    (..., K) float32."""
    s = scores(lp, x)
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           dims.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / jnp.sum(w, -1, keepdims=True) * dims.scale
    return idx.astype(jnp.int32), w


ROW_TILE = 128      # rows of a tile of the grouped product
_VMEM = 15 << 20    # of the 16 MiB a kernel may use unasked, with a margin


def _tile(width: int) -> int:
    """A tile of the grouped product along n: of the width's divisors in
    whole 128-lane rows the one nearest 1,024 (the smaller of two as near),
    so that a tile of an expert's matrix is a piece of about two megabytes
    or more: 1,024 of 2,048 and 3,072, 896 of 2,688, 768 of 1,536, a width
    up to 1,024 whole, and 1,408 of 1,408 and of 2,816, whose only smaller
    divisors, 128 and 256, read the matrix in pieces of half a megabyte at
    two thirds of the bandwidth (PERF.md §6, PR 44).  A width that is no
    whole number of lane rows is taken whole.  Along k it is what is left
    where k whole does not fit (`_tiling`)."""
    fits = [t for t in range(128, width + 1, 128) if width % t == 0]
    return min(fits, key=lambda t: (abs(t - 1024), t)) if fits else width


def _tiling(k: int, n: int, itemsize: int = 2):
    """The (row, k, n) tiles of a grouped product with matrices (k, n):
    k in ONE tile.  The kernel's grid runs the n tiles outermost, then the
    row tiles, the k tiles innermost, and copies a block of a group's matrix
    again whenever the block it asks for changes.  With k cut in two tiles
    or more that is at every grid step: each row tile of a group reads the
    group's whole matrix again, and a prompt's products are bound by those
    reads and not by the MXU (Moonlight's 560 rows an expert read its w1
    4-5 times).  With k whole, consecutive row tiles of a group ask for the
    same block, and a matrix is read once a group whatever its rows.  That
    holds for every call: a decode step, whose groups have one row tile
    each, reads what it read and takes as long, and a re-ask's 64-row
    suffix is a tenth faster.  The row tile stays `ROW_TILE`: a taller one
    wastes products on every group's edge tiles and won nowhere (all timed
    alone on the chip at the three families' shapes: PERF.md §6, PR 45).
    Only where the kernel's blocks at k whole (its three blocks twice, the
    pipeline's double buffer, and a float32 accumulator) would not fit its
    VMEM, as a float32 layer's would not, is k cut by `_tile`.  The widest
    blocks the cells take, bf16 (128, 2,048, 1,408), are 13.4 MiB by this
    reckoning and compile and run on the chip.  `_VMEM` was set against the
    compiler (PR 45: for a v5e it took every tiling up to 15.1 MiB by this
    reckoning, bf16 and float32, and refused every one from 16.4), and
    tests/test_paged_attention.py compiles what the guard lets through at
    that edge: probe again there before changing a tile."""
    tn = _tile(n)
    vmem = 2 * itemsize * (ROW_TILE * k + k * tn + ROW_TILE * tn) \
        + 4 * ROW_TILE * tn
    return ROW_TILE, k if vmem <= _VMEM else _tile(k), tn


def grouped_path() -> str:
    """How `_grouped` multiplies in this process: "megablox" on a TPU,
    "ragged_dot" elsewhere."""
    return "megablox" if jax.devices()[0].platform == "tpu" else "ragged_dot"


def _grouped(rows, weights, sizes):
    """rows (M, k) sorted by group, weights (G, k, n), sizes (G,) -> (M, n):
    each group's rows times its own matrix.  Rows past the groups' total
    are not computed and hold anything."""
    if grouped_path() == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        tile = _tiling(*weights.shape[1:], weights.dtype.itemsize)
        pad = -rows.shape[0] % tile[0]
        out = gmm(jnp.pad(rows, ((0, pad), (0, 0))), weights, sizes,
                  preferred_element_type=rows.dtype, tiling=tile)
        return out[:rows.shape[0]]
    return jax.lax.ragged_dot(rows, weights, sizes,
                              preferred_element_type=rows.dtype)


def held_experts(lp, u, idx, w, dims: RoutedDims, real=None):
    """The held experts' part of the weighted sum: u (..., latent or
    hidden), idx and w (..., K) from `route` -> (out like u, counts (2,)
    int32: distinct held experts that got a row, and (token, expert) rows
    computed).  A row of u that is not `real` (...) (padding, a slot that is
    not live) goes to no expert: it costs no product and touches no
    weights.  ONE sort and two grouped products over all the rows."""
    shape = u.shape
    u, idx, w = (a.reshape(-1, a.shape[-1]) for a in (u, idx, w))
    if real is not None:
        real = real.reshape(-1)
    N, K = idx.shape
    local = idx.reshape(-1) - dims.held_from
    here = (local >= 0) & (local < dims.held)
    if real is not None:
        here = here & jnp.repeat(real, K)
    group = jnp.where(here, local, dims.held)           # elsewhere: sorts last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=dims.held + 1)[:dims.held] \
        .astype(jnp.int32)
    rows = u[order // K]                                # (N K, latent)
    hid = _grouped(rows, lp["w1"].astype(u.dtype), sizes)
    if dims.gated:
        gate, up = jnp.split(hid, 2, axis=-1)
        hid = jax.nn.silu(gate) * up
    else:
        hid = jnp.square(jax.nn.relu(hid))
    out = _grouped(hid, lp["w2"].astype(u.dtype), sizes)
    # Back to (token, choice) order; a row of an expert held elsewhere was
    # not computed and counts nothing.
    back = jnp.argsort(order)
    out = jnp.where(here[:, None], out[back], 0).reshape(N, K, -1)
    weight = jnp.where(here.reshape(N, K), w, 0.0)
    out = jnp.einsum("nkl,nk->nl", out.astype(jnp.float32), weight)
    counts = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes)]).astype(jnp.int32)
    return out.astype(u.dtype).reshape(shape), counts


def _relu2_mlp(x, w1, w2):
    h = jnp.einsum("...e,em->...m", x, w1.astype(x.dtype))
    return jnp.einsum("...m,me->...e", jnp.square(jax.nn.relu(h)),
                      w2.astype(x.dtype))


def _gated_mlp(x, w13, w2):
    gate, up = jnp.split(
        jnp.einsum("...e,em->...m", x, w13.astype(x.dtype)), 2, axis=-1)
    return jnp.einsum("...m,me->...e", jax.nn.silu(gate) * up,
                      w2.astype(x.dtype))


def choose(lp, x, dims: RoutedDims):
    """What comes before the experts, row by row: normalised rows x (..., E)
    -> the chosen experts (..., K), their weights (..., K) float32 and the
    rows the experts act on (..., latent or E)."""
    idx, w = route(lp, x, dims)
    u = jnp.einsum("...e,el->...l", x, lp["w_down"].astype(x.dtype)) \
        if dims.latent else x
    return idx, w, u


def combine(lp, x, y, dims: RoutedDims):
    """What comes after them, row by row: the experts' weighted sum y
    (`held_experts`) back from the latent width, and the shared expert on
    the rows x themselves -> (..., E)."""
    if dims.latent:
        y = jnp.einsum("...l,le->...e", y, lp["w_up"].astype(x.dtype))
    if dims.shared_width:
        shared = _gated_mlp if dims.gated else _relu2_mlp
        y = y + shared(x, lp["ws1"], lp["ws2"])
    return y


def mixer(lp, x, dims: RoutedDims, real=None):
    """The layer on normalised rows x (B, S, E) -> (y (B, S, E), counts (2,)
    int32 as `held_experts` gives them, the chosen experts (B, S, K));
    `real` (B, S) bool: the rows that are not get no routed expert."""
    idx, w, u = choose(lp, x, dims)
    y, counts = held_experts(lp, u, idx, w, dims, real)
    return combine(lp, x, y, dims), counts, idx


def init_layer(key, hidden: int, dims: RoutedDims, dtype):
    """Seeded weights of one layer, normal / sqrt(fan_in); the router's
    correction bias small and not zero, so that choosing by `s + bias` and
    weighting by `s` differ (a stack's `init_params` then sets it to balance
    the experts: `models/transformer.py:balance_routers`)."""
    ks = jax.random.split(key, 8)
    inner = dims.latent or hidden       # the width the experts act on

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)
    lp = {"router": dense(ks[0], (hidden, dims.experts), hidden),
          "router_bias": 0.02 * jax.random.normal(
              ks[1], (dims.experts,), jnp.float32),
          "w1": dense(ks[3], (dims.held, inner,
                              (2 if dims.gated else 1) * dims.width), inner),
          "w2": dense(ks[4], (dims.held, dims.width, inner), dims.width)}
    if dims.latent:
        lp.update(w_down=dense(ks[2], (hidden, dims.latent), hidden),
                  w_up=dense(ks[5], (dims.latent, hidden), dims.latent))
    if dims.shared_width:
        lp.update(ws1=dense(ks[6], (hidden, (2 if dims.gated else 1)
                                    * dims.shared_width), hidden),
                  ws2=dense(ks[7], (dims.shared_width, hidden),
                            dims.shared_width))
    return lp
