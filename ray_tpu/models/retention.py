"""Power retention (the `P` operator of a pattern, models/transformer.py:
`retention_block`; Brumby-14B-Base's layers; Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239), degree 2, gated.

Per KV head g, with its query heads h (H / G of them), d the head width, on
the rotated, normed q and k and the plain v that `block_qkv` gives, and a
log gate a_g(t) <= 0 a token (`gate`), A_g(t) its running sum:

    attention form   w_h(t,s) = exp(A_g(t) - A_g(s)) (q_h(t).k_g(s) / sqrt d)^2   s <= t
                     o_h(t)   = sum_s w_h(t,s) v_g(s) / (sum_s w_h(t,s) + eps)
    state form       S_g(t) = exp(a_g(t)) S_g(t-1) + phi(k_g(t)) [v_g(t) | 1]^T
                     [n_h | z_h] = phi(q_h(t) / sqrt d)^T S_g(t);  o_h = n_h / (z_h + eps)

the same numbers, because phi(x).phi(y) = (x.y)^2: `phi` is the symmetric
square of x cut in blocks of `block` values, the rows c_ij vec(x_i (x) x_j)
for i <= j with c_ii = 1, c_ij = sqrt 2.  `block` 1 is the exact form
(D = d (d + 1) / 2 = 8,256 at d 128, 64.5 rows of 128 lanes); 16 gives
D = 36 x 256 = 9,216, 72 whole rows, and is what the state is held in.

The state a sequence carries is all its cache: {"s": (B, G, d, D), "z":
(B, G, D)} in `state_dtype` (float32): S's value columns with D on the
lanes, and its column of ones apart, so that neither is padded.

ONE mixer serves a prompt's rows and a decode step's one row, and one rule
that reads the shapes picks the form.  One row: the state form (`step`: the
update and the H / G query products in one pass over the state, a Pallas
kernel on a TPU).  More rows: every output from the attention form over the
call's own rows, a chunk of query rows at a time against the keys a chunk
can see, plus the carried state's part where the state has read anything
(phi(q) against it, decayed: a whole prompt pays nothing for it); the state
itself is built from phi(k) a segment of rows at a time and kept only at
the boundaries the caller keeps.  Rows past `length` and slots that are not
`live` move no state.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_HIGH = jax.lax.Precision.HIGH      # one operand is exact in bf16 throughout


@dataclasses.dataclass(frozen=True)
class RetentionDims:
    degree: int = 2             # the power p: only 2 is built
    num_heads: int = 40
    num_kv_heads: int = 8       # a state, and a gate, a KV head
    head_dim: int = 128
    # Rows a state checkpoint's spacing is counted in (llm/engine.py: a
    # checkpoint every `_CKPT_CHUNKS` chunks), and the query rows a prompt's
    # attention form is built for at a time.
    chunk: int = 128
    block: int = 16             # phi's block b: D = n (n + 1) / 2 x b^2
    eps: float = 1e-6
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.degree != 2 or self.head_dim % self.block \
                or self.num_heads % self.num_kv_heads:
            raise ValueError(f"power retention of {self}")

    @property
    def expanded(self) -> int:
        """D: phi's width."""
        n = self.head_dim // self.block
        return n * (n + 1) // 2 * self.block ** 2

    def param_count(self, hidden: int) -> int:
        """The gate's alone; the projections are the attention block's."""
        return hidden * self.num_kv_heads + self.num_kv_heads

    def state_bytes(self) -> int:
        """One sequence's state in one layer."""
        return (self.num_kv_heads * self.expanded * (self.head_dim + 1)
                * jnp.dtype(self.state_dtype).itemsize)


def zero_state(dims: RetentionDims, batch: int):
    """One layer's state of `batch` sequences that have read nothing."""
    G, d, D = dims.num_kv_heads, dims.head_dim, dims.expanded
    return {"s": jnp.zeros((batch, G, d, D), dims.state_dtype),
            "z": jnp.zeros((batch, G, D), dims.state_dtype)}


def phi(x, block: int):
    """The symmetric square of x (..., d) -> (..., D), in x's type."""
    n = x.shape[-1] // block
    iu, ju = np.triu_indices(n)
    xb = x.reshape(*x.shape[:-1], n, block)
    c = np.where(iu == ju, 1.0, math.sqrt(2.0)).astype(np.float32)
    pairs = xb[..., iu, :, None] * xb[..., ju, None, :] \
        * jnp.asarray(c, x.dtype)[:, None, None]
    return pairs.reshape(*x.shape[:-1], len(iu) * block * block)


def gate(w, h):
    """The log gate of normalised rows h (B, S, E): (B, S, G) float32,
    log sigmoid(h W_g + b_g) <= 0."""
    u = jnp.einsum("bse,eg->bsg", h, w["wg"].astype(h.dtype),
                   preferred_element_type=jnp.float32)
    return jax.nn.log_sigmoid(u + w["bg"].astype(jnp.float32))


def init_layer(key, hidden: int, dims: RetentionDims, dtype):
    """The gate's seeded weights.  A seeded gate is not a trained one: with
    the bias drawn like a matrix a head forgets in two tokens, and a wrong
    or stale checkpoint then reads as a right one.  With h W_g ~ N(0, 1) a
    bias b gives a mean log gate of about -exp(0.5 - b) a token; b is set
    so that the heads' lie between -1/1,024 and -1/128, evenly in the
    logarithm: a decay of e^-0.5 to e^-4 over 512 tokens."""
    G = dims.num_kv_heads
    wg = (jax.random.normal(key, (hidden, G), jnp.float32)
          / math.sqrt(hidden)).astype(dtype)
    mean = jnp.exp(jnp.linspace(math.log(1 / 1024), math.log(1 / 128), G))
    return {"wg": wg, "bg": 0.5 - jnp.log(mean)}


# ------------------------------------------------------------ one row -----

def step_path(dims: RetentionDims) -> str:
    """Which form a process's decode steps take: the kernel on a TPU, for
    128-wide heads over a state of whole 128-lane rows in float32."""
    ok = (jax.default_backend() == "tpu" and dims.head_dim == 128
          and dims.expanded % 128 == 0 and dims.state_dtype == "float32")
    return "pallas" if ok else "reference"


def reference_step(pq, pk, v, dec, s, z):
    """The state form in XLA: s' = dec s + v (x) phi(k), the query heads'
    products against s'.  pq (B, G, R, D), pk (B, G, D), v (B, G, d), dec
    (B, G), all float32 -> (s', z', n (B, G, R, d), zq (B, G, R))."""
    s = dec[..., None, None] * s + v[..., :, None] * pk[..., None, :]
    z = dec[..., None] * z + pk
    n = jnp.einsum("bgrD,bgvD->bgrv", pq, s, precision=_HIGH)
    return s, z, n, jnp.einsum("bgrD,bgD->bgr", pq, z, precision=_HIGH)


def _step_kernel(dec_ref, v_ref, pk_ref, pq_ref, s_ref, z_ref,
                 s_out, z_out, n_out, zq_out, *, heads: int):
    """One block of D lanes of one sequence's one KV head: the state read
    once, updated, written once, and the query heads' partial products
    added to what the blocks before left.  All on the vector unit: the
    products are 8 rows against a 128-lane tile, which the matrix unit
    would load as weights once a tile for nothing."""
    from jax.experimental import pallas as pl
    first = pl.program_id(2) == 0
    dec = dec_ref[0, 0]                                     # (1, 1)
    pk = pk_ref[0, 0]                                       # (1, T)
    s = dec * s_ref[0, 0] + v_ref[0, 0] * pk                # (d, T)
    s_out[0, 0] = s
    z = dec * z_ref[0, 0] + pk                              # (1, T)
    z_out[0, 0] = z
    pq = pq_ref[0, 0]                                       # (R, T)
    tiles = s.shape[1] // 128
    ones = jnp.ones((8, 128), jnp.float32)
    for r in range(heads):
        row = pq[r:r + 1]
        acc = s[:, :128] * row[:, :128]
        for t in range(1, tiles):
            acc = acc + s[:, t * 128:(t + 1) * 128] \
                * row[:, t * 128:(t + 1) * 128]
        # The last sum runs along the lanes and its result lies along them:
        # ones (8, 128) . acc^T on the matrix unit, row 0 of it.
        n = jax.lax.dot_general(
            ones, acc, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:1]         # (1, d)
        zq = jnp.sum(z * row, axis=1, keepdims=True)        # (1, 1)
        n_out[0, 0, r:r + 1] = jnp.where(first, n, n_out[0, 0, r:r + 1] + n)
        zq_out[0, 0, r:r + 1] = jnp.where(
            first, zq, zq_out[0, 0, r:r + 1] + zq)


def _lane_block(D: int, most: int = 1536) -> int:
    """The largest multiple of 128 up to `most` that divides D."""
    return max(t for t in range(128, most + 1, 128) if D % t == 0)


def retention_step(pq, pk, v, dec, s, z, interpret: bool = False):
    """`reference_step` as one pass over the state, in place (the state's
    buffers are the results'): grid (sequence, KV head, blocks of D), trace
    name `retention_step`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, G, R, D = pq.shape
    d = v.shape[-1]
    T = _lane_block(D)
    at = lambda b, g, t: (b, g, 0, t)                       # noqa: E731
    one = lambda b, g, t: (b, g, 0, 0)                      # noqa: E731
    f32 = jnp.float32
    s, z4, n, zq = pl.pallas_call(
        functools.partial(_step_kernel, heads=R),
        grid=(B, G, D // T),
        in_specs=[pl.BlockSpec((1, 1, 1, 1), one),          # dec
                  pl.BlockSpec((1, 1, d, 1), one),          # v, a column
                  pl.BlockSpec((1, 1, 1, T), at),           # phi(k)
                  pl.BlockSpec((1, 1, R, T), at),           # phi(q)
                  pl.BlockSpec((1, 1, d, T), at),           # s
                  pl.BlockSpec((1, 1, 1, T), at)],          # z
        out_specs=[pl.BlockSpec((1, 1, d, T), at),
                   pl.BlockSpec((1, 1, 1, T), at),
                   pl.BlockSpec((1, 1, R, d), one),
                   pl.BlockSpec((1, 1, R, 1), one)],
        out_shape=[jax.ShapeDtypeStruct(s.shape, f32),
                   jax.ShapeDtypeStruct((B, G, 1, D), f32),
                   jax.ShapeDtypeStruct((B, G, R, d), f32),
                   jax.ShapeDtypeStruct((B, G, R, 1), f32)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="retention_step", interpret=interpret,
    )(dec[..., None, None], v[..., None], pk[:, :, None], pq, s,
      z[:, :, None])
    return s, z4[:, :, 0], n, zq[..., 0]


def step(q, k, v, a, state, dims: RetentionDims, live=None):
    """One row a sequence: q (B, 1, H, d), k, v (B, 1, G, d), a (B, 1, G)
    -> (o (B, 1, H, d), state')."""
    B, _, H, d = q.shape
    G = dims.num_kv_heads
    f32 = jnp.float32
    a = a[:, 0]
    pk = phi(k[:, 0].astype(f32), dims.block)               # (B, G, D)
    if live is not None:
        # A slot that is not live: decay 1 and nothing added leave its
        # state as it was, bit for bit.
        a = jnp.where(live[:, None], a, 0.0)
        pk = jnp.where(live[:, None, None], pk, 0.0)
    pq = phi(q[:, 0].astype(f32).reshape(B, G, H // G, d)
             * (1.0 / math.sqrt(d)), dims.block)
    args = (pq, pk, v[:, 0].astype(f32), jnp.exp(a),
            state["s"].astype(f32), state["z"].astype(f32))
    if step_path(dims) == "pallas":
        s, z, n, zq = retention_step(*args)
    else:
        s, z, n, zq = reference_step(*args)
    o = n / (zq + dims.eps)[..., None]
    return o.reshape(B, 1, H, d).astype(q.dtype), \
        {"s": s.astype(state["s"].dtype), "z": z.astype(state["z"].dtype)}


# ---------------------------------------------------------- more rows -----

def _weights(qc, Ac, k, Ak, at, scale: float):
    """The attention form's weights of a chunk of query rows qc (B, C, G,
    R, d), whose first is row `at`, over keys k (B, T, G, d): (B, G, R, C,
    T) float32, zero where the key is later than the query."""
    C, T = qc.shape[1], k.shape[1]
    s = jnp.einsum("bcgrd,btgd->bgrct", qc, k,
                   preferred_element_type=jnp.float32) * scale
    seen = at + jnp.arange(C)[:, None] >= jnp.arange(T)[None, :]
    gap = jnp.moveaxis(Ac, 1, 2)[..., :, None] \
        - jnp.moveaxis(Ak, 1, 2)[..., None, :]              # (B, G, C, T)
    decay = jnp.where(seen, jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
    return s * s * decay[:, :, None]


def _parts(x, dtype):
    """Float32 x as a sum of arrays in `dtype`: itself, or for a 16-bit type
    its rounding and what the rounding left, 16 bits of mantissa in all."""
    if jnp.dtype(dtype) == jnp.float32:
        return (x,)
    hi = x.astype(dtype)
    return hi, (x - hi.astype(jnp.float32)).astype(dtype)


def _from_state(qc, Ac, s0, z0, dims: RetentionDims):
    """The carried state's part of a chunk's numerators and normalisers:
    phi(q) in the activations' type, as every product's operand is, against
    the float32 state in `_parts` (a pass of the matrix unit a part)."""
    pq = phi(qc, dims.block)                                # (B, C, G, R, D)
    e = jnp.exp(Ac)[..., None] * (1.0 / dims.head_dim)      # (B, C, G, 1)
    n = sum(jnp.einsum("bcgrD,bgvD->bcgrv", pq, part, precision=_HIGH,
                       preferred_element_type=jnp.float32) for part in s0)
    zq = sum(jnp.einsum("bcgrD,bgD->bcgr", pq, part, precision=_HIGH,
                        preferred_element_type=jnp.float32) for part in z0)
    return n * e[..., None], zq * e


def rows_outputs(q, k, v, A, state, dims: RetentionDims, chunks=None):
    """Every row's output from the attention form over the call's rows and
    the carried state: q (B, S, H, d), k, v (B, S, G, d), A (B, S, G) the
    gate's running sum -> (B, S, H, d); `chunks` (traced): the chunks of
    `dims.chunk` query rows that hold a real row, zeros in the others."""
    B, S, H, d = q.shape
    G, R = dims.num_kv_heads, H // dims.num_kv_heads
    C = min(S, dims.chunk)
    f32, scale = jnp.float32, 1.0 / math.sqrt(d)
    qg = q.reshape(B, S, G, R, d)
    z0 = state["z"].astype(f32)
    read = jnp.any(z0 != 0)             # phi(k)'s squares: 0 = nothing read
    s0, z0 = _parts(state["s"].astype(f32), q.dtype), _parts(z0, q.dtype)
    # A chunk sees no key past its own last row: one branch for every 1,024
    # keys, each built over the keys up to there.
    span = 8 * C
    upto = list(range(span, S, span)) + [S]

    def within(n, qc, Ac, at):
        w = _weights(qc, Ac, k[:, :n], A[:, :n], at, scale)
        num = jnp.einsum("bgrct,btgd->bcgrd", w.astype(v.dtype), v[:, :n],
                         preferred_element_type=f32)
        return num, jnp.moveaxis(w.sum(-1), -1, 1)          # (B, C, G, R)

    def body(i, out):
        at = i * C
        qc = jax.lax.dynamic_slice_in_dim(qg, at, C, 1)
        Ac = jax.lax.dynamic_slice_in_dim(A, at, C, 1)
        num, den = jax.lax.switch(
            (at + C - 1) // span,
            [functools.partial(within, n) for n in upto], qc, Ac, at)
        n0, zq0 = jax.lax.cond(
            read, lambda: _from_state(qc, Ac, s0, z0, dims),
            lambda: (jnp.zeros_like(num), jnp.zeros_like(den)))
        o = (num + n0) / (den + zq0 + dims.eps)[..., None]
        return jax.lax.dynamic_update_slice_in_dim(
            out, o.astype(q.dtype).reshape(B, C, H, d), at, 1)
    return jax.lax.fori_loop(0, S // C if chunks is None else chunks, body,
                             jnp.zeros(q.shape, q.dtype))


def rows_state(k, v, A, real, state, dims: RetentionDims, length=None,
               every: int = 0, slots: int = 0):
    """The state after the call's real rows, built from phi(k) a segment
    of rows at a time, and with `every` the states after every `every` rows
    that were passed whole: the last `slots` of them, boundary j (j x every
    rows) in slot (j - 1) % slots."""
    B, S, G, d = k.shape
    f32 = jnp.float32
    seg = min(S, every or 4 * dims.chunk)
    s, z = state["s"].astype(f32), state["z"].astype(f32)
    kept = (jnp.zeros((B, slots, *s.shape[1:]), state["s"].dtype),
            jnp.zeros((B, slots, *z.shape[1:]), state["z"].dtype))

    def add(s, z, at, upto, frm):
        """Rows [at, at + seg) that are `real` and at or past `frm`, decayed
        to row `upto - 1`, onto the state as it stood after row `frm - 1`."""
        ks, vs, As = (jax.lax.dynamic_slice_in_dim(a, at, seg, 1)
                      for a in (k, v, A))
        end = jax.lax.dynamic_index_in_dim(A, upto - 1, 1, keepdims=False)
        before = jnp.where(frm > 0, jax.lax.dynamic_index_in_dim(
            A, jnp.maximum(frm - 1, 0), 1, keepdims=False), 0.0)
        rows = at + jnp.arange(seg)
        mine = jax.lax.dynamic_slice_in_dim(real, at, seg, 1) \
            & (rows >= frm)[None]
        w = jnp.where(mine[..., None],
                      jnp.exp(jnp.minimum(end[:, None] - As, 0.0)), 0.0)
        pk = phi(ks.astype(f32), dims.block) * w[..., None]  # (B, seg, G, D)
        dec = jnp.exp(end - before)
        s = dec[..., None, None] * s + jnp.einsum(
            "bsgD,bsgv->bgvD", pk, vs.astype(f32), precision=_HIGH)
        return s, dec[..., None] * z + pk.sum(1)

    def whole(j, carry):
        s, z, kept = carry
        s, z = add(s, z, j * seg, (j + 1) * seg, j * seg)
        if slots:
            kept = tuple(jax.lax.dynamic_update_slice_in_dim(
                b, a[:, None].astype(b.dtype), j % slots, 1)
                for b, a in zip(kept, (s, z)))
        return s, z, kept
    full = S // seg if length is None else length // seg
    s, z, kept = jax.lax.fori_loop(0, full, whole, (s, z, kept))
    if length is not None:
        # The rows after the last whole segment (none: decay 1, nothing
        # added), from the segment that holds them.
        s, z = add(s, z, jnp.minimum(full, S // seg - 1) * seg, S,
                   full * seg)
    new = {"s": s.astype(state["s"].dtype), "z": z.astype(state["z"].dtype)}
    return new, ({"s": kept[0], "z": kept[1]} if every else None)


def mixer(q, k, v, a, state, dims: RetentionDims, length=None, live=None,
          every: int = 0, keep: int = 0):
    """Power retention of rows q (B, S, H, d), k, v (B, S, G, d) with log
    gates a (B, S, G) from `state`.  `length`, `live` and `every` as
    `mamba2.mixer` takes them; `keep`: the checkpoints a prompt leaves, the
    last so many it passed (`rows_state`).  Returns (o (B, S, H, d), state',
    checkpoints {"s": (B, slots, G, d, D), "z": (B, slots, G, D)} or None)."""
    B, S = q.shape[:2]
    if S == 1 and length is None:
        o, new = step(q, k, v, a, state, dims, live)
        return o, new, None
    slots = min(keep or S, S // every) if every else 0
    # The loops go by whole chunks and segments: a call of another size (a
    # test's) is padded with rows that are not real.
    unit = every or 4 * dims.chunk
    pad = -S % (unit if S > unit else dims.chunk if S > dims.chunk else 1)
    if pad:
        length = S if length is None else length
        q, k, v, a = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                      for x in (q, k, v, a))
    real = jnp.ones((B, S + pad), bool)
    if length is not None:
        real = real & (jnp.arange(S + pad)[None] < length)
    if live is not None:
        real = real & live[:, None]
    A = jnp.cumsum(jnp.where(real[..., None], a, 0.0), axis=1)
    chunks = None if length is None \
        else -(-length // min(S + pad, dims.chunk))
    o = rows_outputs(q, k, v, A, state, dims, chunks)
    new, kept = rows_state(k, v, A, real, state, dims, length, every, slots)
    return o[:, :S], new, kept
