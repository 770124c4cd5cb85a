"""Attention over the cached tokens an indexer picks (the `S` blocks of a
pattern, models/transformer.py: `indexed_block`).

A query row t scores every cached token s it may see with the indexer,

    I[t, s] = sum_j w_j[t] * relu(qI_j[t] . kI[s])        (float32)

keeps the `top_k` highest (all of them while it sees no more than that; the
least index wins an exact tie) and attends those rows and no others.  The
index keys kI lie in a page pool of their own beside the keys and values
(llm/programs.py: `CACHES["sparse"]`), one row of `width` values a token
and layer, filled with zeros to one 128-lane row.

THE FORMS, and the one rule that picks (`sparse_path`, from the shapes):

- "masked": a prefill's block of query rows against all the key rows it
  is given.  The set is a mask (rows, keys) found from the k-th largest
  score of each row (`kth_largest`), and the attention is the dense one
  under that mask, a KV group at a time: the rows of one block share their
  keys, so gathering 2,048 rows for each of 512 queries would move 2,048
  times the keys it reads.
- "paged": a decode step's one query a sequence, where the paged kernel
  takes the shapes (a TPU, heads of whole lane rows, an index key held as
  one lane row: `paged_attention_over_picks`).  Two Pallas kernels walk the
  slot's LIVE pages, tables and lengths by scalar prefetch, the three pools
  left in HBM where the step has just written its token's rows, and the
  cut stands between them, because it needs every score before any row is
  attended: `index_select` copies a slot's index keys a chunk of pages at
  a time and scores them, (B, T) float32 with -inf past a slot's length;
  `select_mask` marks the `top_k` highest (32 counting passes over half a
  megabyte and a running count of the ties by two triangular products);
  and `ops/paged_attention.py`'s kernel, given those marks (`seen`),
  attends the marked rows of every live page.  It reads EVERY live row's
  key and value (196 MB a layer at 8 x 12k tokens where the picks are 34)
  and is still the shorter: see below.
- "gathered": the plain form of a decode step, the CPU's, the kernels'
  parity oracle and what shapes they do not tile take.  The slot's index
  keys are read through its page row (every page of the table, live or
  not), `lax.top_k` gives the positions (`pick_positions`), and the chosen
  key and value rows are gathered where they lie in the pools
  (`gathered_attention`).

Alone on a v5e at the published widths, 8 slots, a layer (PERF.md §6, PRs
54 and 55; every operand that costs depending on the timing loop's carry:
PR 54's first 0.14 + 0.07 ms were gathers hoisted out of the loop).  A
prefill's block of 512 rows against a 16,384-row bucket: 0.38 ms of index
scores, 0.99 of mask and 5.2 of masked attention in XLA, which is why a
whole prompt goes through the prefill kernel's `mask=` operand.  A decode
step, "gathered": 0.65 ms at every length (the slot's whole index table
gathered 0.16, scores and `lax.top_k` 0.03, the picked key and value rows,
16k of 1 KB each, and the attention over them 0.47: XLA's gather moves a
row in 9.5 ns, 108 GB/s).  "paged": 0.33 / 0.47 / 0.60 ms at 8,192 /
12,000 / 16,128 tokens a slot and 0.11 with one slot of eight live:
`index_select` 0.13 at 12k (6,000 copies of 4 KB: bound by their COUNT,
18.9 ns a copy and 0.15 us a chunk, not by 25 MB), the cut 0.03, the
paged kernel under the marks 0.30 (12,000 copies of 16 KB, 25 ns each,
where the bytes ask 0.24).  The forms cross at 17.7k tokens a slot, past
what the cell reaches; nothing picks between them by length.  A third
candidate, the picked rows copied by the kernel's own DMAs, lost at every
length: Mosaic refuses a copy of ONE row (4 of a tile's 8 sublanes), and
copies of 2 tokens, 32k a layer, take 0.75 ms (23 ns each).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention

_NEG = -1e30
# Index keys one chunk of `index_select` holds in VMEM, a lane row a token.
# The pass is bound by the COUNT of its page copies (18.9 ns each and 0.15
# us a chunk on a v5e, 4 KB a copy: PERF.md §6, PR 55), and `start` always
# issues a whole chunk's: 64 pages of 16 are within 2% of 128 over the
# cell's lengths and a dead slot's one chunk is 1.4 us.
_SELECT_CHUNK_ROWS = 1024


def select_chunk_pages(page: int) -> int:
    """Pages a chunk of `index_select` holds."""
    return max(1, _SELECT_CHUNK_ROWS // page)


def sparse_path(rows: int, q_shape=None, pool_shape=None, index_shape=None,
                tables_shape=None) -> str:
    """Which form a call with `rows` query rows a sequence takes.  More
    than one (a prefill's row block, whose rows share their keys) attend
    densely under a mask.  One row (a decode step) reads its pools "paged",
    through the two kernels that walk a slot's live pages, where the paged
    kernel takes these shapes in this process (`decode_path`: a TPU, and
    what it tiles: q (.., H, D), the key pool, the slots' tables) and the
    index pool's rows are whole lane rows; else, and asked of no shapes, it
    picks positions and gathers them: the plain form."""
    if rows != 1:
        return "masked"
    paged = q_shape is not None and index_shape[-1] % 128 == 0 \
        and index_shape[-2] % 16 == 0 and paged_attention.decode_path(
            q_shape, pool_shape, tables_shape) == "pallas"
    return "paged" if paged else "gathered"


def index_scores(qi, wi, keys):
    """qi (R, J, W) index queries and wi (R, J) their weights, over index
    keys (T, W) shared by the rows -> I (R, T) float32.  A head at a time:
    all heads' products at once are (J, R, T) float32, half a gigabyte for
    a block of 512 rows against 16,384 keys."""
    keys = keys.astype(qi.dtype)

    def head(acc, at):
        q, w = at                                   # (R, W), (R,)
        s = jnp.einsum("rw,tw->rt", q, keys,
                       preferred_element_type=jnp.float32)
        return acc + w[:, None] * jax.nn.relu(s), None
    zero = jnp.zeros((qi.shape[0], keys.shape[0]), jnp.float32)
    out, _ = jax.lax.scan(
        head, zero, (qi.transpose(1, 0, 2),
                     wi.astype(jnp.float32).transpose(1, 0)))
    return out


def _ordered(x):
    """float32 -> uint32 whose order is the floats' (-0 below +0)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(scores, k: int):
    """The k-th largest of each row of `scores` (R, T) float32, as its
    ordered bits (`_ordered`) (R,) uint32; k <= T.  The largest bit pattern
    that k values reach, found a bit at a time: 32 counting passes and no
    sort, which would order rows x keys values to keep one of each row
    (alone on a v5e, the 2,048th of 512 rows x 8,192 / 16,384 keys: 0.29 /
    0.45 ms counted, 2.05 / 4.81 sorted by `lax.top_k`: PERF.md §6, PR 54).
    A decode step wants the positions, not the cut: `pick_positions`."""
    u = _ordered(scores)

    def bit(i, found):
        cand = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[:, None], axis=1)
        return jnp.where(n >= k, cand, found)
    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros((scores.shape[0],), jnp.uint32))


def running_count(marks):
    """(R, T) bool -> (R, T) int32: how many of a row's marks lie at or
    before each position; `jnp.cumsum(marks, 1)` as two products with a
    triangle of ones, within blocks of 128 and over the blocks' totals (0 /
    1 in bfloat16, sums in float32: exact), T a multiple of 128.  XLA's own
    cumulative sum took 0.03 ms of a decode step's layer on a v5e, of (8,
    16,384) and of (8, 128) alike (PERF.md §6, PR 55)."""
    R, T = marks.shape
    upto = (jnp.arange(128)[:, None] <= jnp.arange(128)[None]
            ).astype(jnp.bfloat16)
    within = jnp.einsum("rnk,kj->rnj", marks.reshape(R, T // 128, 128
                                                     ).astype(jnp.bfloat16),
                        upto, preferred_element_type=jnp.float32
                        ).astype(jnp.int32)
    # (A block's count is at most 128: whole in bfloat16.)
    n = jnp.arange(T // 128)
    before = jnp.einsum("rn,nm->rm", within[:, :, -1].astype(jnp.bfloat16),
                        (n[:, None] < n[None]).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32).astype(jnp.int32)
    return (within + before[:, :, None]).reshape(R, T)


def select_mask(scores, visible, k: int, count=None):
    """Which keys each row attends: (R, T) bool, the `k` visible keys with
    the highest `scores` (all the visible ones where there are no more than
    k; of exactly equal scores at the cut the least indices).  `count`:
    the running count of the ties (None: `jnp.cumsum`)."""
    T = scores.shape[1]
    if k >= T:
        return visible
    u = jnp.where(visible, _ordered(scores), 0)
    cut = kth_largest(jnp.where(visible, scores, -jnp.inf), k)[:, None]
    above = u > cut
    ties = (u == cut) & visible
    room = k - jnp.sum(above, axis=1, keepdims=True)
    running = jnp.cumsum(ties, axis=1) if count is None else count(ties)
    return above | (ties & (running <= room))


def masked_attention(q, keys, values, mask, scale: float):
    """q (R, H, D) over keys, values (T, KV, D), key t open to row r where
    mask (R, T) -> (R, H, D).  A KV group at a time: a group's scores are
    (H / KV, R, T) float32."""
    R, H, D = q.shape
    KV = keys.shape[1]

    def group(_, at):
        qg, kg, vg = at                     # (R, G, D), (T, D), (T, D)
        s = jnp.einsum("rgd,td->grt", qg, kg,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[None], s, _NEG)
        # (The row maximum behind a barrier: models/transformer.py,
        # `_latent_softmax`.)
        top = jax.lax.optimization_barrier(jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - top)
        p = (p / jnp.sum(p, -1, keepdims=True)).astype(q.dtype)
        return None, jnp.einsum("grt,td->rgd", p, vg)
    _, o = jax.lax.scan(group, None, (
        q.reshape(R, KV, H // KV, D).transpose(1, 0, 2, 3),
        keys.transpose(1, 0, 2), values.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3).reshape(R, H, D)


def pick_positions(qi, wi, index_rows, lengths, k: int):
    """A decode step's selection: qi (B, J, W), wi (B, J) over each slot's
    index keys index_rows (B, T, W'), a key and zeros up to W' as the pool
    holds them, of which the first `lengths + 1` (B,) are visible (the
    step's own token included) -> (positions (B, K) int32, valid (B, K)
    bool), K = min(k, T): the visible positions with the highest scores,
    highest first.  The queries are filled with zeros to the rows' width:
    the rows are read as they lie."""
    fill = index_rows.shape[-1] - qi.shape[-1]
    qi = jnp.pad(qi, ((0, 0), (0, 0), (0, fill)))
    s = jnp.einsum("bjw,btw->bjt", qi, index_rows.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("bjt,bj->bt", jax.nn.relu(s), wi.astype(jnp.float32))
    T = s.shape[1]
    seen = jnp.arange(T)[None, :] <= lengths[:, None]
    top, at = jax.lax.top_k(jnp.where(seen, s, -jnp.inf), min(k, T))
    return at.astype(jnp.int32), top > -jnp.inf


def gathered_attention(q, pool_k, pool_v, tables, positions, valid, layer,
                       scale: float, heads):
    """q (B, H, D) over the rows at `positions` (B, K) of each slot's
    sequence, read where they lie: pool_k, pool_v (L, N, page, *row) as
    ops/paged_attention.py holds `heads` = (KV, D), `tables` (B, P) the
    slots' page rows, `layer` the layer's index -> (B, H, D).  ONE gather a
    pool, of the chosen rows out of the whole pool (a layer sliced out
    first would be a copy of it)."""
    from .paged_attention import head_rows
    L, N, page = pool_k.shape[:3]
    B, H, D = q.shape
    KV = heads[0]
    phys = jnp.take_along_axis(tables, positions // page, axis=1) * page \
        + positions % page
    flat = (layer * N * page + phys).reshape(-1)

    def rows(pool):
        got = pool.reshape(L * N * page, *pool.shape[3:])[flat]
        return head_rows(got, *heads).reshape(B, -1, KV, heads[1])
    k, v = rows(pool_k), rows(pool_v)
    s = jnp.einsum("bkgd,btkd->bkgt", q.reshape(B, KV, H // KV, D), k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgt,btkd->bkgd", p, v).reshape(B, H, D)


def _index_select_kernel(layer_ref, tables_ref, lengths_ref,  # scalar prefetch
                         q_ref, w_ref, k_hbm, o_ref, kbuf, sem, *,
                         page: int, chunk_pages: int):
    """Every slot's index scores of one layer, the index keys read by the
    slot's live pages as `_paged_kernel` reads keys: (slot, chunk) pairs in
    order, the next chunk's pages in flight while one is scored, a dead
    slot one chunk.  q_ref (B, J, W) the index queries filled to the pool's
    row, w_ref (B, J, 1) float32, k_hbm (L, N, page, W) where it lies,
    o_ref (B, T) float32: I[b, t] up to the slot's length, -inf past it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = q_ref.shape[0]
    P = tables_ref.shape[0] // B
    R = chunk_pages * page
    li = layer_ref[0]

    def n_pages(b):
        return lengths_ref[b] // page + 1

    def page_copy(pid, buf, j):
        return pltpu.make_async_copy(k_hbm.at[li, pid], kbuf.at[buf, j],
                                     sem.at[buf])

    def start(b, c, buf):
        # (Past the slot's last page the last one again, as the paged
        # kernel has it: what it holds past the length reads -inf below.)
        last = n_pages(b) - 1
        for j in range(chunk_pages):
            page_copy(tables_ref[b * P + jnp.minimum(c * chunk_pages + j,
                                                     last)], buf, j).start()

    def wait(buf):
        # ONE wait for the chunk's copies: a wait reads no source and
        # counts the bytes of what it names, here the whole buffer.
        pltpu.make_async_copy(k_hbm.at[li, pl.ds(0, chunk_pages)],
                              kbuf.at[buf], sem.at[buf]).wait()

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    tok = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    start(0, 0, 0)

    def slot_body(b, item):
        q, w = q_ref[b], w_ref[b]                       # (J, W), (J, 1)
        length = lengths_ref[b]
        nc = (n_pages(b) + chunk_pages - 1) // chunk_pages

        def chunk_body(c, item):
            buf = item % 2
            more = c + 1 < nc
            nb = jnp.where(more, b, b + 1)

            @pl.when(nb < B)
            def _prefetch():
                start(nb, jnp.where(more, c + 1, 0), 1 - buf)

            wait(buf)
            s = jax.lax.dot_general(
                q, kbuf[buf].reshape(R, -1), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # (J, R)
            s = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
            o_ref[pl.ds(b, 1), pl.ds(c * R, R)] = jnp.where(
                tok + c * R <= length, s, -jnp.inf)
            return item + 1
        return jax.lax.fori_loop(0, nc, chunk_body, item)

    jax.lax.fori_loop(0, B, slot_body, jnp.int32(0))


def index_select(qi, wi, pool_i, tables, lengths, layer, *,
                 interpret: bool = False):
    """A decode step's index scores through the kernel: qi (B, J, W), wi
    (B, J) over the index keys pool_i (L, N, page, W') holds for the slots'
    `tables` (B, P) in layer `layer` -> I (B, P * page) float32, a slot's
    first `lengths + 1` and -inf past them: `pick_positions`' scores (bf16
    products, float32 sums), of the live pages alone."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, J, W = qi.shape
    page, row = pool_i.shape[2:]
    T = tables.shape[1] * page
    chunk = select_chunk_pages(page)
    R = chunk * page
    kernel = functools.partial(_index_select_kernel, page=page,
                               chunk_pages=chunk)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    if not interpret:       # (as `_paged_lanes_pallas`: never parked in VMEM)
        pool_i = pltpu.with_memory_space_constraint(pool_i, pltpu.HBM)
    scores = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[vmem, vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[pltpu.VMEM((2, chunk, page, row), pool_i.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, -(-T // R) * R), jnp.float32),
        name="index_select",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.pad(qi, ((0, 0), (0, 0), (0, row - W))).astype(pool_i.dtype),
      wi.astype(jnp.float32)[:, :, None], pool_i)
    return scores[:, :T]


@functools.partial(jax.jit, static_argnames=("k", "scale", "interpret"))
def paged_attention_over_picks(q, qi, wi, pool_k, pool_v, pool_i, tables,
                               lengths, layer, k: int, scale: float,
                               interpret: bool = False):
    """The "paged" decode form: q (B, H, D), the index queries qi (B, J, W)
    and weights wi (B, J) against the three pools where they lie -> (o (B,
    H, D), seen (B, T) bool: what each slot attended).  Two kernels over a
    slot's live pages with the cut between them: `index_select`'s scores,
    `select_mask` (the `k` highest of the visible, the least index at an
    exact tie: `pick_positions`' sets), and the paged kernel under those
    marks.  `interpret`: the tests', both kernels interpreted.  A `jit` of
    its own, `layer` traced: a stack whose layers are traced one by one
    (Keye's six) traces and lowers the two kernels ONCE (their page copies
    are unrolled, 400 of them: lowered six times over they were 21 s of
    every replica's start on the chip's host, PERF.md §6, PR 55)."""
    scores = index_select(qi, wi, pool_i, tables, lengths, layer,
                          interpret=interpret)
    seen = select_mask(scores, scores > -jnp.inf, k, count=running_count
                       if scores.shape[1] % 128 == 0 else None)
    if interpret:
        o = paged_attention._paged_decode_pallas(
            q, pool_k, pool_v, tables, lengths, layer, scale, True, seen)
    else:
        o = paged_attention.paged_decode_attention(
            q, pool_k, pool_v, tables, lengths, layer, scale=scale, seen=seen)
    return o, seen


def positions_of(seen, k: int):
    """(B, T) bool of at most `k` marks a row -> (positions (B, K) int32,
    valid (B, K)), K = min(k, T), as `pick_positions` gives them (in order
    of position, not of score)."""
    K = min(k, seen.shape[1])
    at = jnp.argsort(~seen, axis=1, stable=True)[:, :K].astype(jnp.int32)
    return at, jnp.take_along_axis(seen, at, axis=1)
