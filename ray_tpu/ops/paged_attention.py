"""Paged decode attention: one query token per slot against a paged KV pool.

The serving engine's decode step (llm/programs.py:_decode_fn) attends each
slot's new token to the keys and values its page table holds.  On a TPU this
is a Pallas kernel that moves only the pages a slot holds: the pool stays in
HBM, each live page is copied to VMEM once, and all `H // KV` query heads of
a KV head are computed against that one copy (GQA by grouping: no widened
keys).  Softmax is online over chunks of pages, in float32.

A page of one layer is `(page, KV, D)`; seen as rows it is `(page * KV, D)`
with row `t * KV + h`.  The kernel multiplies all `H` query heads against
all rows of a chunk on the MXU (the unit is idle in decode) and masks the
rows of the other KV heads, so no key is ever regrouped in memory.

`paged_decode_attention` runs the kernel on a TPU for shapes it tiles
(`kernel_tiles`) and `reference_paged_attention` everywhere else (the CPU
test mesh, rows that fill no whole lane rows, pages under 16 rows, tables
over scalar memory); the reference is also the kernel's parity oracle.

WHICH ROWS THE KERNEL TAKES.  Rows held by heads, `(page, KV, D)` with `D`
whole lane rows, as above.  Rows of lanes (`pool_row` below: heads narrower
than a lane row, a token's `KV * D` values one row of whole lane rows) it
reads as ONE KV head as wide as the row, `W = KV * D`: Mosaic slices no 64
lanes out of a row, so each query head is widened to `W` with zeros outside
its KV head's lanes (`_lane_queries`), the other heads' lanes add exact
zeros to its scores, and of its `W` sums it keeps its own `D`
(`_lane_results`); the products run on the matrix unit, idle in decode.  A
latent row (one row a token, key and value at once) is the same kernel with
one pool.  THE CHUNK: `start` always issues a whole chunk's copies (past a
slot's last page the last one is fetched again), so a chunk costs its full
bytes however short the slot, a dead slot (length 0) included, and its size
goes by the row's bytes: `_CHUNK_ROWS` rows of one lane row each for heads
(512 KiB a pool), half that many lane rows for a lanes pool
(`_lanes_chunk_pages`: 16 pages of 16 rows of 512 lanes; timed on the chip
at 4-64 pages: short chat contexts beside dead slots want 8, long documents
64, and 16 is within 0.03 ms a step of either's best), 1,024 rows for a
latent pool.

THE POOL'S ROW follows the head width (`pool_row`).  A token's keys of one
layer are `(KV, D)`, and where `D` is a whole number of 128-lane rows the
pool holds them so: `(L, N, page, KV, D)`.  A head narrower than a lane row
leaves that array without a 128-wide minor dimension; the chip then lays the
pages innermost and every scatter and gather of the serving step converts
the whole pool.  So where `KV * D` is a multiple of 128 the pool holds a
token's keys of all KV heads as ONE row of `KV * D` lanes, `(L, N, page,
KV * D)`, for which there is one layout to want.  A LATENT layer caches one
row a token that is key and value at once (`[c | k_r]`, 512 + 64 values for
the published widths): one KV "head" that is no whole number of lane rows.
The pool holds it as one row padded with zeros to whole lane rows, `(L, N,
page, 640)` (the chip pads a 576-wide minor dimension to 640 either way),
and a latent model has ONE pool where the others have two: the second is
None, an empty tree, so the engine's allocation, install, decode write and
gathers stay one code path (`jax.tree.map` over the pair).  `pool_shape`,
`pool_rows` and `head_rows` are the only places that know; the engine's
allocation, install, decode write and every reader go through them.
`paged_latent_attention` is the decode step's read of such a pool: the
same kernel, one copy of a page serving as keys and, in its first lanes,
as values.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

# Rows (tokens x KV heads) of keys one chunk holds in VMEM: K and V chunks,
# double-buffered, take 4 x _CHUNK_ROWS x D x 2 bytes (2 MiB at D = 128).
_CHUNK_ROWS = 2048
_LANES = 128


def pool_row(num_kv_heads: int, head_dim: int) -> str:
    """How the pool holds one token's keys (or values) of one layer:
    "heads", `(KV, D)`; "lanes", one row of `KV * D` lanes, for heads
    narrower than a lane row whose KV heads together fill whole ones; or
    "latent", the one row a token that a latent layer caches (one KV head,
    wider than a lane row and no whole number of them: 576), padded to whole
    lane rows."""
    if num_kv_heads == 1 and head_dim > _LANES and head_dim % _LANES:
        return "latent"
    narrow = head_dim < _LANES and (num_kv_heads * head_dim) % _LANES == 0
    return "lanes" if narrow else "heads"


def _row_shape(num_kv_heads: int, head_dim: int) -> tuple:
    row = pool_row(num_kv_heads, head_dim)
    if row == "latent":
        return (-(-head_dim // _LANES) * _LANES,)
    if row == "lanes":
        return (num_kv_heads * head_dim,)
    return (num_kv_heads, head_dim)


def pool_shape(layers: int, n_pages: int, page: int, num_kv_heads: int,
               head_dim: int) -> tuple:
    """The shape of one pool (keys or values) of `layers` attention layers."""
    return (layers, n_pages, page) + _row_shape(num_kv_heads, head_dim)


def pool_rows(x, num_kv_heads: int, head_dim: int):
    """Rows `(..., KV, D)` as the pool holds them (the two minor dimensions
    merge where the row is "lanes": no element moves; a "latent" row gains
    its zeros)."""
    row = _row_shape(num_kv_heads, head_dim)
    if pool_row(num_kv_heads, head_dim) == "latent":
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, row[0] - head_dim)])
    return x.reshape(x.shape[:-2] + row)


def head_rows(x, num_kv_heads: int, head_dim: int):
    """Rows read out of a pool, `(..., *row)`, as `(..., KV, D)`."""
    lead = x.ndim - len(_row_shape(num_kv_heads, head_dim))
    if pool_row(num_kv_heads, head_dim) == "latent":
        x = x[..., :head_dim]
    return x.reshape(x.shape[:lead] + (num_kv_heads, head_dim))


def _own_lanes(H: int, KV: int):
    """(1, H, KV, 1): whether KV head `k`'s lanes of a row are query head
    `h`'s own (heads group in order: head h reads KV head h // (H // KV))."""
    own = (jnp.arange(H) // (H // KV))[:, None] == jnp.arange(KV)  # (H, KV)
    return own[None, :, :, None]


def _lane_queries(q, own):
    """Queries (B, H, D) as wide as a row of `KV * D` lanes: zeros outside
    their KV head's lanes, so the other heads' products add exact zeros."""
    B, H, D = q.shape
    return jnp.where(own, q[:, :, None], 0).reshape(B, H, own.shape[2] * D)


def _lane_results(o, own):
    """Of each head's row of sums (B, H, KV * D) its own lanes, (B, H, D)."""
    B, H, W = o.shape
    KV = own.shape[2]
    return jnp.where(own, o.reshape(B, H, KV, W // KV), 0).sum(2)


def _valid(T: int, lengths, seen):
    """(B, T): the rows a slot attends, those up to its length and, of
    them, those `seen` (B, T) marks (None: all)."""
    valid = jnp.arange(T)[None] <= lengths[:, None]
    return valid if seen is None else valid & seen


def _lanes_attention(q, ck, cv, lengths, scale, seen=None):
    """The reference over rows of `C = KV * D` lanes, ck / cv (B, T, C), which
    are never split into heads (that would move 64-lane halves of every
    gathered row about): each query head is widened to a whole row with
    zeros outside its KV head's lanes, so the other heads' products add exact
    zeros to its scores, and of its output row it keeps its own lanes."""
    H, D = q.shape[1:]
    T, KV = ck.shape[1], ck.shape[2] // D
    own = _own_lanes(H, KV)
    s = jnp.einsum("bhc,btc->bht", _lane_queries(q, own), ck,
                   preferred_element_type=jnp.float32) * scale
    valid = _valid(T, lengths, seen)                              # (B, T)
    s = jnp.where(valid[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # Dead rows may hold anything (NaN included): select, never multiply.
    cv = jnp.where(valid[:, :, None], cv, 0)
    o = jnp.einsum("bht,btc->bhc", p, cv,
                   preferred_element_type=jnp.float32)
    return _lane_results(o, own).astype(q.dtype)


def reference_paged_attention(q, pool_k, pool_v, tables, lengths, layer=None,
                              *, scale: Optional[float] = None, seen=None):
    """Plain `jax.numpy` form of `paged_decode_attention` (same signature):
    gathers every slot's whole table and masks what is past its length."""
    B, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pages = (tables,) if layer is None else (layer, tables)
    if pool_k.ndim == 2 + len(pages):           # rows of lanes: (.., KV * D)
        rows = (B, -1, pool_k.shape[-1])
        return _lanes_attention(q, pool_k[pages].reshape(rows),
                                pool_v[pages].reshape(rows), lengths, scale,
                                seen)
    page, KV = pool_k.shape[-3:-1]
    T = tables.shape[1] * page
    ck = pool_k[pages].reshape(B, T, KV, D)
    cv = pool_v[pages].reshape(B, T, KV, D)
    qg = q.reshape(B, KV, H // KV, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, ck,
                   preferred_element_type=jnp.float32) * scale
    valid = _valid(T, lengths, seen)                              # (B, T)
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # Dead rows may hold anything (NaN included): select, never multiply.
    cv = jnp.where(valid[:, :, None, None], cv, 0)
    o = jnp.einsum("bkgt,btkd->bkgd", p, cv,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, D).astype(q.dtype)


def _wide_queries(q, pool):
    """Queries (B, H, C) as wide as the latent pool's padded row."""
    return jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - q.shape[-1])))


def reference_latent_attention(q, pool, tables, lengths, layer=None, *,
                               scale: float, value_lanes: int):
    """Plain `jax.numpy` form of `paged_latent_attention` (same signature):
    gathers every slot's whole table and masks what is past its length.  A
    latent row is a row of lanes with ONE KV head that is key and value:
    `_lanes_attention` over it, of whose sums the first lanes are kept."""
    pages = (tables,) if layer is None else (layer, tables)
    rows = pool[pages].reshape(q.shape[0], -1, pool.shape[-1])    # (B, T, W)
    return _lanes_attention(_wide_queries(q, pool), rows, rows, lengths,
                            scale)[..., :value_lanes]


def _paged_kernel(layer_ref, tables_ref, lengths_ref,      # scalar prefetch
                  q_ref, *refs,
                  scale: float, page: int, kv_heads: int, chunk_pages: int,
                  value_lanes: int = 0, selects: bool = False):
    """All slots of one layer.  Work is the list of (slot, chunk) pairs in
    order; while one chunk is computed the next one's pages are in flight,
    across slot boundaries too.  `refs`: k_hbm, v_hbm, o_ref, kbuf, vbuf,
    bias_scr, sem; with `value_lanes` (a latent pool) there is one pool and
    one buffer, k_hbm, o_ref, kbuf, bias_scr, sem: a copied row is the key
    and, in its first `value_lanes` lanes, the value.  With `selects` the
    first of `refs` is `seen_ref` (B * chunks a table, R) float32, whole in
    VMEM, a row for each chunk of each slot's table: of a slot's rows up to
    its length the kernel attends those marked other than 0 and no others
    (every live page is still copied); the buffers are then (2, chunk_pages,
    rows_page, width) and ONE wait a pool covers a chunk's copies (12,000
    waits a layer at 8 x 12k tokens were 0.04 ms of 0.44 on a v5e)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if selects:
        seen_ref, *refs = refs
    if value_lanes:
        k_hbm, o_ref, kbuf, bias_scr, sem = refs
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, bias_scr, sem = refs
    B, H, _ = q_ref.shape
    D = o_ref.shape[-1]
    P = tables_ref.shape[0] // B
    rows_page = page * kv_heads
    R = chunk_pages * rows_page
    groups = H // kv_heads
    li = layer_ref[0]

    def n_pages(b):
        return lengths_ref[b] // page + 1

    def n_chunks(b):
        return (n_pages(b) + chunk_pages - 1) // chunk_pages

    def page_copies(pid, buf, j):
        # (`selects`: the buffers are (2, chunk_pages, rows_page, width).)
        dst = j if selects else pl.ds(j * rows_page, rows_page)
        keys = pltpu.make_async_copy(k_hbm.at[li, pid], kbuf.at[buf, dst],
                                     sem.at[0, buf])
        if value_lanes:
            return (keys,)
        return (keys,
                pltpu.make_async_copy(v_hbm.at[li, pid], vbuf.at[buf, dst],
                                      sem.at[1, buf]))

    def start(b, c, buf):
        """Start the chunk's page copies.  Past the slot's last page the
        last one is fetched again: the buffer then holds live pages only,
        and the repeated rows lie past `lengths[b]`, where the mask drops
        them."""
        last = n_pages(b) - 1
        for j in range(chunk_pages):
            pid = tables_ref[b * P + jnp.minimum(c * chunk_pages + j, last)]
            for cp in page_copies(pid, buf, j):
                cp.start()

    def wait(buf):
        if selects:
            # ONE wait a pool for the chunk's copies: it counts the bytes
            # of what it names, here the whole buffer.
            some = pl.ds(0, chunk_pages)
            pltpu.make_async_copy(k_hbm.at[li, some], kbuf.at[buf],
                                  sem.at[0, buf]).wait()
            pltpu.make_async_copy(v_hbm.at[li, some], vbuf.at[buf],
                                  sem.at[1, buf]).wait()
            return
        for j in range(chunk_pages):
            for cp in page_copies(0, buf, j):    # a wait reads no source
                cp.wait()

    # Row r of a chunk is token r // KV of KV head r % KV: query head j sees
    # the rows of head j // groups alone.
    row = jax.lax.broadcasted_iota(jnp.int32, (H, R), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, R), 0)
    bias_scr[...] = jnp.where(row % kv_heads == head // groups,
                              0.0, -1e30).astype(jnp.float32)
    row_tok = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) // kv_heads

    start(0, 0, 0)

    def slot_body(b, item):
        q = q_ref[b]                                          # (H, D)
        length = lengths_ref[b]
        nc = n_chunks(b)

        def chunk_body(c, carry):
            m, l, acc, item = carry
            buf = item % 2
            more = c + 1 < nc
            nb = jnp.where(more, b, b + 1)

            @pl.when(nb < B)
            def _prefetch():
                start(nb, jnp.where(more, c + 1, 0), 1 - buf)

            wait(buf)
            k = kbuf[buf]                                     # (R, D)
            v = k[:, :value_lanes] if value_lanes else vbuf[buf]
            if selects:
                k, v = k.reshape(R, -1), v.reshape(R, -1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # (H, R)
            s = s * scale + bias_scr[...]
            live = row_tok + c * (chunk_pages * page) <= length
            if selects:
                live &= seen_ref[pl.ds(b * (seen_ref.shape[0] // B) + c, 1),
                                 :] != 0
            s = jnp.where(live, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            if selects:
                # A chunk may hold no row that is seen: until one is, the
                # maximum stands at the mask's own value and exp gives 1.
                p = jnp.where(live, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (H, D)
            return m_new, l, acc, item + 1

        m, l, acc, item = jax.lax.fori_loop(
            0, nc, chunk_body,
            (jnp.full((H, 1), -1e30, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, D), jnp.float32), item))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return item

    jax.lax.fori_loop(0, B, slot_body, jnp.int32(0))


def _chunk_marks(seen, tokens: int, kv_heads: int):
    """seen (B, T) bool -> (B * chunks, tokens * kv_heads) float32: a row
    for each chunk of `tokens` tokens of each slot's table (filled with
    zeros to whole chunks), a token's mark once for each of its KV heads'
    rows, as a chunk holds them.  The repeat is a product with a 0 / 1
    matrix (exact): XLA's own interleaves lanes through two relayouts of
    the whole array, 0.04 ms a layer at 8 x 16,384 x 4 on a v5e."""
    B, T = seen.shape
    fill = -T % tokens
    rows = jnp.pad(seen, ((0, 0), (0, fill))).reshape(-1, tokens)
    if kv_heads == 1:
        return rows.astype(jnp.float32)
    each = jnp.arange(tokens * kv_heads)[None] // kv_heads \
        == jnp.arange(tokens)[:, None]
    return jnp.dot(rows.astype(jnp.bfloat16), each.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _paged_pair_pallas(q, pool_k, pool_v, tables, lengths, layer, scale,
                       page, kv_heads, chunk_pages, interpret, seen=None):
    """`_paged_kernel` over a pair of pools (L, N, page, ...) seen as rows,
    (L, N, page * kv_heads, width), for queries (B, H, width) -> the same.
    `seen` (B, T) bool or None: the cached tokens a slot attends, of those
    up to its length (T the table's tokens, filled to whole chunks; a row
    of the kernel is a token's one KV head, so each mark is repeated)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, width = q.shape[1:]
    rows = pool_k.shape[:2] + (page * kv_heads, width)
    R = chunk_pages * page * kv_heads
    kernel = functools.partial(_paged_kernel, scale=scale, page=page,
                               kv_heads=kv_heads, chunk_pages=chunk_pages,
                               selects=seen is not None)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    marks, chunk = (), (R,)
    if seen is not None:
        chunk = (chunk_pages, page * kv_heads)
        marks = (_chunk_marks(seen, chunk_pages * page, kv_heads),)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[vmem] * (1 + len(marks)) + [hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, *chunk, width), pool_k.dtype),
                pltpu.VMEM((2, *chunk, width), pool_v.dtype),
                pltpu.VMEM((H, R), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # (Under marks it traces by a name of its own: a reader can tell
        # the attention that selects from the one that reads every row.)
        name="paged_decode_attention" if seen is None
        else "sparse_decode_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q, *marks, pool_k.reshape(rows), pool_v.reshape(rows))


def heads_chunk_pages(page: int, kv_heads: int) -> int:
    """Pages a chunk of a pool of rows held by heads holds."""
    return max(1, _CHUNK_ROWS // (page * kv_heads))


def _paged_decode_pallas(q, pool_k, pool_v, tables, lengths, layer, scale,
                         interpret=False, seen=None):
    """The kernel over pools of rows held by heads, (L, N, page, KV, D):
    a page is `page * KV` rows of D lanes."""
    page, KV = pool_k.shape[2:4]
    return _paged_pair_pallas(
        q, pool_k, pool_v, tables, lengths, layer, scale, page, KV,
        heads_chunk_pages(page, KV), interpret, seen)


def _paged_latent_pallas(q, pool, tables, lengths, layer, scale,
                         value_lanes, interpret=False):
    """`_paged_kernel` over a latent pool (L, N, page, W): one KV head, W
    lanes a row, half the rows a chunk (a row is 5 lane rows wide)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, _ = q.shape
    W = pool.shape[-1]
    page = pool.shape[2]
    chunk_pages = max(1, _CHUNK_ROWS // 2 // page)
    R = chunk_pages * page
    kernel = functools.partial(_paged_kernel, scale=scale, page=page,
                               kv_heads=1, chunk_pages=chunk_pages,
                               value_lanes=value_lanes)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[vmem, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, R, W), pool.dtype),
                pltpu.VMEM((H, R), jnp.float32),
                pltpu.SemaphoreType.DMA((1, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, value_lanes), q.dtype),
        name="paged_latent_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      _wide_queries(q, pool), pool)


def _lanes_chunk_pages(page: int, width: int) -> int:
    """Pages a chunk of a lanes pool holds.  A chunk always costs its full
    bytes (`start` fetches a short slot's last page again), so it is sized
    by the row's bytes, not its rows: half of `_CHUNK_ROWS` lane rows (the
    module docstring has the timings)."""
    return max(1, _CHUNK_ROWS * _LANES // 2 // width // page)


def _paged_lanes_pallas(q, pool_k, pool_v, tables, lengths, layer, scale,
                        interpret=False, seen=None):
    """The kernel over a pair of lanes pools (L, N, page, W = KV * D): to
    the kernel ONE KV head of W lanes, the queries widened to the row
    (`_lane_queries`) so that every head's product runs over whole lane rows
    and adds exact zeros outside its KV head's; of the (B, H, W) sums each
    head keeps its own D lanes."""
    from jax.experimental.pallas import tpu as pltpu

    page, W = pool_k.shape[2:]
    own = _own_lanes(q.shape[1], W // q.shape[2])
    # A pool small enough for VMEM (LFM2's cell: 100 MB) XLA's memory-space
    # assignment parks there between two layers' calls, a copy in and a copy
    # out of a whole pool (0.21 ms a step on the chip).  The kernel copies
    # the live pages itself: hold the pools where they lie.  (The
    # interpreter knows no memory spaces.)
    if not interpret:
        pool_k, pool_v = (pltpu.with_memory_space_constraint(pool, pltpu.HBM)
                          for pool in (pool_k, pool_v))
    o = _paged_pair_pallas(
        _lane_queries(q, own), pool_k, pool_v, tables, lengths, layer, scale,
        page, 1, _lanes_chunk_pages(page, W), interpret, seen)
    return _lane_results(o, own)


# Page tables ride in scalar memory (1 MiB on a v5e) beside the lengths.
_TABLE_BYTES = 512 << 10


def kernel_tiles(q_shape, pool_shape, tables_shape) -> bool:
    """Whether the Pallas kernel can tile these shapes: a head of whole
    128-lane rows or, narrower, a pool whose rows are lanes (`pool_row`:
    the KV heads together whole lane rows, which the kernel reads as one
    head as wide as the row), pages of whole bf16 sublane tiles, heads that
    group, page tables that fit scalar memory."""
    H, D = q_shape[-2:]
    if pool_shape[-1] == D:                     # rows held by heads
        (page, KV), whole = pool_shape[-3:-1], D % _LANES == 0
    else:                                       # rows of KV * D lanes
        page, width = pool_shape[-2:]
        KV, whole = width // D, width % _LANES == 0 and width % D == 0
    return whole and page % 16 == 0 and H % KV == 0 \
        and 4 * math.prod(tables_shape) <= _TABLE_BYTES


def latent_kernel_tiles(pool_shape, tables_shape, value_lanes: int) -> bool:
    """`kernel_tiles` for a latent pool: rows and values of whole lane
    rows, pages of whole bf16 sublane tiles, tables that fit."""
    return pool_shape[-1] % _LANES == 0 and value_lanes % _LANES == 0 \
        and pool_shape[-2] % 16 == 0 \
        and 4 * math.prod(tables_shape) <= _TABLE_BYTES


def decode_path(q_shape, pool_shape, tables_shape,
                value_lanes: int = 0) -> str:
    """Which implementation `paged_decode_attention` (with `value_lanes`,
    over a latent pool: `paged_latent_attention`) runs for these shapes in
    this process: "pallas" or "reference"."""
    on_tpu = jax.devices()[0].platform == "tpu"
    tiles = latent_kernel_tiles(pool_shape, tables_shape, value_lanes) \
        if value_lanes else kernel_tiles(q_shape, pool_shape, tables_shape)
    return "pallas" if on_tpu and tiles else "reference"


def paged_decode_attention(q, pool_k, pool_v, tables, lengths, layer=None, *,
                           scale: Optional[float] = None, seen=None):
    """Attention of one new token per slot over the pages the slot holds.

    q (B, H, D) after RoPE; pool_k / pool_v (N, page, KV, D), or the stacked
    (L, N, page, KV, D) with `layer` the (traced) index to read, so that a
    layer loop never slices the pool, either with its two minor dimensions
    as one where `pool_row` says "lanes"; tables (B, P) physical page ids;
    lengths (B,) tokens already cached: positions 0..lengths[b] are attended
    (the new token's key is at index lengths[b], written by the caller).
    Slot b reads pages tables[b, 0 .. lengths[b] // page] and no other.
    `seen` (B, P * page) bool: of those positions a slot attends the ones it
    marks (an attention that SELECTS, ops/sparse_attention.py; every live
    page is read all the same).  Returns (B, H, D) in q's dtype.

    On a TPU, for shapes `kernel_tiles` accepts, this is the Pallas kernel;
    otherwise `reference_paged_attention`."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if decode_path(q.shape, pool_k.shape, tables.shape) != "pallas":
        return reference_paged_attention(q, pool_k, pool_v, tables, lengths,
                                         layer, scale=scale, seen=seen)
    if layer is None:
        pool_k, pool_v, layer = pool_k[None], pool_v[None], 0
    kernel = _paged_lanes_pallas if pool_k.ndim == 4 else _paged_decode_pallas
    return kernel(q, pool_k, pool_v, tables, lengths, layer, scale, seen=seen)


def paged_latent_attention(q, pool, tables, lengths, layer=None, *,
                           scale: float, value_lanes: int):
    """`paged_decode_attention` over a LATENT pool, where a token's one row
    is its key for every query head and, in its first `value_lanes` lanes,
    its value: q (B, H, C) the queries as wide as the row's real values (the
    absorbed form, models/transformer.py: `latent_absorb`), pool (N, page,
    W) or the stacked (L, N, page, W) with `layer`, W the row padded to
    whole lane rows.  Returns (B, H, value_lanes) in q's dtype: every page a
    slot holds is copied once and serves all H heads twice."""
    if decode_path(q.shape, pool.shape, tables.shape,
                   value_lanes) != "pallas":
        return reference_latent_attention(
            q, pool, tables, lengths, layer, scale=scale,
            value_lanes=value_lanes)
    if layer is None:
        pool, layer = pool[None], 0
    return _paged_latent_pallas(q, pool, tables, lengths, layer, scale,
                                value_lanes)
