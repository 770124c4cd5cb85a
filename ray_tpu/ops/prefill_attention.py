"""Prefill attention: one prompt's queries against its own keys, causally,
and (on a prefix-cache hit) against the prefix its slot already holds in the
paged pool — blocked, with an online softmax, so that no S x S score matrix
is ever made.

The serving engine's prefill bodies call it (llm/programs.py:_prefill_fn,
whole or over cached pages, and a latent pattern's whole prompt in
_latent_prefill_attend).  It is forward only and emits no residuals;
training's differentiable kernel is ops/flash_attention.py and shares
nothing with it.

Keys (and queries) are `Dk` wide and values `Dv`, both read from the shapes:
one width in a dense decoder, 128 + 64 = 192 over 128 in a latent layer's
expanded form.  A `Dk` that is not whole 128-lane rows is filled with zero
columns up to the next one before the call (exact; the MXU contracts 128
deep, so 192 costs two passes either way, and Mosaic takes no 64-lane slice
of a separate rotated part: PERF.md, PR 47).

Keys are `[prefix_len tokens in pages | Sb new tokens]`.  The grid is one
cell per (KV head, query block); a cell loads its `H // KV` query heads once
and walks the key blocks it can see, each K/V block copied to VMEM once for
all of them (GQA by grouping: no widened keys).  What it can see:

  - the prefix: chunks of `block` tokens below `prefix_len`, read from the
    pool through the slot's page row (a page past the last live one is
    never fetched, so what lies there cannot reach the output);
  - the new keys: blocks 0..qi, the last one masked along its diagonal.
    Blocks above the diagonal are not run, and neither is a query block that
    starts at or past `length`: it writes zeros (those rows are garbage by
    the prefill's contract).  Keys past `length` need no mask of their own:
    only query rows past `length` can see them.

A page of one layer is `(page, KV, D)`; as the pool lies in HBM it can be
seen as `(page * KV, D)` rows with no copy (ops/paged_attention.py), row
`t * KV + h`.  Whole pages are copied to VMEM and the rows of this cell's KV
head are read out of the buffer with a sublane stride (bf16: through a
32-bit view of the row pairs), so the MXU only sees keys of its own head.

`prefill_attention` runs the kernel for shapes `kernel_tiles` accepts when
`prefill_path` says so (a TPU, at or over `MIN_ROWS` padded rows, or
`MIN_ROWS_PAGED` with a prefix); callers keep their XLA expression for
everything else.  `kv_blocks` is the host-side
count of key blocks a call runs beside what S x S would have run.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Query rows and key rows of one block (a power of two: prefill buckets are;
# a multiple of 128: a block's keys are the lanes of its scores).
_BLOCK = 512
# The smallest padded lengths the kernel takes, as measured on a v5e at the
# serving cells' widths (PERF.md, PR 32).  A whole prompt under 1,024 rows
# builds at most 16 MB of scores a layer and the XLA expression is 0.4 ms a
# prefill faster there; the suffix form wins from the smallest block, since
# what it replaces gathers the slot's whole page row whatever the prefix is.
MIN_ROWS = 1024
MIN_ROWS_PAGED = 128
# Page rows ride in scalar memory beside the three scalars.
_TABLE_BYTES = 512 << 10
_VMEM_LIMIT = 48 << 20


def _block(rows: int) -> int:
    return min(rows, _BLOCK)


def kernel_tiles(q_shape, kv_heads: int, dtype, *, value: Optional[int] = None,
                 page: Optional[int] = None, table_len: int = 0) -> bool:
    """Whether the kernel can tile a prefill of q `(Sb, H, D)` over
    `kv_heads` whose values are `value` wide (None: D, as the keys): values
    of whole 128-lane rows, heads that group, whole blocks; the keys' width
    is the wrapper's to fill up to whole lane rows.  With a prefix in pages
    (`page` given) keys and values lie in pools of ONE row width, and also:
    pages of whole sublane tiles that divide a block, a KV-head stride the
    row read can take, and a page row that fits scalar memory."""
    Sb, H, D = q_shape
    value = D if value is None else value
    bits = jnp.dtype(dtype).itemsize * 8
    ok = value % 128 == 0 and H % kv_heads == 0 and Sb % _block(Sb) == 0 \
        and _block(Sb) % 128 == 0 and bits in (16, 32)
    if ok and page is not None:
        ok = D == value and (page * kv_heads) % (256 // bits) == 0 \
            and _block(Sb) % page == 0 \
            and (bits == 32 or kv_heads == 1 or kv_heads % 2 == 0) \
            and 4 * table_len <= _TABLE_BYTES
    return ok


def prefill_path(q_shape, kv_heads: int, dtype, *, value: Optional[int] = None,
                 page: Optional[int] = None, table_len: int = 0) -> str:
    """Which form a prefill of these shapes takes in this process: "kernel"
    (on a TPU, `MIN_ROWS` padded rows or more, `MIN_ROWS_PAGED` with a
    prefix in pages, shapes that tile) or "xla" (the caller's own
    expression)."""
    on_tpu = jax.devices()[0].platform == "tpu"
    least = MIN_ROWS if page is None else MIN_ROWS_PAGED
    return "kernel" if on_tpu and q_shape[0] >= least and kernel_tiles(
        q_shape, kv_heads, dtype, value=value, page=page,
        table_len=table_len) else "xla"


def kv_blocks(length: int, padded: int, prefix_len: int = 0,
              table_tokens: int = 0) -> Tuple[int, int]:
    """(key blocks the kernel runs, key blocks the dense S x S form covers)
    for one prefill of `length` real rows padded to `padded`, after
    `prefix_len` cached tokens of a `table_tokens`-token page row; counted
    per query block, the same for every head and layer."""
    b = _block(padded)
    live_q = -(-length // b)
    run = live_q * (live_q + 1) // 2 + live_q * -(-prefix_len // b)
    dense = (padded // b) * -(-(table_tokens + padded) // b)
    return run, dense


def _kernel(*refs, scale: float, block: int, groups: int, paged: bool,
            page: int, kv_heads: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if paged:
        (meta, pages, q_ref, k_hbm, v_hbm, pk_hbm, pv_hbm, o_ref, q_scr,
         kbuf, vbuf, m_scr, l_scr, acc_scr, sem, kpg, vpg, psem) = refs
    else:
        (meta, q_ref, k_hbm, v_hbm, o_ref, q_scr, kbuf, vbuf, m_scr, l_scr,
         acc_scr, sem) = refs
    h, qi = pl.program_id(0), pl.program_id(1)
    length = meta[0]
    Dk, Dv = q_scr.shape[-1], acc_scr.shape[-1]

    @pl.when(qi * block >= length)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    def new_copies(ki, buf):
        rows = pl.ds(pl.multiple_of(ki * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[h, rows], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[h, rows], vbuf.at[buf],
                                      sem.at[1, buf]))

    def attend(k, v, visible=None):
        """One key block against every query head of the group; `visible`
        (broadcasts to (block, block)) is the last key column a row sees."""
        def head(g, _):
            s = jax.lax.dot_general(
                q_scr[g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if visible is not None:
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(col <= visible, s, -1e30)
            # The statistics are kept across all 128 lanes (every lane of
            # a row the same), so no update works on one-lane columns.
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - pltpu.repeat(m_new, s.shape[1] // 128, axis=1))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = pltpu.repeat(alpha, Dv // 128, axis=1) * acc_scr[g] \
                + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_scr[g] = m_new
        jax.lax.fori_loop(0, groups, head, None)

    @pl.when(qi * block < length)
    def _live():
        for cp in new_copies(0, 0):
            cp.start()
        for g in range(groups):
            # The softmax scale goes into q once, not into every score.
            q_scr[g] = (q_ref[:, g * Dk:(g + 1) * Dk].astype(jnp.float32)
                        * scale).astype(q_scr.dtype)
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        if paged:
            prefix_len, li = meta[1], meta[2]
            n_pre = (prefix_len + block - 1) // block
            chunk_pages = block // page
            rows_page = page * kv_heads
            last_page = (prefix_len + page - 1) // page - 1

            def page_copies(pid, j, buf):
                dst = pl.ds(pl.multiple_of(j * rows_page, rows_page),
                            rows_page)
                return (pltpu.make_async_copy(
                            pk_hbm.at[li, pid], kpg.at[buf, dst],
                            psem.at[0, buf]),
                        pltpu.make_async_copy(
                            pv_hbm.at[li, pid], vpg.at[buf, dst],
                            psem.at[1, buf]))

            def start_chunk(c, buf):
                """Past the prefix's last page that page is fetched again:
                the buffer holds live pages only, and the repeated rows lie
                past `prefix_len`, where the mask drops them."""
                def one(j, _):
                    pid = pages[jnp.minimum(c * chunk_pages + j, last_page)]
                    for cp in page_copies(pid, j, buf):
                        cp.start()
                jax.lax.fori_loop(0, chunk_pages, one, None)

            def wait_chunk(buf):
                def one(j, _):
                    for cp in page_copies(0, j, buf):  # a wait reads no source
                        cp.wait()
                jax.lax.fori_loop(0, chunk_pages, one, None)

            def head_rows(ref, buf):
                """Rows `t * KV + h` of a chunk: this cell's KV head."""
                if kv_heads == 1:
                    return ref[buf]
                if ref.dtype.itemsize == 4:
                    return ref[buf, pl.ds(h, block, stride=kv_heads), :]
                # Two bf16 rows share a 32-bit sublane: rows 2r (low half)
                # and 2r + 1 (high half).  Read the pairs that hold head h
                # and widen the half that is it (exact: bf16 is the upper
                # half of a float32).
                pair = ref.bitcast(jnp.uint32)[
                    buf, pl.ds(h // 2, block, stride=kv_heads // 2), :]
                bits = jnp.where(h % 2 == 0, pair << 16,
                                 pair & jnp.uint32(0xFFFF0000))
                return jax.lax.bitcast_convert_type(
                    bits, jnp.float32).astype(ref.dtype)

            @pl.when(n_pre > 0)
            def _first():
                start_chunk(0, 0)

            def chunk_body(c, _):
                buf = c % 2

                @pl.when(c + 1 < n_pre)
                def _prefetch():
                    start_chunk(c + 1, 1 - buf)

                wait_chunk(buf)
                k, v = head_rows(kpg, buf), head_rows(vpg, buf)

                @pl.when(c + 1 < n_pre)
                def _whole():
                    attend(k, v)

                @pl.when(c + 1 == n_pre)
                def _last():
                    attend(k, v, prefix_len - 1 - c * block)

            jax.lax.fori_loop(0, n_pre, chunk_body, None)

        def new_body(ki, _):
            buf = ki % 2

            @pl.when(ki < qi)
            def _prefetch():
                for cp in new_copies(ki + 1, 1 - buf):
                    cp.start()

            for cp in new_copies(0, buf):
                cp.wait()
            k, v = kbuf[buf], vbuf[buf]

            @pl.when(ki < qi)
            def _below():
                attend(k, v)

            @pl.when(ki == qi)
            def _diagonal():
                attend(k, v, jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0))

        jax.lax.fori_loop(0, qi + 1, new_body, None)
        for g in range(groups):
            o_ref[:, g * Dv:(g + 1) * Dv] = (
                acc_scr[g] / pltpu.repeat(l_scr[g], Dv // 128, axis=1)
            ).astype(o_ref.dtype)


def _prefill_attention_pallas(q, k, v, length, pool_k=None, pool_v=None,
                              pages=None, prefix_len=0, layer=0, *,
                              scale: float, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Sb, H, Dk = q.shape
    KV, Dv = v.shape[1:]
    # Keys (and queries) of a width that is not whole lane rows take zero
    # columns up to the next one: a zero column adds nothing to a score.
    fill = -Dk % 128
    if fill:
        q, k = (jnp.pad(a, ((0, 0), (0, 0), (0, fill))) for a in (q, k))
        Dk += fill
    groups = H // KV
    block = _block(Sb)
    paged = pool_k is not None
    meta = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                      for x in (length, prefix_len, layer)])

    def q_map(h, qi, meta, *_):
        # A query block past `length` is not computed: point it at the last
        # live one, which is already there.
        return jnp.minimum(qi, jnp.maximum(meta[0] - 1, 0) // block), h

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((block, groups * Dk), q_map), hbm, hbm]
    scratch = [
        pltpu.VMEM((groups, block, Dk), q.dtype),
        pltpu.VMEM((2, block, Dk), k.dtype),
        pltpu.VMEM((2, block, Dv), v.dtype),
        pltpu.VMEM((groups, block, 128), jnp.float32),     # running max
        pltpu.VMEM((groups, block, 128), jnp.float32),     # running denom
        pltpu.VMEM((groups, block, Dv), jnp.float32),      # accumulator
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    args = [meta, q.reshape(Sb, H * Dk), k.transpose(1, 0, 2),
            v.transpose(1, 0, 2)]
    page = 0
    if paged:
        L, N, page = pool_k.shape[:3]
        in_specs += [hbm, hbm]
        scratch += [pltpu.VMEM((2, block * KV, Dk), pool_k.dtype),
                    pltpu.VMEM((2, block * KV, Dv), pool_v.dtype),
                    pltpu.SemaphoreType.DMA((2, 2))]
        args.insert(1, pages.astype(jnp.int32))
        args += [pool_k.reshape(L, N, page * KV, Dk),
                 pool_v.reshape(L, N, page * KV, Dv)]
    kernel = functools.partial(_kernel, scale=scale, block=block,
                               groups=groups, paged=paged, page=page,
                               kv_heads=KV)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 if paged else 1,
            grid=(KV, Sb // block),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block, groups * Dv),
                                   lambda h, qi, *_: (qi, h)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((Sb, H * Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="prefill_attention",
        interpret=interpret,
    )(*args)
    return out.reshape(Sb, H, Dv)


def prefill_attention(q, k, v, length, pool_k=None, pool_v=None, pages=None,
                      prefix_len=0, layer=0, *, scale: Optional[float] = None):
    """Causal attention of one prompt's new rows, through the kernel.

    q (Sb, H, Dk), k (Sb, KV, Dk) and v (Sb, KV, Dv) after RoPE, padded:
    rows at or past `length` (traced scalar) come back as anything finite.
    The two widths are read from the shapes (a latent layer's expanded form
    has keys of 192 over values of 128; a dense decoder's are one width).
    With `pool_k`, `pool_v` (L, N, page, KV, D), `pages` (P,) the slot's
    page row, `prefix_len` and `layer` (traced scalars), row i also attends
    to the `prefix_len` tokens the pages hold, which precede row 0; only
    pages below `prefix_len` are read.  Returns (Sb, H, Dv) in q's dtype.

    The caller decides with `prefill_path` whether to call this at all."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _prefill_attention_pallas(q, k, v, length, pool_k, pool_v, pages,
                                     prefix_len, layer, scale=scale)
