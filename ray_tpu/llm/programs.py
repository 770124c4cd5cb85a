"""What the serving engine (llm/engine.py) compiles: every function it
traces, and the ONE table of how a configuration caches.

The blocks live in models/transformer.py (`KINDS`) and know nothing of a
pool.  What a kind of layer leaves in the engine's page pool, how a prefill
and a decode step attend what lies there, and what the host counts for it
are the rows of `CACHES` and `COUNTED` below: `cache_of(cfg)` is looked up
once by the engine and again, at trace time, by the programs.  A new
architecture costs a block in `models/`, a row here, a configuration file
and its family; no line of the scheduler.
"""

from __future__ import annotations

import copy
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..models import mamba2, retention
from ..models.transformer import (ATTEND, LATENT_FORMS, ROW_BLOCK,
                                  TransformerConfig, blocks_to_run,
                                  decoder_block, embed_tokens, latent_absorb,
                                  latent_expand, latent_form, latent_unabsorb,
                                  lm_logits, over_rows, rope_angles,
                                  run_pattern, scan_blocks, state_axis,
                                  state_bytes)
from ..ops.paged_attention import (head_rows, heads_chunk_pages,
                                   paged_decode_attention,
                                   paged_latent_attention, pool_row, pool_rows,
                                   pool_shape)
from ..ops.sparse_attention import (gathered_attention, index_scores,
                                    masked_attention,
                                    paged_attention_over_picks,
                                    pick_positions, positions_of,
                                    select_chunk_pages, select_mask,
                                    sparse_path)


# ---- What the forms share --------------------------------------------------

def _prefill_path(cfg: TransformerConfig, rows: int, kv_sharding,
                  page: Optional[int] = None, table_len: int = 0) -> str:
    """The attention form a prefill of `rows` padded rows takes: "kernel"
    (ops/prefill_attention.py) or "xla" (`_xla_prefill_attention`).
    Decided from the platform and the shapes alone; under a `tp` mesh the
    kernel runs per shard, so a shard's heads decide.  The heads and widths
    are the entry's (`Cache.prefill_heads`), and so is whether the kernel
    can read a prefix out of its pages."""
    from ..ops.prefill_attention import prefill_path
    tp = 1
    if kv_sharding is not None and "tp" in kv_sharding.spec:
        tp = kv_sharding.mesh.shape["tp"]
    cache = cache_of(cfg)
    kv_heads, value = cache.prefill_heads(cfg)
    if cfg.num_heads % tp or kv_heads % tp \
            or (page is not None and not cache.kernel_over_pages):
        return "xla"
    return prefill_path((rows, cfg.num_heads // tp, cfg.head_dim_),
                        kv_heads // tp, cfg.dtype, value=value, page=page,
                        table_len=table_len)


def _per_shard(kernel, kv_sharding, args: str):
    """A Pallas attention kernel as it runs beside a pool placed as
    `kv_sharding`.  The kernel is a custom call the GSPMD partitioner cannot
    split, so on a mesh it runs per shard (training's flash kernel does the
    same, models/transformer.py:_flash_attention): KV heads and their query
    groups over `tp`, everything else whole on every device.  `args` names
    the kernel's positional arguments: "h" one split by heads, "p" a pool as
    it lies, "." one every device holds whole."""
    if kv_sharding is None:
        return kernel
    from jax.sharding import PartitionSpec as P
    spec = kv_sharding.spec
    by = {"h": P(None, "tp") if "tp" in spec else P(), "p": spec, ".": P()}
    return jax.shard_map(kernel, mesh=kv_sharding.mesh,
                         in_specs=tuple(by[a] for a in args),
                         out_specs=by["h"], check_vma=False)


def _xla_prefill_attention(q, k, v, mask, cfg: TransformerConfig):
    """A prefill's attention with the scores built: q (1, Sb, H, D) over
    k, v (1, T, KV, D), key t open to query s where mask[s, t]."""
    groups = cfg.num_heads // cfg.num_kv_heads
    kr = jnp.repeat(k, groups, axis=2)
    vr = jnp.repeat(v, groups, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, kr)
    if cfg.attention_scale is None:
        scores = scores / jnp.sqrt(
            jnp.asarray(cfg.head_dim_, jnp.float32)).astype(q.dtype)
    else:
        scores = scores * jnp.asarray(cfg.attention_scale, q.dtype)
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, vr)


def _suffix_mask(rows: int, T: int, prefix_len):
    """Key t (over [cached T | suffix rows]) is open to suffix query s iff
    it is a REAL cached prefix position or a suffix position <= s."""
    tpos = jnp.arange(T + rows)
    qpos = jnp.arange(rows)
    return (tpos[None, :] < prefix_len) | (
        (tpos[None, :] >= T) & (tpos[None, :] - T <= qpos[:, None]))


# ---- A pair of pools, keys and values: `D`, `*` ----------------------------

def _pair_prefill_attend(cfg: TransformerConfig, rows: int, length,
                         kv_sharding, cached=None, blocks=None,
                         row_block: int = ROW_BLOCK):
    """For `rows` padded rows of which `length` are real and, in the suffix
    form, `cached` = (pool_k, pool_v, pages, prefix_len, page) — the slot's
    page row, whose first `prefix_len` tokens precede row 0 — returns
    (attend, per_layer) for `scan_blocks`: `attend(q, k, v, *at)` gives
    (o, the layer's new cache rows (k[0], v[0])).  `blocks`, `row_block`
    (`over_rows`'s): the suffix form's built scores are row-wise in their
    QUERIES, so they are built for the query blocks that hold a real row, a
    block at a time against all keys, and o is zeros in the others."""
    pool = per_layer = ()
    if cached is None:
        path = _prefill_path(cfg, rows, kv_sharding)
    else:
        pool_k, pool_v, pages, prefix_len, page = cached
        T = pages.shape[0] * page
        path = _prefill_path(cfg, rows, kv_sharding, page, pages.shape[0])
    if path == "kernel":
        # Blocked, no S x S scores, nothing run past `length`.  The whole
        # pool goes in as it lies; the kernel copies the pages below
        # `prefix_len` of layer `li` and no other.
        from ..ops.prefill_attention import prefill_attention
        kernel = _per_shard(
            functools.partial(prefill_attention, scale=cfg.attention_scale),
            kv_sharding, "hhh.pp..." if cached else "hhh.")
        if cached:
            pool = (pool_k, pool_v, pages, prefix_len)
            per_layer = (jnp.arange(pool_k.shape[0], dtype=jnp.int32),)

        def scores(q, k, v, *li):
            return kernel(q[0], k[0], v[0], length, *pool, *li)[None]
    elif cached is None:
        def scores(q, k, v):
            mask = jnp.tril(jnp.ones((rows, rows), bool))
            return _xla_prefill_attention(q, k, v, mask, cfg)
    else:
        mask = _suffix_mask(rows, T, prefix_len)
        heads = cfg.cache_row
        if cfg.repeats > 1:
            # A scanned period's layer is a traced index: ONE gather of the
            # slot's pages out of the whole pool (a layer sliced out first
            # is a copy of it, 67 MB a pool a repeat at Granite's sizes).
            per_layer = (jnp.arange(pool_k.shape[0], dtype=jnp.int32),)

            def cached_rows(pool, li):
                return (pool_k, pool_v)[pool][jnp.full_like(pages, li), pages]
        else:
            per_layer = (pool_k, pool_v)

            def cached_rows(pool, *layer):  # pk, pv: (N, page, *row)
                return layer[pool][pages]

        def scores(q, k, v, *at):
            ck = head_rows(cached_rows(0, *at), *heads).reshape(T, *heads)
            cv = head_rows(cached_rows(1, *at), *heads).reshape(T, *heads)
            keys = jnp.concatenate([ck[None], k], axis=1)
            values = jnp.concatenate([cv[None], v], axis=1)
            return over_rows(
                lambda q, mask: (_xla_prefill_attention(
                    q, keys, values, mask, cfg),),
                [(q, 1), (mask, 0)], (q,), blocks, row_block)[0]

    def attend(q, k, v, *at):
        return scores(q, k, v, *at), (k[0], v[0])   # drop the B=1 dim
    return attend, per_layer


def _pair_decode_attend(cfg: TransformerConfig, kv_sharding, tables, lengths,
                        written):
    """A decode step's attention over the pair: attend(pools, q, k, v, li)
    writes the token's key and value where they land in layer `li` and
    reads the pages a slot holds -> (o, the pools written)."""
    paged = _per_shard(
        functools.partial(paged_decode_attention, scale=cfg.attention_scale),
        kv_sharding, "hpp...")

    def attend(pools, q, k, v, li):
        pools = written(pools[0], li, k), written(pools[1], li, v)
        return paged(q[:, 0], *pools, tables, lengths, li)[:, None], pools
    return attend


# ---- One pool of latent rows: `L` ------------------------------------------

def _latent_prefill_attend(cfg: TransformerConfig, rows: int, length,
                           kv_sharding, cached=None, blocks=None,
                           row_block: int = ROW_BLOCK):
    """`_pair_prefill_attend` for a pattern of latent layers: the key rows
    are the slot's cached rows as they lie in its pages (none: a whole
    prompt) and then the prefill's own, and `latent_form` says from the
    cached rows which of `LATENT_FORMS` attends them (over gathered rows the
    absorbed one alone).  A whole prompt is attended expanded: every head
    its own keys, nope + rope wide, over values of `value`; on path "kernel"
    it up-projects its rows once and goes through the blocked kernel, which
    runs no block past `length` or above the diagonal and builds no scores
    array.  The pool holds compressed rows and no head's keys, so over
    cached pages there is the XLA form alone; it and everything else builds
    its scores a block of query rows at a time.  attend(q, row, w, *at) ->
    (o, (the layer's new cache rows (Sb, 1, C), None: no second pool))."""
    if cached is None:
        if _prefill_path(cfg, rows, kv_sharding) == "kernel":
            from ..ops.prefill_attention import prefill_attention

            def attend(q, row, w):
                k, v = latent_expand(w, row[:, :, 0], cfg)
                return prefill_attention(q[0], k[0], v[0], length,
                                         scale=cfg.latent.scale)[None], \
                    (row[0], None)
            return attend, ()
        T, per_layer = 0, ()
        mask = jnp.tril(jnp.ones((rows, rows), bool))
    else:
        pool, _, pages, prefix_len, page = cached
        T = pages.shape[0] * page
        per_layer = (jnp.arange(pool.shape[0], dtype=jnp.int32),)
        mask = _suffix_mask(rows, T, prefix_len)
    build = LATENT_FORMS[latent_form(T)]
    heads = cfg.cache_row

    def attend(q, row, w, *li):
        keys = row[:, :, 0]
        if li:
            # ONE gather of the slot's pages out of the whole pool (a layer
            # sliced out first is a copy of it: 0.25 GB a layer).
            cached_rows = pool[jnp.full_like(pages, li[0]), pages]
            keys = jnp.concatenate(
                [head_rows(cached_rows, *heads).reshape(1, T, heads[1]),
                 keys], axis=1)
        form = build(w, keys, cfg)
        o = jax.ShapeDtypeStruct((*q.shape[:3], cfg.latent.value), q.dtype)
        ins, block = [(q, 1), (mask, 0)], lambda q, mask: (form(q, mask),)
        if not li and blocks is not None:
            # A whole prompt's block of query rows sees no key past its own
            # last row: one branch for every two blocks of keys, each built
            # over the keys up to there (half the scores of a full bucket).
            step = 2 * row_block
            upto = [functools.partial(form, upto=min(n, rows))
                    for n in range(step, rows + step, step)]
            ins.append((jnp.arange(rows), 0))
            block = lambda q, mask, at: (jax.lax.switch(
                at[-1] // step, upto, q, mask),)
        return over_rows(block, ins, (o,), blocks, row_block)[0], \
            (row[0], None)
    return attend, per_layer


def _latent_decode_attend(cfg: TransformerConfig, kv_sharding, tables,
                          lengths, written):
    """One query row a slot over rows that lie in the pool: the absorbed
    form (`latent_form(cached)`), the rows read where they lie
    (ops/paged_attention.py: `paged_latent_attention`)."""
    def attend(pools, q, row, w, li):
        pool = written(pools[0], li, row)
        o = paged_latent_attention(
            latent_absorb(w, q[:, 0], cfg), pool, tables, lengths, li,
            scale=cfg.latent.scale, value_lanes=cfg.latent.rank)
        return latent_unabsorb(w, o[:, None], cfg), (pool, None)
    return attend


# ---- Keys, values and an index key a token: `S` ----------------------------
# Three pools, the third of index keys (`IndexerDims.row`: lanes).  It rides
# with the second wherever a pair is handed on: `pool_v` is the tuple
# (values, index keys), one tree to the engine and to `_install_fn`, which
# go by leaves; only the two functions below open it.

def _sparse_prefill_attend(cfg: TransformerConfig, rows: int, length,
                           kv_sharding, cached=None, blocks=None,
                           row_block: int = ROW_BLOCK, expose: bool = False):
    """`_pair_prefill_attend` for attention with an indexer: the key rows
    are the slot's cached rows as its page row holds them (none: a whole
    prompt, `Cache.whole_program`) and then the prefill's own, the same for
    keys, values and index keys.  A block of query rows at a time
    (`over_rows`): the indexer's scores of every key row, the mask of the
    `top_k` highest among those a row may see, the dense attention under
    it (ops/sparse_attention.py, "masked").  On path "kernel" (a whole
    prompt of 1,024 padded rows or more on a TPU: `_prefill_path`) the row
    blocks leave their masks, int8, and the blocked prefill kernel attends
    under them: no float32 scores are built.  attend(q, k, v, qi, ki, wi,
    *at) -> (o, (the layer's new rows k, (v, index key)[, the mask (rows,
    keys) with `expose`: the check's])."""
    z, heads = cfg.indexer, cfg.cache_row
    T, prefix, per_layer = 0, 0, ()
    kernel = cached is None and _prefill_path(cfg, rows, kv_sharding) \
        == "kernel"
    if cached is not None:
        pool_k, (pool_v, pool_i), pages, prefix, page = cached
        T = pages.shape[0] * page
        per_layer = (jnp.arange(pool_k.shape[0], dtype=jnp.int32),)
    tpos = jnp.arange(T + rows)

    def attend(q, k, v, qi, ki, wi, *li):
        keys, values, index = k[0], v[0], ki[0, :, 0]
        if li:
            at = jnp.full_like(pages, li[0])    # ONE gather out of a pool

            def before(pool, new, row):
                return jnp.concatenate([head_rows(
                    pool[at, pages], *row).reshape(T, *row), new])
            keys = before(pool_k, keys, heads)
            values = before(pool_v, values, heads)
            index = before(pool_i, ki[0], z.row)[:, 0]

        def block(q, qi, wi, at, upto=T + rows):
            # Key t is open to row `at`: a real cached token, or one of the
            # prefill's own up to the row itself (`_suffix_mask`).  `upto`
            # (static): the first so many keys alone, for a block whose
            # rows see no later one.
            t = tpos[None, :upto]
            seen = (t < prefix) | ((t >= T) & (t - T <= at[:, None]))
            mask = select_mask(index_scores(
                qi[0], wi[0, :, 0], index[:upto]), seen, z.top_k)
            whole = ()
            if kernel or expose:    # over all the keys, as the caller has it
                whole = (jnp.pad(mask, ((0, 0), (0, T + rows - upto)))[None]
                         .astype(jnp.int8 if kernel else bool),)
            if kernel:              # the attention comes after, blocked
                return whole
            o = masked_attention(q[0], keys[:upto], values[:upto], mask,
                                 cfg.score_scale)
            return (o[None], *whole)
        half = block
        if not li and blocks is not None:
            # A whole prompt's block of query rows sees no key past its own
            # last row: one branch for every four blocks of keys, each
            # scored, cut and attended over the keys up to there (0.44 of a
            # full bucket's work at three quarters full; the first, at or
            # under `top_k` keys, selects nothing).
            step = 4 * row_block
            upto = [functools.partial(block, upto=min(n, rows))
                    for n in range(step, rows + step, step)]
            half = lambda q, qi, wi, at: jax.lax.switch(    # noqa: E731
                at[-1] // step, upto, q, qi, wi, at)
        masks = jax.ShapeDtypeStruct((1, rows, T + rows),
                                     jnp.int8 if kernel else bool)
        outs = (masks,) if kernel else (q, masks) if expose else (q,)
        got = over_rows(half, [(q, 1), (qi, 1), (wi, 1),
                               (jnp.arange(rows), 0)], outs, blocks,
                        row_block)
        o = got[0]
        if kernel:
            from ..ops.prefill_attention import prefill_attention
            o = prefill_attention(q[0], k[0], v[0], length, mask=got[0][0],
                                  scale=cfg.attention_scale)[None]
        picked = (got[-1][0] != 0,) if expose else ()
        return o, (k[0], (v[0], ki[0]), *picked)
    return attend, per_layer


def _sparse_decode_path(cfg: TransformerConfig, pool_k, slots: int,
                        table_rows: int) -> str:
    """The form `sparse_path` gives the decode step of an engine of `slots`
    slots of `table_rows` tokens over the key pool `pool_k`: the shapes
    `_sparse_decode_attend` hands it, from what the host holds
    (`Cache.decode_form`)."""
    layers, n_pages, page = pool_k.shape[:3]
    return sparse_path(
        1, (slots, cfg.num_heads, cfg.head_dim_), pool_k.shape,
        pool_shape(layers, n_pages, page, *cfg.indexer.row),
        (slots, table_rows // page))


def _sparse_decode_attend(cfg: TransformerConfig, kv_sharding, tables,
                          lengths, written):
    """One query row a slot: the token's key, value and index key written
    where they land, then (ops/sparse_attention.py: `sparse_path`, from
    the shapes) "paged": the slot's index keys scored by its live pages,
    the cut, and the paged kernel over the rows it marks, all three pools
    read where they lie; or "gathered", the plain form: the slot's index
    keys read through its page row and scored, and the `top_k` rows picked
    gathered out of the pools.  `pools` may carry a third member, (layers,
    B, K) int32: the positions every layer picked, -1 where it picked fewer
    (`Cache.picks`: the check's), from the selection the step attended
    under."""
    z, heads = cfg.indexer, cfg.cache_row

    def attend(pools, q, k, v, qi, ki, wi, li):
        pool_k, (pool_v, pool_i), *picked = pools
        pool_k, pool_v = written(pool_k, li, k), written(pool_v, li, v)
        pool_i = written(pool_i, li, ki, z.row)
        if sparse_path(1, q[:, 0].shape, pool_k.shape, pool_i.shape,
                       tables.shape) == "paged":
            o, seen = paged_attention_over_picks(
                q[:, 0], qi[:, 0], wi[:, 0, 0], pool_k, pool_v, pool_i,
                tables, lengths, li, z.top_k, cfg.score_scale)
            at, valid = positions_of(seen, z.top_k) if picked else (None,) * 2
        else:
            # (The rows as they lie, a lane row each: split into the key
            # and its zeros they are relaid, 33 MB a layer, 0.27 ms on a
            # v5e.)
            index = pool_i[jnp.full_like(tables, li), tables]
            at, valid = pick_positions(
                qi[:, 0], wi[:, 0, 0],
                index.reshape(tables.shape[0], -1, index.shape[-1]), lengths,
                z.top_k)
            o = gathered_attention(q[:, 0], pool_k, pool_v, tables, at,
                                   valid, li, cfg.score_scale, heads)
        picked = [p.at[li].set(jnp.where(valid, at, -1)) for p in picked]
        return o[:, None], (pool_k, (pool_v, pool_i), *picked)
    return attend


# ---- What the host counts for a kind of layer ------------------------------
# From shapes and lengths it already holds: nothing is read back but the
# routed counts, which ride behind a step's tokens.  A row of `COUNTED` is
# "zero": (cfg, pool=, keep=, slots=) -> the kind's `<name>_stats()` as it
# starts
# (docs/serving.md has the keys), nothing where the configuration has no
# such layer; and the events it counts, each (the counters, ...) -> what
# the event's span carries beside its own fields: "prefill" (real rows,
# the cached tokens before them or None: a prompt given no pages, the
# cached rows they see, the rows the row-wise halves ran: the bucket's, or
# its row blocks that hold a real row), "decode" (the live slots' lengths,
# what came back
# behind the step's tokens), "admit" (the checkpoint boundaries an admitted
# prompt passed, and kept).

def _routed_zero(cfg, **_):
    n, r = cfg.count("E"), cfg.routed
    return n and {"enabled": True, "held": r.held, "experts": r.experts,
                  "top_k": r.top_k, "touched": [0] * n, "rows": [0] * n,
                  "step_touched": [0] * n, "step_rows": [0] * n}


def _routed_decode(c, lengths, tail):
    step = tail.reshape(-1, 2)
    for key, column in (("touched", 0), ("rows", 1)):
        c["step_" + key] = step[:, column].tolist()
        c[key] = [a + b for a, b in zip(c[key], c["step_" + key])]
    return {"experts": sum(c["step_touched"])}


def _latent_zero(cfg, pool, **_):
    act = jnp.dtype(cfg.dtype).itemsize
    return cfg.latent and {
        "enabled": True, "layers": cfg.count("L"),
        "row_bytes": cfg.latent.row * act,
        "pool_row_bytes": pool.shape[-1] * act,
        "pool_row": pool_row(*cfg.cache_row), "rows_read": 0,
        "step_rows_read": 0, "rows_attended": 0, "rows_expanded": 0,
        "prefills": {"expanded": 0, "absorbed": 0}, "form": ""}


def _latent_prefill(c, rows, prefix_len, table, ran):
    form = latent_form(table)       # the rule the program went by
    attended = (prefix_len or 0) + rows
    expanded = attended if form == "expanded" else 0
    c["form"] = form
    c["prefills"][form] += 1
    c["rows_attended"] += attended
    c["rows_expanded"] += expanded
    return {"form": form, "expanded": expanded}


def _latent_decode(c, lengths, tail):
    rows = int((lengths + 1).sum())         # this step's token included
    c["rows_read"] += rows
    c["step_rows_read"] = rows
    return {"latent_rows": rows}


def _retention_zero(cfg, keep, **_):
    z = cfg.retention
    return z and {
        "enabled": True, "layers": cfg.count("P"),
        "row_bytes": state_bytes(cfg), "block": z.block, "D": z.expanded,
        "path": retention.step_path(z), "keep": keep, "rows_stepped": 0,
        "step_rows_stepped": 0, "prefills": {"attention": 0, "chunked": 0},
        "form": "", "boundaries_passed": 0, "boundaries_kept": 0}


def _retention_prefill(c, rows, prefix_len, table, ran):
    # The host's mirror of the rule `retention.mixer` goes by (the state's
    # part is added where the state has read anything, which on the device
    # is `any(z != 0)` and is not read back): a prefill from a checkpoint
    # starts from such a state, a whole prompt from the zero row.
    form = "chunked" if prefix_len else "attention"
    c["form"] = form
    c["prefills"][form] += 1
    return {"form": form}


def _retention_decode(c, lengths, tail):
    c["rows_stepped"] += len(lengths)
    c["step_rows_stepped"] = len(lengths)
    return {"state_rows": len(lengths)}


def _retention_admit(c, passed, kept):
    c["boundaries_passed"] += passed
    c["boundaries_kept"] += kept
    return {"kept": kept, "passed": passed}


def _mamba_zero(cfg, slots, **_):
    # `path`: the form the decode step's program is built with, which says
    # whose state it moves: the live slots' ("pallas"), or every slot's.
    return cfg.count("M") and {
        "enabled": True, "layers": cfg.count("M"),
        "row_bytes": cfg.count("M") * cfg.mamba.state_bytes(
            jnp.dtype(cfg.dtype).itemsize),
        "path": mamba2.step_path(cfg.mamba, live=True), "slots": slots,
        "rows_stepped": 0, "step_rows_stepped": 0, "rows_moved": 0,
        "prefill_rows": 0, "prefill_rows_run": 0}


def _mamba_prefill(c, rows, prefix_len, table, ran):
    # A scan runs every row it is given: the bucket's, or the row blocks'.
    c["prefill_rows"] += rows
    c["prefill_rows_run"] += ran
    return {"scan_rows": ran}


def _mamba_decode(c, lengths, tail):
    c["rows_stepped"] += len(lengths)
    c["step_rows_stepped"] = len(lengths)
    c["rows_moved"] += len(lengths) if c["path"] == "pallas" else c["slots"]
    return {"ssm_rows": len(lengths)}


def _sparse_zero(cfg, slots, table_rows, pool=None, **_):
    # `index_rows_read`: what the selection needs, every live token's index
    # key.  What the step's PROGRAM reads, by its form (`path["decode"]`):
    # `index_rows_scanned`, index rows of `index_pool_row_bytes` each, and
    # `kv_rows_read`, rows of a key and a value (`row_bytes`).  "gathered"
    # goes by the table: every slot's `table_rows` index rows, live or not
    # (`scanned_a_step`), and the rows it picked, `topk` a slot.  "paged"
    # goes by the lengths: every slot's live pages rounded up to the
    # kernels' chunks (`chunk_rows`: the index pass's, the attention's), a
    # dead slot one chunk, in both passes.
    z = cfg.indexer
    if not z:
        return z
    act = jnp.dtype(cfg.dtype).itemsize
    kvh, d = cfg.cache_row
    path = sparse_path(1) if pool is None else _sparse_decode_path(
        cfg, pool, slots, table_rows)
    page = 1 if pool is None else pool.shape[2]
    return {
        "enabled": True, "layers": cfg.count("S"), "topk": z.top_k,
        "row_bytes": 2 * kvh * d * act, "index_row_bytes": z.width * act,
        "index_pool_row_bytes": z.row[0] * z.row[1] * act,
        "scanned_a_step": slots * table_rows, "slots": slots, "page": page,
        "picked_a_step": slots * min(z.top_k, table_rows),
        "chunk_rows": [page * select_chunk_pages(page),
                       page * heads_chunk_pages(page, kvh)],
        "rows_visible": 0, "rows_selected": 0, "index_rows_read": 0,
        "index_rows_scanned": 0, "kv_rows_read": 0,
        "step_rows_visible": 0, "step_rows_selected": 0,
        "prefill_pairs_visible": 0, "prefill_pairs_selected": 0,
        "path": {"decode": path, "prefill": sparse_path(ROW_BLOCK)}}


def _sparse_prefill(c, rows, prefix_len, table, ran):
    # Row i of the prefill sees the cached tokens and its own up to itself.
    first, last = (prefix_len or 0) + 1, (prefix_len or 0) + rows
    seen = (first + last) * rows // 2
    full = max(0, last - max(first, c["topk"]) + 1)     # rows over the cut
    picked = seen - (max(first, c["topk"]) + last) * full // 2 \
        + full * c["topk"]
    c["prefill_pairs_visible"] += seen
    c["prefill_pairs_selected"] += picked
    return {"pairs_visible": seen, "pairs_selected": picked}


def _sparse_decode(c, lengths, tail):
    seen = lengths.astype(np.int64) + 1         # this step's token included
    rows, picked = int(seen.sum()), int(np.minimum(seen, c["topk"]).sum())
    c["rows_visible"] += rows
    c["rows_selected"] += picked
    c["index_rows_read"] += rows
    if c["path"]["decode"] == "paged":
        live = (lengths.astype(np.int64) // c["page"] + 1) * c["page"]
        dead = c["slots"] - len(lengths)        # a chunk each, as the live

        def covered(chunk):     # rows a pass covers: whole chunks a slot
            return int((-(-live // chunk)).sum() + dead) * chunk
        scanned, read = map(covered, c["chunk_rows"])
    else:
        scanned, read = c["scanned_a_step"], c["picked_a_step"]
    c["index_rows_scanned"] += scanned
    c["kv_rows_read"] += read
    c["step_rows_visible"], c["step_rows_selected"] = rows, picked
    return {"sparse_rows": picked}


# Whatever the configuration caches: a routed, a power retention and a
# Mamba-2 layer have counters and no pool.
COUNTED: Dict[str, Dict[str, Callable]] = {
    "sparse": {"zero": _sparse_zero, "prefill": _sparse_prefill,
               "decode": _sparse_decode},
    "routed": {"zero": _routed_zero, "decode": _routed_decode},
    "latent": {"zero": _latent_zero, "prefill": _latent_prefill,
               "decode": _latent_decode},
    "retention": {"zero": _retention_zero, "prefill": _retention_prefill,
                  "decode": _retention_decode, "admit": _retention_admit},
    "mamba": {"zero": _mamba_zero, "prefill": _mamba_prefill,
              "decode": _mamba_decode},
}


def counters(cfg: TransformerConfig, **facts) -> Dict[str, dict]:
    """The counters of the kinds `cfg` has, by `COUNTED`'s names."""
    made = {name: row["zero"](cfg, **facts) for name, row in COUNTED.items()}
    return {name: c for name, c in made.items() if c}


def count(counts: Dict[str, dict], event: str, *args) -> Dict[str, Any]:
    """One `event` counted by every kind that counts it; returns the fields
    its span carries."""
    fields = {}
    for name, c in counts.items():
        if event in COUNTED[name]:
            fields.update(COUNTED[name][event](c, *args))
    return fields


def report(counts: Dict[str, dict], name: str, steps: int) -> Dict[str, Any]:
    """`<name>_stats()` after `steps` decode steps: a copy."""
    if name not in counts:
        return {"enabled": False}
    return {**copy.deepcopy(counts[name]), "steps": steps}


# ---- The table -------------------------------------------------------------

class Cache(NamedTuple):
    """How a configuration caches, as plain functions (a row is added by
    editing `CACHES`).  `pools`: how many there are (`make_pools`): two of
    keys and values a layer and head, which is what is shipped, streamed
    and demoted; ONE whose row is both; THREE, an index key a token beside
    the pair (`beside(cfg)`: the (heads, width) of every pool after the
    second, which ride with the second as one tree); none (no layer
    attends, a pattern of recurrent layers alone: no array, and the whole
    cache is the state rows).  `prefill_heads(cfg)` -> the (KV heads, value
    width) a prefill's attention runs over, and `kernel_over_pages`: the blocked kernel can
    read a cached prefix out of these pages (`_prefill_path`);
    `whole_program`: a whole prompt is given no pages, another program than
    a suffix's; `prefill_attend(cfg, rows, length, kv_sharding, cached,
    blocks, row_block)` -> (attend, per_layer) as `_pair_prefill_attend`
    has it; `decode_attend(cfg, kv_sharding, tables, lengths, written)` ->
    attend(pools, *a layer's q and new rows, li) -> (o, the pools written);
    `value_lanes(cfg)`: `decode_path`'s, and `decode_form(cfg, pool_k,
    slots, table_rows)` the step's attention path where `decode_path`
    alone does not say it.  `picks(cfg,
    slots, table)`: for a form that SELECTS what it attends, the buffer a traced decode step
    carries with its pools and fills with what every layer picked
    (`_decode_logits_fn`'s `expose`; its prefill_attend takes `expose`
    too); None: the form reads every row it may."""
    pools: int = 0
    prefill_heads: Optional[Callable] = None
    kernel_over_pages: bool = True
    whole_program: bool = False
    prefill_attend: Optional[Callable] = None
    decode_attend: Optional[Callable] = None
    value_lanes: Callable = lambda cfg: 0
    decode_form: Optional[Callable] = None
    beside: Callable = lambda cfg: ()
    picks: Optional[Callable] = None


CACHES: Dict[str, Cache] = {
    "pair": Cache(2, lambda cfg: (cfg.num_kv_heads, cfg.head_dim_),
                  prefill_attend=_pair_prefill_attend,
                  decode_attend=_pair_decode_attend),
    "latent": Cache(1, lambda cfg: (cfg.num_heads, cfg.latent.value),
                    kernel_over_pages=False, whole_program=True,
                    prefill_attend=_latent_prefill_attend,
                    decode_attend=_latent_decode_attend,
                    value_lanes=lambda cfg: cfg.latent.rank),
    "sparse": Cache(3, lambda cfg: (cfg.num_kv_heads, cfg.head_dim_),
                    kernel_over_pages=False, whole_program=True,
                    prefill_attend=_sparse_prefill_attend,
                    decode_attend=_sparse_decode_attend,
                    decode_form=_sparse_decode_path,
                    beside=lambda cfg: (cfg.indexer.row,),
                    picks=lambda cfg, slots, table: jnp.full(
                        (cfg.count("S"), slots,
                         min(cfg.indexer.top_k, table)), -1, jnp.int32)),
    "none": Cache(),
}


def cache_of(cfg: TransformerConfig) -> Cache:
    """`CACHES`' entry for a configuration.  It has one: a pattern's
    attention layers are all `*`, all `L` or all `S`."""
    if cfg.indexer and "S" in cfg.kinds:
        return CACHES["sparse"]
    if cfg.latent:
        if pool_row(*cfg.cache_row) != "latent":
            raise ValueError(
                f"a cache row of {cfg.cache_row[1]} values is not a "
                "latent row: more than one 128-lane row and no whole "
                "number of them (ops/paged_attention.py: pool_row)")
        return CACHES["latent"]
    return CACHES["pair" if set(cfg.kinds) & set(ATTEND) else "none"]


def make_pools(cfg: TransformerConfig, n_pages: int, page: int, kv_sharding):
    """(pool_k, pool_v) of `n_pages` pages as `cache_of(cfg)` has them, None
    (an empty tree) for one it has not: rows for the layers that attend,
    all of the dense decoder's, the `*`, `L` or `S` layers of a pattern.
    Where the entry has pools `beside` the pair, pool_v is the tuple
    (values, *those), each with its own row."""
    layers = sum(cfg.count(k) for k in ATTEND)
    cache = cache_of(cfg)
    rows = [cfg.cache_row] * min(cache.pools, 2) + list(cache.beside(cfg))
    pools = [jnp.zeros(pool_shape(layers, n_pages, page, *row), cfg.dtype,
                       device=kv_sharding) for row in rows]
    pools += [None] * (2 - len(pools))
    return pools[0], (pools[1] if len(pools) == 2 else tuple(pools[1:]))


# ---- Prefill ---------------------------------------------------------------

def _prefill_fn(params, tokens, length, cfg: TransformerConfig,
                kv_sharding=None, row_block: int = ROW_BLOCK, cached=None):
    """The dense decoder's prefill: tokens (1, Sb) padded → (last_logits
    (V,), k, v (L, Sb, KV, D)).  A whole prompt, or (`cached` = (pool_k,
    pool_v, pages, prefix_len, page)) the suffix half of a prefix-cache
    hit: ONLY tokens[prefix_len:] run, attending the cached KV of
    tokens[:prefix_len] already resident in the pool's shared pages.
    pages: (P,) a full page-table row — shared prefix pages first, then the
    freshly reserved pages whose contents are garbage (masked, like
    decode's scratch reads; prefix_len is page-aligned by construction).
    Both return the same, so the install path is shared.

    Cache rows at positions ≥ length are padding's, or zeros where the
    bucket is run by row blocks (`decoder_block`: those past the last block
    that holds a real row); decode masks them out via per-slot lengths, and
    the last-real-token logits only attend backwards (causal), so padding
    never leaks into results.  `row_block`: the tests'."""
    S = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg)
    # RoPE at absolute positions: a suffix's row i is prefix_len + i.
    at = jnp.arange(0, S, dtype=jnp.float32) if cached is None \
        else cached[3] + jnp.arange(S, dtype=jnp.int32)
    cos, sin = rope_angles(at, cfg)
    attend, per_layer = cache_of(cfg).prefill_attend(
        cfg, S, length, kv_sharding, cached)
    x, (ks, vs) = scan_blocks(params["layers"], x, cos, sin, attend, cfg,
                              per_layer, length, row_block)
    return lm_logits(params, x[0, length - 1], cfg), ks, vs


def _state_prefill_fn(params, pool_k, pool_v, pages, tokens, prefix_len,
                      length, ckpt, row, cfg: TransformerConfig, page: int,
                      every: int, row_block: int = ROW_BLOCK, keep: int = 0,
                      expose: bool = False):
    """A prefill of a pattern with recurrent layers: ONE form for a whole
    prompt and for a suffix, since both run the recurrence from a given
    state.  The rows `tokens` (1, Sb), of which `length` are real, follow
    `prefix_len` tokens whose keys and values lie in `pages` (as
    `_prefill_fn`'s `cached` has it) and whose recurrent state is row `row`
    of the checkpoint pool `ckpt` (row 0: the state of having read nothing,
    with prefix_len 0).  Returns (last-token logits, the attention layers'
    ks, vs (nA, Sb, KV, D), the state after `length` rows, the state after
    every `every` rows (the stateful mixers' `every`), the experts every row
    chose (nE, Sb, K)).  Where the bucket is run by row blocks (`run_pattern`
    says when) what lies past the last block that holds a real row is
    zeros, as `_prefill_fn` has it: ks, vs, the checkpoints at boundaries
    past the prompt (`_install_state` gives those to the scratch row), the
    experts chosen.  `row_block`: the tests'.  `pages` None: a whole
    prompt that attends nothing cached (`Cache.whole_program`, whose
    attention form follows from that), or a pattern no layer of which
    attends (no pool: ks and vs are None, and what precedes the rows is in
    the state alone).  `keep`: `run_pattern`'s.  `expose` (a reference
    check's trace): where the attention SELECTS what it reads
    (`Cache.picks`), one result more, what every layer picked for every row
    (nA, Sb, keys) bool."""
    Sb = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg)
    cos, sin = rope_angles(prefix_len + jnp.arange(Sb, dtype=jnp.int32), cfg)
    cached = None if pages is None else (pool_k, pool_v, pages, prefix_len,
                                         page)
    attend, per_layer = None, ()
    cache = cache_of(cfg)
    if cache.prefill_attend is not None:
        attend, per_layer = cache.prefill_attend(
            cfg, Sb, length, None, cached,
            blocks_to_run(length, Sb, row_block, every), row_block,
            **({"expose": True} if expose and cache.picks else {}))
    at = _on_axis(state_axis(cfg))
    rec = [{k: c[k][at(row)][at(None)] for k in c} for c in ckpt]
    x, kv, rec, kept, _, chosen = run_pattern(
        params["layers"], x, cos, sin, attend, cfg, rec, per_layer,
        length=length, every=every, row_block=row_block, keep=keep)
    ks, vs, *picked = kv or (None, None)
    return (lm_logits(params, x[0, length - 1], cfg), ks, vs, rec, kept,
            chosen, *picked)


def _on_axis(axis: int):
    """at(*index) -> the index of a state tree's leaf whose sequences (slots,
    checkpoint rows, a prefill's one) lie on `axis` (`state_axis`), every
    axis before it taken whole."""
    return lambda *index: (*(slice(None),) * axis, *index)


def _install_state_fn(rec, ckpt, slot, end, kept, rows, axis: int = 0):
    """Write a prefill's recurrent state into slot `slot` of the resident
    per-slot state `rec`, and the checkpoints it passed into rows `rows`
    (n,) of the pool `ckpt`; a checkpoint nobody keeps goes to row 1, the
    scratch row.  `axis` (static): `state_axis`, the leaves' axis of slots
    and rows; a scanned period's repeats lie before it and are written
    together."""
    at = _on_axis(axis)
    rec = [{k: r[k].at[at(slot)].set(e[k][at(0)]) for k in r}
           for r, e in zip(rec, end)]
    ckpt = [{k: c[k].at[at(rows)].set(kp[k][at(0)]) for k in c}
            for c, kp in zip(ckpt, kept)]
    return rec, ckpt


def _install_fn(pool_k, pool_v, ks, vs, pages, page: int, kv_sharding):
    """Write a prefill's (L, Sb, KV, D) kv into the slot's reserved pages,
    whole pages of rows as the pool holds them (`pool_rows`).

    pages: (P,) int32 physical page ids.  Entries past the slot's reserved
    count are 0 — the shared scratch page, whose contents are garbage by
    contract: every read of it is masked (valid = t <= length always stays
    within the reserved pages) and the allocator never hands page 0 out."""
    L, Sb = ks.shape[:2]
    P = pages.shape[0]
    pad = P * page - Sb
    # (Each step over the pools' leaves, each with its own row: a latent
    # pattern's second is None, an empty tree; one with an index key beside
    # the pair holds (values, index keys) there.)
    pools, new = (pool_k, pool_v), (ks, vs)
    if pad > 0:
        new = jax.tree.map(
            lambda r: jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0))), new)
    new = jax.tree.map(
        lambda r: pool_rows(r.reshape(L, P, page, *r.shape[2:]),
                            *r.shape[2:]), new)
    pools = jax.tree.map(lambda pool, r: pool.at[:, pages].set(r), pools, new)
    if kv_sharding is not None:
        pools = jax.lax.with_sharding_constraint(pools, kv_sharding)
    return pools


# ---- The decode step -------------------------------------------------------

def _decode_logits_fn(params, pool_k, pool_v, tables, last_tokens, lengths,
                      active, cfg: TransformerConfig, page: int, kv_sharding,
                      rec=(), expose: bool = False):
    """The model half of a decode step: every slot's last token through the
    layers against the paged pool -> (pool_k', pool_v', logits (B, V) f32),
    and for a pattern three more: the recurrent layers' per-slot state `rec`
    advanced for the active slots, the routed layers' counts (n, 2) and
    their chosen experts (n, B, 1, K); with `expose` (a reference check's
    trace) and an attention that SELECTS what it reads, one more: the
    positions every layer picked for every slot (`Cache.picks`).

    The pool is carried through the layer loop whole and written where the
    new token lands; attention (`Cache.decode_attend`) reads the pages a
    slot holds.  Nothing in the step is sized by the pool or by
    max_batch x max_len but the donated pool itself."""
    # An inactive slot is one token on the scratch page: it costs one page
    # and what it computes is dropped.
    tables = jnp.where(active[:, None], tables, 0)
    lengths = jnp.where(active, lengths, 0)
    x = embed_tokens(params, last_tokens, cfg)[:, None]           # (B,1,E)
    # Per-slot RoPE at each slot's own position.
    cos, sin = rope_angles(lengths, cfg)                          # (B, D/2)
    cos, sin = cos[:, None], sin[:, None]                         # (B,1,D/2)
    # Physical write position of the incoming token for every slot.
    write_page = jnp.take_along_axis(
        tables, (lengths // page)[:, None], axis=1)[:, 0]         # (B,)
    write_off = lengths % page
    heads = cfg.cache_row

    def written(pool, li, new, row=heads):  # new (B, 1, *row): one a slot
        return pool.at[li, write_page, write_off].set(
            pool_rows(new[:, 0], *row))

    cache = cache_of(cfg)
    decode_attend = cache.decode_attend
    # (No pool: no layer attends, and `attend` is never called.)
    over = decode_attend and decode_attend(cfg, kv_sharding, tables, lengths,
                                           written)
    if cfg.pattern:
        # The pools are carried from one attention layer to the next and
        # written layer by layer, in place.
        layer = () if pool_k is None else (
            jnp.arange(pool_k.shape[0], dtype=jnp.int32),)
        picks = (cache.picks(cfg, *tables.shape[:1],
                             tables.shape[1] * page),) \
            if expose and cache.picks else ()
        x, (pool_k, pool_v, *picks), rec, _, counts, chosen = run_pattern(
            params["layers"], x, cos, sin, over, cfg, rec, layer,
            live=active, pools=(pool_k, pool_v, *picks))
        return (pool_k, pool_v, lm_logits(params, x[:, 0], cfg), rec, counts,
                chosen, *picks)

    def body(carry, layer):
        x, *pools = carry               # the whole pool, in place
        lp, li = layer

        def attend(*at):
            return over(pools, *at, li)
        x, pools = decoder_block(lp, x, cos, sin, attend, cfg)
        return (x, *pools), None

    (x, pool_k, pool_v), _ = jax.lax.scan(
        body, (x, pool_k, pool_v),
        (params["layers"], jnp.arange(pool_k.shape[0], dtype=jnp.int32)))
    if kv_sharding is not None:
        pool_k = jax.lax.with_sharding_constraint(pool_k, kv_sharding)
        pool_v = jax.lax.with_sharding_constraint(pool_v, kv_sharding)
    return pool_k, pool_v, lm_logits(params, x[:, 0], cfg)


def _sample_fn(logits, active, temps, key):
    """Every slot's next token from its logits (B, V): greedy where its
    temperature is 0, else drawn with its own split of `key`; 0 for an
    inactive slot."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    keys = jax.random.split(key, logits.shape[0])
    sampled = jax.vmap(
        lambda key, lg, t: jax.random.categorical(
            key, lg / jnp.maximum(t, 1e-6)))(keys, logits, temps)
    nxt = jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)
    return jnp.where(active, nxt, 0)


# The decode step's resident state is `slots`, one int32 row a slot, and the
# sampling key.  A row is the slot's page-table row (P physical page ids)
# and then these columns (the temperature as its float32 bits); the packed
# update the host sends has one column more, `take`: the device is to
# accept the row.
_COL_LAST, _COL_LENGTH, _COL_ACTIVE, _COL_TEMP = range(4)
_COLS = 4


def _pack_rows(tables, last, lengths, active, temps, take) -> np.ndarray:
    """Host side: every slot's row as the host's mirrors have it, (B, P + 5)
    int32, with `take` marking the slots the device is to accept."""
    P = tables.shape[1]
    rows = np.empty((tables.shape[0], P + _COLS + 1), np.int32)
    rows[:, :P] = tables
    rows[:, P + _COL_LAST] = last
    rows[:, P + _COL_LENGTH] = lengths
    rows[:, P + _COL_ACTIVE] = active
    rows[:, P + _COL_TEMP] = np.asarray(temps, np.float32).view(np.int32)
    rows[:, -1] = take
    return rows


def _accept_rows(slots, update):
    """Device side: the rows a packed update marks replace the state's; an
    update that marks none leaves it as it is."""
    return jnp.where(update[:, -1:] != 0, update[:, :-1], slots)


def _decode_fn(params, pool_k, pool_v, state, update, cfg: TransformerConfig,
               page: int, kv_sharding):
    """One decode step for ALL slots against the paged pool, on state that
    stays on the device.

    pool_k/pool_v (L, N, page, *row: `pool_shape`).  `state` = {"slots":
    (B, P + 4) int32, "rng": the sampling key} is RESIDENT: the step takes
    it, advances it and returns it, donated like the two pools, so between
    two steps the
    host uploads nothing and runs no program.  A slot's row holds its page
    table (page 0 = scratch for inactive slots), its last token, the tokens
    it has in cache (the new token is written at that index), whether it is
    active, and its temperature (0 = greedy).  The step first accepts
    `update` (`_pack_rows`), the one packed upload through which the host
    writes the slots IT changed (a reservation, an admission, a
    retirement); then it splits the key as the host would (`rng, key =
    split(rng)`: the same two keys), samples, and advances what it owns:
    last token <- next token and length + 1 for the active slots.  On a
    mesh the state is replicated.
    A pattern with recurrent layers keeps their state there too, under
    "rec": one tree for each stateful layer, a row a slot, advanced
    by the step for the active slots; the host writes a slot's row when it
    installs a prefill (`_install_state_fn`) and at no other time.
    Returns (pool_k', pool_v', state', out): `out` the next tokens (B,),
    and after them a pattern's routed counts, flattened (held experts
    touched and rows computed, for each `E` layer): one read-back."""
    slots = _accept_rows(state["slots"], update)
    P = slots.shape[1] - _COLS
    tables, last, lengths = (slots[:, :P], slots[:, P + _COL_LAST],
                             slots[:, P + _COL_LENGTH])
    active = slots[:, P + _COL_ACTIVE] != 0
    temps = jax.lax.bitcast_convert_type(slots[:, P + _COL_TEMP], jnp.float32)
    rng, key = jax.random.split(state["rng"])
    pool_k, pool_v, logits, *pattern = _decode_logits_fn(
        params, pool_k, pool_v, tables, last, lengths, active, cfg, page,
        kv_sharding, state.get("rec", ()))
    nxt = _sample_fn(logits, active, temps, key)
    slots = slots.at[:, P + _COL_LAST].set(jnp.where(active, nxt, last))
    slots = slots.at[:, P + _COL_LENGTH].add(active)
    state = {"slots": slots, "rng": rng}
    if pattern:
        state["rec"], counts, _ = pattern
        if counts is not None:
            nxt = jnp.concatenate([nxt, counts.reshape(-1)])
    if kv_sharding is not None:
        state = jax.lax.with_sharding_constraint(
            state, NamedSharding(kv_sharding.mesh, PartitionSpec()))
    return pool_k, pool_v, state, nxt
