"""Continuous-batching LLM generation engine, TPU-first.

Reference surface: python/ray/llm/_internal — the reference wraps vLLM
(engines/vllm/) for batch inference and serving.  On TPU we own the whole
stack, so the engine is native JAX on the in-tree flagship transformer
(models/transformer.py) and is built around XLA's compilation model:

  - ONE compiled decode step for the whole slot batch: static shapes,
    per-slot lengths/active masks as data, so admission/retirement of
    requests never recompiles.
  - PAGED KV cache (vLLM's PagedAttention storage model, re-done for XLA):
    a fixed pool of (page_size)-token blocks shared by all slots, indexed
    through a per-slot page table.  A request only reserves the pages its
    prompt + max_tokens need, so many short requests fit a pool that a
    dense (max_batch, max_len) cache could not.  Pages are reserved at
    admission (no mid-flight exhaustion, no preemption machinery).
  - Prefill is compiled per prompt-length *bucket* (pow-2 padding) —
    a handful of compilations total, amortized across all requests.  The
    attention form is the bucket's: on a TPU, with 128-wide heads, a
    whole-prompt bucket of 1,024 rows or more and a suffix bucket (a
    prefix-cache hit, a chunk) of 128 or more run the blocked kernel
    (ops/prefill_attention.py: no S x S scores, nothing run past the
    prompt's real length, the prefix read from its pages); smaller
    buckets, other head widths and the CPU build the scores in XLA.
    `prefill_stats()` says which form the prefills took.
  - KV pool lives on device between steps (no host round-trips in the
    decode loop); only sampled token ids come back per step.  So does the
    step's own state (page tables, last tokens, lengths, active mask,
    temperatures, sampling key): the step advances it, and the host
    writes to it only the slots it changed (`_decode_fn`).
  - Tensor parallelism via GSPMD: pass ``mesh=`` and the engine shards
    weights (heads/kv_heads/mlp over tp, Megatron layout) and the KV pool
    (kv_heads over tp) with NamedShardings; XLA inserts the collectives in
    prefill and the decode step.  The vocab axis stays replicated so the
    embedding row-gather never forces a resharding round-trip.  Same
    tokens come out sharded or not (tests/test_llm.py).

vLLM-parity naming: SamplingParams / add_request / step mirror
vllm's engine surface so reference users can map concepts 1:1.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import os
import tempfile
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .._private import flight_recorder
from ..exceptions import KVGatherError
from ..models import retention
from ..models.transformer import (ATTEND, LATENT_FORMS, ROW_BLOCK, STATEFUL,
                                  TransformerConfig, blocks_to_run,
                                  decoder_block, embed_tokens, init_params,
                                  latent_absorb, latent_expand, latent_form,
                                  latent_unabsorb, lm_logits, over_rows,
                                  param_logical_axes, rope_angles, row_blocks,
                                  run_pattern, scan_blocks, state_bytes,
                                  state_chunk, zero_state)
from ..ops.paged_attention import (decode_path, head_rows,
                                   paged_decode_attention,
                                   paged_latent_attention, pool_row, pool_rows,
                                   pool_shape)
from .tick_phases import TickPhases


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 32
    temperature: float = 0.0          # 0 = greedy
    eos_id: Optional[int] = None


@dataclasses.dataclass
class _Request:
    req_id: int
    prompt: List[int]
    params: SamplingParams
    out: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    pages: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    # Why generation ended: "stop" (eos), "length" (max_tokens/max_len),
    # "cancelled" (client disconnect / deadline expiry) — OpenAI naming.
    finish_reason: str = ""
    # Prefix-cache bookkeeping: pages borrowed from the cache (ref-held,
    # never written by this request) and how many prompt tokens they cover.
    shared_pages: List[int] = dataclasses.field(default_factory=list)
    prefix_len: int = 0
    no_cache: bool = False
    # A model with recurrent layers: the checkpoint row its prefill starts
    # from (0: from nothing), the tokens of its hit that lie past that
    # checkpoint and run again, and the rows reserved for the checkpoints it
    # will pass, by boundary (tokens).
    from_row: int = 0
    recomputed: int = 0
    new_rows: Dict[int, int] = dataclasses.field(default_factory=dict)
    # P/D external admission: a shipped KV blob installed at admission
    # instead of running prefill (add_external_request).
    kv_blob: Optional[dict] = None
    first_token: int = -1
    # Chunked in-pool prefill: tokens already prefilled into the slot's
    # pages (advances per tick so one huge prompt can't starve a tick).
    prefilled: int = 0
    # Paged cross-host KV (add_paged_request): the prompt's KV lives in
    # external parts — local dicts or remote-arena refs — and only the
    # decode tail occupies pool pages.  ext_written counts decode-tail
    # tokens whose KV has been appended (the next write position is
    # ext_len + ext_written).
    kv_paged: bool = False
    ext_parts: List[dict] = dataclasses.field(default_factory=list)
    ext_len: int = 0
    ext_written: int = 0
    # Typed failure (e.g. KVGatherError on a remote part): the request
    # retires with finish_reason "error" and NEVER emits a wrong token.
    error: Optional[BaseException] = None
    # SP accounting: shard i's stripe of the slot's pages (which pages a
    # sequence-parallel prefill shard installed / would hand off).
    sp_stripes: Optional[List[List[int]]] = None


@dataclasses.dataclass
class _Flight:
    """A decode step that has been dispatched and not read yet: what it
    returns (the next tokens, a pattern's routed counts after them), whom
    it ran for (slot -> request), the slot rows the host wrote before it,
    and whether it left while the step before it was still unread."""
    nxt: Any
    batch: Dict[int, _Request]
    t0: int
    synced: int
    queued: bool = False


# --------------------------------------------------------------------------
# Pure compiled pieces
# --------------------------------------------------------------------------

def _prefill_path(cfg: TransformerConfig, rows: int, kv_sharding,
                  page: Optional[int] = None, table_len: int = 0) -> str:
    """The attention form a prefill of `rows` padded rows takes: "kernel"
    (ops/prefill_attention.py) or "xla" (`_xla_prefill_attention`).
    Decided from the platform and the shapes alone; under a `tp` mesh the
    kernel runs per shard, so a shard's heads decide.  A latent layer's
    whole prompt is attended expanded: every head its own keys, nope + rope
    wide, over values of `value`; its pool holds compressed rows and no
    head's keys, so over cached pages it has the XLA form alone."""
    from ..ops.prefill_attention import prefill_path
    tp = 1
    if kv_sharding is not None and "tp" in kv_sharding.spec:
        tp = kv_sharding.mesh.shape["tp"]
    kv_heads, value = cfg.num_kv_heads, cfg.head_dim_
    if cfg.latent:
        kv_heads, value = cfg.num_heads, cfg.latent.value
    if cfg.num_heads % tp or kv_heads % tp \
            or (cfg.latent and page is not None):
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
    scores = jnp.einsum("bshd,bthd->bhst", q, kr) / jnp.sqrt(
        jnp.asarray(cfg.head_dim_, jnp.float32)).astype(q.dtype)
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, vr)


def _prefill_attend(cfg: TransformerConfig, rows: int, length, kv_sharding,
                    cached=None, blocks=None, row_block: int = ROW_BLOCK):
    """The one place a prefill's attention form is chosen (`_prefill_path`).
    For `rows` padded rows of which `length` are real and, in the suffix
    form, `cached` = (pool_k, pool_v, pages, prefix_len, page) — the slot's
    page row, whose first `prefix_len` tokens precede row 0 — returns
    (attend, per_layer) for `scan_blocks`: `attend(q, k, v, *at)` gives
    (o, the layer's new cache rows (k[0], v[0])).  `blocks`, `row_block`
    (`over_rows`'s): the suffix form's built scores are row-wise in their
    QUERIES, so they are built for the query blocks that hold a real row, a
    block at a time against all keys, and o is zeros in the others.
    A latent pattern's pool_v is None and its attend is `run_pattern`'s for
    an `L` layer (`_latent_prefill_attend`)."""
    if cfg.latent:
        return _latent_prefill_attend(cfg, rows, length, cached, blocks,
                                      row_block)
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
        kernel = _per_shard(prefill_attention, kv_sharding,
                            "hhh.pp..." if cached else "hhh.")
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
        per_layer = (pool_k, pool_v)
        heads = cfg.cache_row

        def scores(q, k, v, pk, pv):        # pk, pv: (N, page, *row)
            ck = head_rows(pk[pages], *heads).reshape(T, *heads)
            cv = head_rows(pv[pages], *heads).reshape(T, *heads)
            keys = jnp.concatenate([ck[None], k], axis=1)
            values = jnp.concatenate([cv[None], v], axis=1)
            return over_rows(
                lambda q, mask: (_xla_prefill_attention(
                    q, keys, values, mask, cfg),),
                [(q, 1), (mask, 0)], (q,), blocks, row_block)[0]

    def attend(q, k, v, *at):
        return scores(q, k, v, *at), (k[0], v[0])   # drop the B=1 dim
    return attend, per_layer


def _suffix_mask(rows: int, T: int, prefix_len):
    """Key t (over [cached T | suffix rows]) is open to suffix query s iff
    it is a REAL cached prefix position or a suffix position <= s."""
    tpos = jnp.arange(T + rows)
    qpos = jnp.arange(rows)
    return (tpos[None, :] < prefix_len) | (
        (tpos[None, :] >= T) & (tpos[None, :] - T <= qpos[:, None]))


def _latent_prefill_attend(cfg: TransformerConfig, rows: int, length, cached,
                           blocks, row_block: int):
    """`_prefill_attend` for a pattern of latent layers: the key rows are
    the slot's cached rows as they lie in its pages (none: a whole prompt)
    and then the prefill's own, and `latent_form` says from the cached rows
    which of `LATENT_FORMS` attends them (over gathered rows the absorbed
    one alone).  A whole prompt on path "kernel" up-projects its rows once
    and goes through the blocked kernel, which runs no block past `length`
    or above the diagonal and builds no scores array; everything else
    builds its scores a block of query rows at a time.  attend(q, row, w,
    *at) -> (o, (the layer's new cache rows (Sb, 1, C), None: no second
    pool))."""
    if cached is None:
        if _prefill_path(cfg, rows, None) == "kernel":
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


def _prefill_fn(params, tokens, length, cfg: TransformerConfig,
                kv_sharding=None, row_block: int = ROW_BLOCK):
    """tokens (1, Sb) padded prompt → (last_logits (V,), k, v (L, Sb, KV, D)).

    Cache rows at positions ≥ length are padding's, or zeros where the
    bucket is run by row blocks (`decoder_block`: those past the last block
    that holds a real row); decode masks them out via per-slot lengths, and
    the last-real-token logits only attend backwards (causal), so padding
    never leaks into results.  `row_block`: the tests'."""
    S = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg)
    cos, sin = rope_angles(jnp.arange(0, S, dtype=jnp.float32), cfg)
    attend, per_layer = _prefill_attend(cfg, S, length, kv_sharding)
    x, (ks, vs) = scan_blocks(params["layers"], x, cos, sin, attend, cfg,
                              per_layer, length, row_block)
    return lm_logits(params, x[0, length - 1], cfg), ks, vs


def _state_prefill_fn(params, pool_k, pool_v, pages, tokens, prefix_len,
                      length, ckpt, row, cfg: TransformerConfig, page: int,
                      every: int, row_block: int = ROW_BLOCK, keep: int = 0):
    """A prefill of a pattern with recurrent layers: ONE form for a whole
    prompt and for a suffix, since both run the recurrence from a given
    state.  The rows `tokens` (1, Sb), of which `length` are real, follow
    `prefix_len` tokens whose keys and values lie in `pages` (as
    `_suffix_prefill_fn` has it) and whose recurrent state is row `row` of
    the checkpoint pool `ckpt` (row 0: the state of having read nothing,
    with prefix_len 0).  Returns (last-token logits, the attention layers'
    ks, vs (nA, Sb, KV, D), the state after `length` rows, the state after
    every `every` rows (the stateful mixers' `every`), the experts every row
    chose (nE, Sb, K)).  Where the bucket is run by row blocks (`run_pattern`
    says when) what lies past the last block that holds a real row is
    zeros, as `_prefill_fn` has it: ks, vs, the checkpoints at boundaries
    past the prompt (`_install_state` gives those to the scratch row), the
    experts chosen.  `row_block`: the tests'.  `pages` None: a whole
    prompt that attends nothing cached (a latent pattern's, whose attention
    form follows from that: `_latent_prefill_attend`), or a pattern no
    layer of which attends (no pool: ks and vs are None, and what precedes
    the rows is in the state alone).  `keep`: `run_pattern`'s."""
    Sb = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg)
    cos, sin = rope_angles(prefix_len + jnp.arange(Sb, dtype=jnp.int32), cfg)
    cached = None if pages is None else (pool_k, pool_v, pages, prefix_len,
                                         page)
    attend, per_layer = None, ()
    if set(cfg.kinds) & set(ATTEND):
        attend, per_layer = _prefill_attend(
            cfg, Sb, length, None, cached,
            blocks_to_run(length, Sb, row_block, every), row_block)
    rec = [{k: c[k][row][None] for k in c} for c in ckpt]
    x, kv, rec, kept, _, chosen = run_pattern(
        params["layers"], x, cos, sin, attend, cfg, rec, per_layer,
        length=length, every=every, row_block=row_block, keep=keep)
    ks, vs = kv or (None, None)
    return (lm_logits(params, x[0, length - 1], cfg), ks, vs, rec, kept,
            chosen)


def _install_state_fn(rec, ckpt, slot, end, kept, rows):
    """Write a prefill's recurrent state into slot `slot` of the resident
    per-slot state `rec`, and the checkpoints it passed into rows `rows`
    (n,) of the pool `ckpt`; a checkpoint nobody keeps goes to row 1, the
    scratch row."""
    rec = [{k: r[k].at[slot].set(e[k][0]) for k in r}
           for r, e in zip(rec, end)]
    ckpt = [{k: c[k].at[rows].set(kp[k][0]) for k in c}
            for c, kp in zip(ckpt, kept)]
    return rec, ckpt


def _install_fn(pool_k, pool_v, ks, vs, pages, page: int, kv_sharding):
    """Write a prefill's (L, Sb, KV, D) kv into the slot's reserved pages,
    whole pages of rows as the pool holds them (`pool_rows`).

    pages: (P,) int32 physical page ids.  Entries past the slot's reserved
    count are 0 — the shared scratch page, whose contents are garbage by
    contract: every read of it is masked (valid = t <= length always stays
    within the reserved pages) and the allocator never hands page 0 out."""
    L, Sb, KV, D = ks.shape
    P = pages.shape[0]
    pad = P * page - Sb
    # (Each step over the pair of pools, of which a latent pattern's second
    # is None: an empty tree.)
    pools, new = (pool_k, pool_v), (ks, vs)
    if pad > 0:
        new = jax.tree.map(
            lambda r: jnp.pad(r, ((0, 0), (0, pad), (0, 0), (0, 0))), new)
    new = jax.tree.map(
        lambda r: pool_rows(r.reshape(L, P, page, KV, D), KV, D), new)
    pools = jax.tree.map(lambda pool, r: pool.at[:, pages].set(r), pools, new)
    if kv_sharding is not None:
        pools = jax.lax.with_sharding_constraint(pools, kv_sharding)
    return pools


def _decode_logits_fn(params, pool_k, pool_v, tables, last_tokens, lengths,
                      active, cfg: TransformerConfig, page: int, kv_sharding,
                      rec=()):
    """The model half of a decode step: every slot's last token through the
    layers against the paged pool -> (pool_k', pool_v', logits (B, V) f32),
    and for a pattern three more: the recurrent layers' per-slot state `rec`
    advanced for the active slots, the routed layers' counts (n, 2) and
    their chosen experts (n, B, 1, K).

    The pool is carried through the layer loop whole and written where the
    new token lands; attention (ops/paged_attention.py) reads the pages a
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
    paged = _per_shard(paged_decode_attention, kv_sharding, "hpp...")
    heads = cfg.cache_row

    def written(pool, li, new):     # new (B, 1, KV, D): one row a slot
        return pool.at[li, write_page, write_off].set(
            pool_rows(new[:, 0], *heads))

    if cfg.pattern:
        pools = [pool_k, pool_v]        # written layer by layer, in place

        def attend(q, k, v, li):
            pools[0] = written(pools[0], li, k)
            pools[1] = written(pools[1], li, v)
            return paged(q[:, 0], *pools, tables, lengths, li)[:, None], None

        def latent_attend(q, row, w, li):
            # One query row a slot over rows that lie in the pool: the
            # absorbed form (`latent_form(cached)`), the rows read where
            # they lie (ops/paged_attention.py: `paged_latent_attention`).
            pools[0] = written(pools[0], li, row)
            o = paged_latent_attention(
                latent_absorb(w, q[:, 0], cfg), pools[0], tables, lengths,
                li, scale=cfg.latent.scale, value_lanes=cfg.latent.rank)
            return latent_unabsorb(w, o[:, None], cfg), None
        if cfg.latent:
            attend = latent_attend
        # (No pool: no layer attends, and `attend` is never called.)
        layer = () if pool_k is None else (
            jnp.arange(pool_k.shape[0], dtype=jnp.int32),)
        x, _, rec, _, counts, chosen = run_pattern(
            params["layers"], x, cos, sin, attend, cfg, rec, layer,
            live=active)
        return (*pools, lm_logits(params, x[:, 0], cfg), rec, counts, chosen)

    def body(carry, layer):
        x, pk, pv = carry               # pk/pv: the whole pool, in place
        lp, li = layer

        def attend(q, k, v):
            wk, wv = written(pk, li, k), written(pv, li, v)
            o = paged(q[:, 0], wk, wv, tables, lengths, li)       # (B,H,D)
            return o[:, None], (wk, wv)
        x, (pk, pv) = decoder_block(lp, x, cos, sin, attend, cfg)
        return (x, pk, pv), None

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


def _suffix_prefill_fn(params, pool_k, pool_v, pages, tokens, prefix_len,
                       length, cfg: TransformerConfig, page: int,
                       kv_sharding=None, row_block: int = ROW_BLOCK):
    """Suffix half of a prefix-cache hit: run the transformer over ONLY
    tokens[prefix_len:] while attending to the cached KV of
    tokens[:prefix_len] already resident in the pool's shared pages.

    pages: (P,) a full page-table row — shared prefix pages first, then
    the freshly reserved pages whose contents are garbage (masked, like
    decode's scratch reads; prefix_len is page-aligned by construction).
    tokens: (1, Sb) the PADDED suffix; length = real suffix length.
    Returns (last-token logits, suffix ks, vs (L, Sb, KV, D)) — the same
    contract as _prefill_fn, so the install path is shared."""
    Sb = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg)
    # RoPE at absolute positions prefix_len + i.
    cos, sin = rope_angles(prefix_len + jnp.arange(Sb, dtype=jnp.int32), cfg)
    attend, per_layer = _prefill_attend(
        cfg, Sb, length, kv_sharding, (pool_k, pool_v, pages, prefix_len, page))
    x, (ks, vs) = scan_blocks(params["layers"], x, cos, sin, attend, cfg,
                              per_layer, length, row_block)
    return lm_logits(params, x[0, length - 1], cfg), ks, vs


class _PrefixCache:
    """Page-granular KV prefix reuse (vLLM's PagedAttention block
    sharing, Kwon et al. SOSP'23, mapped onto the paged pool): every
    FULL prompt page is keyed by the rolling hash of all tokens up to
    its end, so requests sharing a prompt prefix share the physical
    pages — skipping both the page allocation and the prefill compute
    for the shared span.

    Entries are LRU-ordered; eviction is driven by pool pressure (the
    reserve path evicts until the new request fits or the cache is dry).
    Pages are ref-counted by the engine: cache membership holds one ref
    per entry, each active request one — a page returns to the free
    list only when the last holder lets go, so evicting an entry out
    from under an in-flight request is safe.

    STATE CHECKPOINTS (`every` > 0: a model with recurrent layers).  Cached
    keys and values are then half of what a prefix left behind: the other
    half is the recurrent state after it, which is kept only at every
    `every`-th token (a row of the engine's checkpoint pool, keyed like the
    page that ends there).  An entry can be used from the last such
    boundary at or before it: `lookup` cuts the hit back to there and the
    prefill recomputes the tokens between (`recomputed` counts them).  An
    entry holds a reference to every checkpoint row at or before its own
    boundary, as it does to its pages, so evicting it frees pages and rows
    together and a row outlives every entry that could use it.  The rows
    are this cache's to hand out (`free_rows`): nothing else holds one.
    A prefill keeps the LAST `keep` boundaries it passes (`boundaries`), a
    number that follows from the rows there are and names no model: a
    re-ask needs the last boundary inside the text it shares, and a row may
    cost as much as thousands of tokens of keys and values.  Where no layer
    attends there are no pages (`insert` without a page row): an entry then
    holds rows only, and the keys, the boundaries and the eviction are as
    they are."""

    def __init__(self, page: int, tag: bytes = b"", every: int = 0,
                 rows: Sequence[int] = ()):
        self.page = page
        self.every = every
        self.free_rows: List[int] = list(rows)
        self.n_rows = len(self.free_rows)
        # boundary key -> checkpoint row, and back; row -> entries holding
        # it; entry key -> the rows it holds
        self._rows: Dict[bytes, int] = {}
        self._row_key: Dict[int, bytes] = {}
        self._row_refs: Dict[int, int] = {}
        self._held: Dict[bytes, List[int]] = {}
        self.recomputed = 0         # tokens recomputed behind a checkpoint
        self.hit_tokens = 0         # prompt tokens of the requests that hit
        self.rows_kept = 0
        self.rows_evicted = 0
        # Key namespace tag: sequence-parallel engines key their pages
        # per SP layout (tag = b"sp<degree>") so pages cached under one
        # shard→stripe mapping can never alias pages cached under
        # another — the per-shard half of "prefix-cache keys become
        # per-shard" (the other half is _Request.sp_stripes).
        self.tag = tag
        self._memo: Tuple[Any, List[bytes]] = (None, [])
        # rolling-hash key -> page ids covering the whole prefix
        self._entries: "OrderedDict[bytes, List[int]]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.hit_pages = 0          # pages whose prefill was skipped
        self.evictions = 0

    def _keys(self, prompt: Sequence[int], upto: int) -> List[bytes]:
        """Rolling hash at every page boundary 1..upto.  One admission asks
        three times (`lookup`, `boundaries`, `insert`) about one prompt:
        the last prompt's keys are kept, by the list's identity."""
        memo, keys = self._memo
        if memo is prompt and len(keys) >= upto:
            return keys[:upto]
        full = max(upto, len(prompt) // self.page)
        data = np.asarray(prompt[:full * self.page], np.int32).tobytes()
        h = hashlib.blake2b(digest_size=16)
        h.update(self.tag)
        keys, step = [], 4 * self.page
        for k in range(full):
            h.update(data[k * step:(k + 1) * step])
            keys.append(h.copy().digest())
        self._memo = (prompt, keys)
        return keys[:upto]

    def lookup(self, prompt: Sequence[int]) -> Tuple[int, List[int], int]:
        """Longest cached prefix usable by this prompt: (token count,
        page ids, checkpoint row).  Capped at S-1 tokens — the last prompt
        token's logits must be computed, so at least a one-token suffix
        always runs through prefill.  With state checkpoints the hit is cut
        back to the last boundary that kept one (row 0 with no tokens: a
        miss); without, the row is 0 and means nothing."""
        usable = (len(prompt) - 1) // self.page
        if usable <= 0:
            return 0, [], 0
        keys = self._keys(prompt, usable)
        for k in range(usable, 0, -1):
            pages = self._entries.get(keys[k - 1])
            if pages is None:
                continue
            row, found = 0, k
            if self.every:
                per = self.every // self.page
                k -= k % per
                while k and keys[k - 1] not in self._rows:
                    k -= per
                if not k:
                    break               # cached pages, but no state to go on
                row = self._rows[keys[k - 1]]
                self.recomputed += (found - k) * self.page
            self._entries.move_to_end(keys[found - 1])
            self.hits += 1
            self.hit_pages += k
            self.hit_tokens += len(prompt)
            return k * self.page, list(pages[:k]), row
        self.misses += 1
        return 0, [], 0

    def boundaries(self, prompt: Sequence[int], after: int,
                   keep: int = 0) -> List[int]:
        """The checkpoint boundaries (token counts) of `prompt` past
        `after` that its full pages cover, the last `keep` of them (0:
        all), and of those the ones no row is kept for yet."""
        if not self.every:
            return []
        full = len(prompt) // self.page * self.page
        marks = range(after + self.every, full + 1, self.every)[-keep:]
        if not marks:
            return []
        keys = self._keys(prompt, full // self.page)
        return [b for b in marks if keys[b // self.page - 1] not in self._rows]

    def hold_row(self, row: int, by: int = 1) -> None:
        """A prefill that starts from `row` holds it (`by` 1) until it has
        run (`by` -1); row 0, the state of nothing read, is nobody's."""
        if row:
            self._row_refs[row] += by
            if not self._row_refs[row]:
                self._drop_row(row)
                self.rows_evicted += 1

    def _drop_row(self, row: int) -> None:
        del self._rows[self._row_key.pop(row)], self._row_refs[row]
        self.free_rows.append(row)

    def insert(self, prompt: Sequence[int], table_row, incref,
               rows: Optional[Dict[int, int]] = None) -> None:
        """Register every full prompt page of a freshly admitted request
        (decode writes land strictly after them, so they are immutable);
        `table_row` None: there are no pages, and an entry holds rows only.
        `rows`: boundary (tokens) -> the checkpoint row (taken from
        `free_rows`) this prefill wrote for it; each new entry takes a
        reference to every row at or before its boundary, and a row no
        entry took goes back."""
        full = len(prompt) // self.page
        if full <= 0:
            for row in (rows or {}).values():
                self.free_rows.append(row)
            return
        keys = self._keys(prompt, full)
        for b, row in (rows or {}).items():
            self._rows[keys[b // self.page - 1]] = row
            self._row_key[row] = keys[b // self.page - 1]
            self._row_refs[row] = 0
            self.rows_kept += 1
        per = self.every // self.page if self.every else 0
        for k in range(1, full + 1):
            key = keys[k - 1]
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            pages = [] if table_row is None \
                else [int(p) for p in table_row[:k]]
            self._entries[key] = pages
            for p in pages:
                incref(p)
            if per:
                self._held[key] = held = [
                    self._rows[keys[j - 1]] for j in range(per, k + 1, per)
                    if keys[j - 1] in self._rows]
                for r in held:
                    self._row_refs[r] += 1
        for row in (rows or {}).values():
            if not self._row_refs[row]:
                self._drop_row(row)
                self.rows_kept -= 1

    def evict_lru(self, decref, demote=None) -> bool:
        """Drop the least-recently-used entry; True if one was dropped.
        Pages still held by active requests stay allocated (ref > 0); a
        checkpoint row whose last holder this entry was is free again.
        `demote(key, pages)` — when given — runs BEFORE the refs drop,
        so the hook can copy the page contents out of the pool while
        they are still guaranteed unrecycled (after decref the pages
        rejoin the free list and may be overwritten by any admission)."""
        if not self._entries:
            return False
        key, pages = self._entries.popitem(last=False)
        self.evictions += 1
        if demote is not None:
            demote(key, pages)
        for p in pages:
            decref(p)
        for r in self._held.pop(key, ()):
            self._row_refs[r] -= 1
            if not self._row_refs[r]:
                self._drop_row(r)
                self.rows_evicted += 1
        return True


class _KVDemoteStore:
    """Demoted prefix-cache pages: bounded host window + NVMe overflow.

    LRU-evicted prefix-cache entries land here instead of being freed
    outright: the evicted pages' contents move device -> host (a byte-
    bounded LRU window) and overflow to NVMe part files under the spill
    dir, in the external-KV part format ({"k", "v", "len"}).  A later
    request sharing the prefix PROMOTES the entry back into the pool
    (device_put + page re-alloc) instead of re-running prefill — the
    same demote-then-restore policy shape as the object store's
    arena -> NVMe spill tier, driven by the same pool-pressure signal.
    Entries are caches, never truth: any demoted entry may be dropped
    (e.g. on a disk write failure) at the cost of a re-prefill."""

    def __init__(self, byte_limit: int, spill_dir: str):
        self.byte_limit = max(0, int(byte_limit))
        self.spill_dir = spill_dir
        self._host: "OrderedDict[bytes, dict]" = OrderedDict()
        self._disk: Dict[bytes, str] = {}
        self._host_bytes = 0
        self._seq = 0
        self.demoted_pages = 0
        self.promoted_pages = 0
        self.disk_spills = 0

    def __len__(self) -> int:
        return len(self._host) + len(self._disk)

    def contains(self, key: bytes) -> bool:
        return key in self._host or key in self._disk

    def put(self, key: bytes, k_np, v_np, npages: int) -> None:
        if self.contains(key):
            return
        self._host[key] = {"k": k_np, "v": v_np, "len": int(npages)}
        self._host_bytes += k_np.nbytes + v_np.nbytes
        self.demoted_pages += int(npages)
        while self._host_bytes > self.byte_limit and self._host:
            okey, part = self._host.popitem(last=False)
            self._host_bytes -= part["k"].nbytes + part["v"].nbytes
            self._spill(okey, part)

    def _spill(self, key: bytes, part: dict) -> None:
        try:
            os.makedirs(self.spill_dir, exist_ok=True)
            self._seq += 1
            path = os.path.join(
                self.spill_dir,
                "kvdemote-%d-%d.npz" % (os.getpid(), self._seq))
            np.savez(path, k=part["k"], v=part["v"],
                     len=np.int64(part["len"]))
            self._disk[key] = path
            self.disk_spills += 1
        except OSError:
            pass    # dropped: a demoted entry is a cache, never truth

    def get(self, key: bytes) -> Optional[dict]:
        """Pop an entry for promotion ({"k","v","len"}), or None."""
        part = self._host.pop(key, None)
        if part is not None:
            self._host_bytes -= part["k"].nbytes + part["v"].nbytes
            self.promoted_pages += part["len"]
            return part
        path = self._disk.pop(key, None)
        if path is None:
            return None
        try:
            with np.load(path) as z:
                part = {"k": z["k"], "v": z["v"], "len": int(z["len"])}
        except OSError:
            return None
        finally:
            try:
                os.unlink(path)
            except OSError:
                pass
        self.promoted_pages += part["len"]
        return part

    def stats(self) -> Dict[str, Any]:
        return {"demoted_pages": self.demoted_pages,
                "promoted_pages": self.promoted_pages,
                "demoted_entries": len(self),
                "demoted_host_bytes": self._host_bytes,
                "demoted_disk_entries": len(self._disk),
                "demoted_disk_spills": self.disk_spills}


class _KVWindow:
    """Bounded host-side prefetch window over external KV parts.

    The streamed-attention path never materializes a paged request's
    context in the device pool; what it does need is the CURRENT part's
    bytes on host.  This window holds at most `capacity` parts (LRU),
    fetched through the engine's `kv_fetch` callback (the serving layer
    wires it to an object-plane get — a swarm-plane bulk pull when the
    part lives in a remote arena) and optionally warmed ahead of the
    attention step via `kv_prefetch` (async; gather overlaps compute).
    A window smaller than the part count degrades to re-fetching —
    counted, never silent (`refetches`)."""

    def __init__(self, capacity: int, fetch, prefetch=None):
        self.capacity = max(1, int(capacity))
        self._fetch = fetch
        self._prefetch = prefetch
        self._data: "OrderedDict[str, dict]" = OrderedDict()
        self._futures: Dict[str, Any] = {}
        # Recently-seen keys for refetch detection, LRU-BOUNDED: a
        # prefill shard streams thousands of one-shot context-part keys
        # that no request ever drop()s — an unbounded set would be a
        # slow leak in exactly the always-on serving process.
        self._seen: "OrderedDict[str, None]" = OrderedDict()
        self._seen_cap = max(64, 16 * self.capacity)
        self.fetches = 0
        self.refetches = 0
        self.bytes_fetched = 0
        self.wait_s = 0.0

    def _mark_seen(self, key: str) -> None:
        self._seen[key] = None
        self._seen.move_to_end(key)
        while len(self._seen) > self._seen_cap:
            self._seen.popitem(last=False)

    def _validate(self, key: str, data) -> dict:
        if not isinstance(data, dict) or "k" not in data or "v" not in data:
            raise KVGatherError(
                f"KV part {key!r} resolved to {type(data).__name__}, "
                f"expected a {{'k','v','len'}} dict")
        return data

    def _admit(self, key: str, data: dict) -> dict:
        self._data[key] = data
        self._data.move_to_end(key)
        while len(self._data) > self.capacity:
            self._data.popitem(last=False)
        return data

    def put(self, key: str, data: dict) -> None:
        """Seed a locally-produced part (chunked prefill keeps its own
        freshly published stripes hot for the next chunk)."""
        self._mark_seen(key)
        self._admit(key, data)

    def prefetch(self, items) -> None:
        """Kick async fetches for [(key, handle)] not already resident."""
        if self._prefetch is None:
            return
        for key, handle in items:
            if key in self._data or key in self._futures:
                continue
            try:
                self._futures[key] = self._prefetch(handle)
            except Exception:      # prefetch is best-effort; get() retries
                self._futures.pop(key, None)

    def get(self, key: str, handle) -> dict:
        import time as _time
        data = self._data.get(key)
        if data is not None:
            self._data.move_to_end(key)
            return data
        t0 = _time.perf_counter()
        fut = self._futures.pop(key, None)
        try:
            if fut is not None:
                data = fut.result()
            else:
                data = self._fetch(handle)
        except KVGatherError:
            raise
        except Exception as e:
            raise KVGatherError(
                f"gather of KV part {key!r} failed: "
                f"{type(e).__name__}: {e}") from e
        self.wait_s += _time.perf_counter() - t0
        data = self._validate(key, data)
        self.fetches += 1
        if key in self._seen:
            self.refetches += 1
        self._mark_seen(key)
        self.bytes_fetched += (getattr(data["k"], "nbytes", 0)
                               + getattr(data["v"], "nbytes", 0))
        return self._admit(key, data)

    def drop(self, keys) -> None:
        for k in keys:
            self._data.pop(k, None)
            self._futures.pop(k, None)
            self._seen.pop(k, None)

    def stats(self) -> Dict[str, Any]:
        return {"fetches": self.fetches, "refetches": self.refetches,
                "bytes": self.bytes_fetched, "wait_s": self.wait_s,
                "resident": len(self._data), "capacity": self.capacity}


def _default_kv_fetch(handle):
    """Engine-standalone fetch: parts passed by value ARE their data."""
    if isinstance(handle, dict):
        return handle
    raise KVGatherError(
        f"remote KV handle {type(handle).__name__} needs a kv_fetch "
        f"callback (the serving layer wires ray_tpu.get)")


# --------------------------------------------------------------------------
# Engine
# --------------------------------------------------------------------------

# A pattern with stateful layers keeps a state checkpoint every this many
# of their chunks (`state_chunk`: 4 x 128 = 512 tokens for Mamba-2's
# published scan chunk, and for the short convolution's), and
# pads no prefill below `_MIN_STATE_ROWS` rows: under that a prefill's time
# is the weights' read, and a bucket fewer is a program fewer to warm (a
# warm-up that reaches the suffix programs through one shared page of 16
# tokens starts at 24 rows).
_CKPT_CHUNKS = 4
_MIN_STATE_ROWS = 32


class LLMEngine:
    """Continuous-batching engine (reference concept: vllm engine wrapped
    by python/ray/llm/_internal/serve/engines/vllm/; here native JAX with
    paged KV and optional GSPMD tensor parallelism)."""

    def __init__(self, cfg: TransformerConfig, params=None, *,
                 max_batch: int = 4, max_len: int = 256, seed: int = 0,
                 mesh=None, rules=None, page_size: int = 64,
                 kv_pages: Optional[int] = None,
                 ckpt_rows: Optional[int] = None,
                 prefix_cache: bool = False,
                 sp_degree: Optional[int] = None,
                 sp_strategy: str = "ring",
                 prefill_chunk: Optional[int] = None,
                 kv_gather_window: int = 4,
                 kv_fetch=None, kv_prefetch=None):
        """kv_pages sizes the shared pool (default: enough for every slot
        at max_len — set it lower to oversubscribe: admission then queues
        until pages free up).  ckpt_rows sizes the state-checkpoint pool of
        a model with recurrent layers, in rows an operator can keep (two
        more are the engine's own); default: one for every `_every` tokens
        of `kv_pages`, so that the page pool is the one thing sized, which
        serves where a row is small beside the pages of those tokens.  A
        row that is a whole cache (power retention: 38 MB a layer) is sized
        by what it costs, and where no layer attends nothing else sizes
        it; a prefill then keeps only the last boundaries it passes, the
        rows' share of two prompts a slot and at least two (`_keep`), where
        the default pool keeps every one.  mesh: shard weights + KV over
        its tp axis.
        prefix_cache=True enables page-granular KV prefix reuse (shared
        full prompt pages skip prefill; LRU-evicted under pool
        pressure) — off by default: retired pages then linger in the
        cache instead of returning to the free list immediately.

        sp_degree (default: cfg.sp_degree) > 1 runs prefill attention
        sequence-parallel over an ``sp`` mesh axis (ring attention, or
        Ulysses via sp_strategy="ulysses") — a local sp mesh is built
        when no mesh is passed.  prefill_chunk (tokens, rounded to a
        page multiple) bounds the per-tick prefill compute: a longer
        prompt advances one chunk per step() so a huge prompt neither
        compiles one giant XLA bucket nor starves the continuous-
        batching tick.  kv_gather_window / kv_fetch / kv_prefetch
        configure the streamed cross-host KV path (add_paged_request):
        at most `window` external parts are host-resident at once,
        fetched via kv_fetch (blocking) and warmed via kv_prefetch
        (async) so the gather overlaps decode compute."""
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.page = max(8, min(page_size, max_len))
        self.pages_per_slot = math.ceil(max_len / self.page)
        # The pool has rows for the layers that attend: all of the dense
        # decoder's, the `*` or `L` layers of a pattern.  Where none does
        # (a pattern of recurrent layers alone) there is NO pool: no array,
        # no page to reserve or to wait for, and the whole cache is the
        # state rows below.
        L = sum(cfg.count(k) for k in ATTEND)
        pages = kv_pages if kv_pages is not None \
            else max_batch * self.pages_per_slot
        # page 0 is scratch (inactive-slot writes land there); never handed out
        self.n_pages = 1 + (pages if L else 0)
        kvh, d = cfg.cache_row
        # State checkpoints, every `_every` tokens (0: no recurrent layer).
        self._every = _CKPT_CHUNKS * state_chunk(cfg)
        if cfg.pattern:
            if mesh is not None or prefill_chunk or (sp_degree or 1) > 1 \
                    or getattr(cfg, "sp_degree", 1) > 1:
                raise ValueError(
                    "a pattern of layer kinds is served on one device, whole "
                    "prompts at a time: no mesh, sp_degree or prefill_chunk")
            if self._every % self.page:
                raise ValueError(
                    f"page_size {self.page} does not divide the state "
                    f"checkpoints' spacing of {self._every} tokens")
            if cfg.latent and pool_row(*cfg.cache_row) != "latent":
                raise ValueError(
                    f"a cache row of {cfg.cache_row[1]} values is not a "
                    "latent row: more than one 128-lane row and no whole "
                    "number of them (ops/paged_attention.py: pool_row)")

        from . import sequence_parallel as _sp
        deg = sp_degree if sp_degree is not None \
            else getattr(cfg, "sp_degree", 1)
        if sp_degree is None and deg == 1 and mesh is not None \
                and mesh.shape.get("sp", 1) > 1:
            # No caller-requested degree: adopt the mesh's sp axis.  An
            # EXPLICIT sp_degree (or cfg default > 1) is never silently
            # overridden — a mismatch hits the ValueError below.
            deg = mesh.shape["sp"]
        self.sp_degree = max(1, int(deg))
        self.sp_strategy = sp_strategy
        sp_built = False
        if self.sp_degree > 1:
            if self.sp_degree & (self.sp_degree - 1):
                raise ValueError(
                    f"sp_degree={self.sp_degree} must be a power of two "
                    f"(pow-2 prefill buckets shard evenly)")
            if max_len % self.sp_degree:
                # _bucket clamps to max_len, so a non-divisible max_len
                # would reach shard_map as an unsplittable sequence axis
                # on the first long prompt — fail at construction instead.
                raise ValueError(
                    f"max_len={max_len} must be divisible by "
                    f"sp_degree={self.sp_degree} (prefill buckets clamp "
                    f"to max_len)")
            _sp.validate_sp(cfg, self.sp_degree, sp_strategy)
            if mesh is None:
                mesh = _sp.sp_mesh(self.sp_degree)
                sp_built = True
            elif mesh.shape.get("sp", 1) != self.sp_degree:
                raise ValueError(
                    f"sp_degree={self.sp_degree} but the given mesh's sp "
                    f"axis is {mesh.shape.get('sp', 1)} — build the mesh "
                    f"with MeshSpec(sp={self.sp_degree})")
        self.mesh = mesh
        self._sp = _sp

        self._kv_shd = None
        param_shd = None
        if sp_built:
            # Engine-built sp-only mesh: weights + pool REPLICATE over
            # the sp devices (only the prefill sequence axis is
            # sharded); decode/install run identically on every shard.
            from jax.sharding import NamedSharding, PartitionSpec as P
            param_shd = NamedSharding(mesh, P())
            self._kv_shd = NamedSharding(mesh, P())
        elif mesh is not None:
            from ..parallel.sharding import LogicalAxisRules, tree_shardings
            from jax.sharding import NamedSharding, PartitionSpec as P
            # Megatron layout minus vocab-parallel: replicating the (small)
            # embed/lm_head keeps token gathers collective-free.
            rules = rules or LogicalAxisRules.default().with_overrides(
                ("vocab", None), ("embed", None))
            has_tp = "tp" in mesh.shape
            if has_tp and cfg.num_kv_heads % mesh.shape["tp"]:
                raise ValueError(
                    f"num_kv_heads={cfg.num_kv_heads} not divisible by "
                    f"tp={mesh.shape['tp']}")
            param_shd = tree_shardings(param_logical_axes(cfg), mesh, rules)
            # No tp axis (e.g. a dp-only serving mesh): weights + KV
            # replicate rather than erroring on the undefined axis name.
            # (Dimension 3 is the KV heads, or the row of KV * D lanes with
            # the KV heads major: either way a shard holds whole heads.)
            self._kv_shd = NamedSharding(
                mesh, P(None, None, None, "tp") if has_tp else P())
        self.params = params if params is not None else \
            init_params(cfg, jax.random.key(seed))
        if param_shd is not None:
            self.params = jax.device_put(self.params, param_shd)

        # Two pools, keys and values; a latent pattern has ONE, whose row is
        # both, and None (an empty tree) where the others have the second.
        shape = pool_shape(L, self.n_pages, self.page, kvh, d)
        self._pk = jnp.zeros(shape, cfg.dtype, device=self._kv_shd) \
            if L else None
        self._pv = None if cfg.latent or not L else jnp.zeros(
            shape, cfg.dtype, device=self._kv_shd)
        self._free_slots = list(range(max_batch))
        self._free_pages = list(range(1, self.n_pages))
        # page -> holder count (requests + cache entries); a page leaves
        # _free_pages with count 1 and returns when the count hits 0.
        self._page_refs: Dict[int, int] = {}
        cache_tag = (b"sp%d" % self.sp_degree) if self.sp_degree > 1 else b""
        # `ckpt_rows` checkpoint rows, or one for every `_every` tokens of
        # `kv_pages`.  Row 0 is the state of having read nothing and row 1
        # takes the checkpoints nobody keeps; neither is handed out.
        n_rows = 0
        if self._every and prefix_cache:
            n_rows = ckpt_rows if ckpt_rows is not None \
                else pages * self.page // self._every
        # A prefill keeps the last `_keep` boundaries it passes.  A pool
        # sized by the pages (rows that are small beside them) keeps every
        # one, as it always did: an early boundary serves a prompt that
        # shares only its beginning.  A pool sized by its bytes keeps the
        # rows' share of two prompts a slot, at least two (the last
        # boundary may fall inside a prompt's own question).
        self._keep = max_len // self._every if self._every else 0
        if ckpt_rows is not None:
            self._keep = min(max(2, n_rows // (2 * max_batch)), self._keep)
        self._cache = _PrefixCache(self.page, cache_tag, self._every,
                                   range(2, 2 + n_rows)) \
            if prefix_cache else None
        stateful = [k for k in cfg.kinds if k in STATEFUL]
        self._ckpt = [zero_state(cfg, k, 2 + n_rows) for k in stateful]
        # KV offload tier: LRU-evicted prefix-cache pages demote into a
        # bounded host window (NVMe overflow) instead of being freed;
        # hits promote back via device_put.  Pool squeezes (mem_chaos)
        # park free pages on the ballast list so admission sees a
        # smaller pool and the eviction/demotion path actually drains.
        self._demote: Optional[_KVDemoteStore] = None
        self._ballast_pages: List[int] = []
        if self._cache is not None:
            try:
                from .._private.config import get_config as _getcfg
                _c = _getcfg()
                _demo_on = bool(_c.kv_cache_demotion_enabled)
                _demo_lim = int(_c.kv_demoted_bytes_limit)
                _demo_dir = str(_c.object_spill_dir or "")
            except Exception:
                _demo_on, _demo_lim, _demo_dir = True, 256 << 20, ""
            if not _demo_dir:
                _demo_dir = os.path.join(
                    tempfile.gettempdir(),
                    "ray_tpu_kv_demote_%d" % os.getpid())
            if _demo_on and not self._every and not cfg.latent:
                # (Demoted pages would leave their state checkpoints behind;
                # the store keeps K/V pairs, and a latent page is one array.)
                self._demote = _KVDemoteStore(_demo_lim, _demo_dir)
        self._tables = np.zeros((max_batch, self.pages_per_slot), np.int32)
        self._slots: Dict[int, _Request] = {}
        self._waiting: List[_Request] = []
        # Live requests by id (waiting + active): cancel_request and the
        # serving layer's stream fan-out address requests through this.
        self._requests: Dict[int, _Request] = {}
        self._tick_events: List[Tuple[int, int, bool]] = []
        self._next_id = 0
        # The scheduler's truth about every slot, on the host.  The decode
        # step works on its own copy on the device (`self._dev`), which it
        # advances itself; `_touched` marks the slots whose host side has
        # changed since the device last accepted them.
        self._last = np.zeros(max_batch, np.int32)
        self._lengths = np.zeros(max_batch, np.int32)
        self._temps = np.zeros(max_batch, np.float32)
        self._touched = np.zeros(max_batch, bool)
        # The decode step that is out, where the last `step()` left one
        # (`_next_batch_if_ahead`, `_next_batch_if_queued`); the owner's word
        # that someone is waiting to hand the engine work (a replica: its
        # lock has waiters), which keeps the next step back; and the
        # owner's hook for a tick's events so far, its first tokens, called
        # on the engine's thread before the tick's decode step is read.
        # `hold_ahead` None: no owner who could tell, and no step leaves
        # ahead (a request added between two calls joins the very next
        # step, as ever).
        self._ahead: Optional[_Flight] = None
        self.hold_ahead: Optional[Callable[[], bool]] = None
        self.hand_first: Optional[Callable[[List[Tuple[int, int, bool]]],
                                           None]] = None
        self._state_shd = None if mesh is None else NamedSharding(
            mesh, PartitionSpec())
        idle = np.zeros(max_batch, bool)
        none = _pack_rows(self._tables, self._last, self._lengths, idle,
                          self._temps, idle)
        self._dev = jax.device_put(
            {"slots": none[:, :-1], "rng": jax.random.key(seed + 1)},
            self._state_shd)
        if self._every:
            # Per slot, the recurrent layers' state: resident with the rest.
            self._dev["rec"] = [zero_state(cfg, k, max_batch)
                                for k in stateful]
        # What the routed layers' decode steps touched, a row a layer:
        # held experts that got a row, (token, expert) rows computed;
        # cumulative, and the last step's.
        self._routed = np.zeros((cfg.count("E"), 2), np.int64)
        self._step_routed = np.zeros((cfg.count("E"), 2), np.int64)
        # The update of a step before which no slot was touched: marks none.
        self._no_rows = jax.device_put(none, self._state_shd)
        self._prefill_jit = {}
        self.phases = TickPhases()
        # How much of what the tables address the batch decode step reads
        # (ops/paged_attention.py reads live pages only), and by which path.
        self._decode_steps = 0
        self._steps_queued = 0      # of them, left with the one before unread
        self._pages_read = 0
        self._step_pages_read = 0
        # How often the host wrote slot state to the device, and how many
        # slot rows: a step no slot was touched before writes none.
        self._state_syncs = 0
        self._state_rows = 0
        self._step_state_rows = 0
        # Which attention form the prefills took (ops/prefill_attention.py)
        # and how many key blocks they ran beside what S x S covers; how
        # many row blocks a layer's halves ran beside the bucket's
        # (models/transformer.py:row_blocks); the last one's, as its
        # `prefill` span carries them.
        self._prefill_stats = {"path": "", "kernel_calls": 0, "xla_calls": 0,
                               "kv_blocks_run": 0, "kv_blocks_dense": 0,
                               "row_blocks_run": 0, "row_blocks_dense": 0}
        self._prefill_ran: Dict[str, Any] = {}
        # A latent pattern: the cache rows its decode steps read (live
        # tokens, all slots) and the key rows its prefills attended, with
        # how many of them were up-projected to per-head keys and values
        # (`latent_form`: all of an expanded prefill's, none of an absorbed
        # one's); real rows, counted on the host.
        self._latent = {"rows_read": 0, "step_rows_read": 0,
                        "rows_attended": 0, "rows_expanded": 0,
                        "prefills": {"expanded": 0, "absorbed": 0},
                        "form": ""}
        # A pattern of power retention layers: the sequences its decode
        # steps moved the state of, its prefills by form ("attention": a
        # whole prompt, every output from the rows' own keys; "chunked":
        # from a checkpoint, the state's part beside them), and the
        # checkpoint boundaries the admitted prompts passed and kept.
        self._retention = {"rows_stepped": 0, "step_rows_stepped": 0,
                           "prefills": {"attention": 0, "chunked": 0},
                           "form": "", "boundaries_passed": 0,
                           "boundaries_kept": 0}
        page, kv_shd = self.page, self._kv_shd
        # The decode step stays a lambda ON PURPOSE: the benchmark's
        # `decode_tick` and `decode_roofline` readers pick it out of a
        # trace as the most-run `jit__lambda`, and with every other engine
        # program a named function (`jit_prefill`, `jit_suffix_prefill`,
        # `jit_sp_prefill`, `jit_sp_suffix_prefill`, `jit_install_kv`) it
        # is the ONLY `jit__lambda` of a serving trace.  It becomes
        # `decode_step` when a benchmark PR points the readers at that
        # name (ROADMAP).
        self._decode_jit = jax.jit(
            lambda p, pk, pv, state, update: _decode_fn(
                p, pk, pv, state, update, cfg, page, kv_shd),
            donate_argnums=(1, 2, 3))

        def install_kv(pk, pv, ks, vs, pages):
            return _install_fn(pk, pv, ks, vs, pages, page, kv_shd)
        self._install_jit = jax.jit(install_kv, donate_argnums=(0, 1))

        self._install_state_jit = jax.jit(_install_state_fn,
                                          donate_argnums=(0, 1))
        self._trace_jit = None          # `trace_logits` builds it

        # Chunked in-pool prefill: chunk size is a page multiple so every
        # chunk boundary is a page boundary (the suffix path requires a
        # page-aligned resident prefix).
        if prefill_chunk:
            c = max(self.page, int(prefill_chunk))
            self.prefill_chunk: Optional[int] = c - (c % self.page)
        else:
            self.prefill_chunk = None
        self._prefilling: Dict[int, _Request] = {}

        # Streamed cross-host KV (paged requests + pool-free prefill).
        from .sequence_parallel import StreamAttn
        self._stream_attn = StreamAttn(cfg)
        self._kv_window = _KVWindow(kv_gather_window,
                                    kv_fetch or _default_kv_fetch,
                                    kv_prefetch)
        self._part_seq = 0

        def _tail_gather(pk, pv, li, pages):
            return jax.tree.map(lambda pool: head_rows(
                pool[li][pages], kvh, d).reshape(-1, kvh, d), (pk, pv))
        self._tail_gather_jit = jax.jit(_tail_gather)

        def _append_tail(pk, pv, ks, vs, page_id, off):
            pools = jax.tree.map(
                lambda pool, r: pool.at[:, page_id, off].set(
                    pool_rows(r, kvh, d)), (pk, pv), (ks, vs))
            if kv_shd is not None:
                pools = jax.lax.with_sharding_constraint(pools, kv_shd)
            return pools
        self._append_tail_jit = jax.jit(_append_tail,
                                        donate_argnums=(0, 1))

    # ------------------------------------------------------------ requests --
    def _dense_only(self, what: str) -> None:
        if self.cfg.latent:
            raise ValueError(
                f"{what}: a shipped or streamed cache is a K/V pair, and a "
                "latent pattern caches one row a token in one pool")
        if self.cfg.pattern:
            raise ValueError(
                f"{what}: keys and values shipped or streamed from elsewhere "
                "are not the whole cache of a pattern with other layer kinds")

    def _pages_needed(self, req: _Request) -> int:
        if self._pk is None:
            return 0                    # no layer attends: nothing to hold
        if req.kv_paged:
            # External context: only the decode tail lives in the pool.
            return math.ceil((req.params.max_tokens + 1) / self.page)
        budget = len(req.prompt) + req.params.max_tokens + 1
        return math.ceil(min(budget, self.max_len) / self.page)

    def add_request(self, prompt_tokens: Sequence[int],
                    params: Optional[SamplingParams] = None, *,
                    no_cache: bool = False) -> int:
        if len(prompt_tokens) >= self.max_len:
            raise ValueError(
                f"prompt ({len(prompt_tokens)}) >= max_len ({self.max_len})")
        req = _Request(self._next_id, list(prompt_tokens),
                       params or SamplingParams())
        req.no_cache = no_cache
        need = self._pages_needed(req)
        if need > self.n_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.n_pages - 1} — raise kv_pages or lower max_tokens")
        self._next_id += 1
        self._requests[req.req_id] = req
        self._waiting.append(req)
        return req.req_id

    def add_external_request(self, kv_blob: dict, first_token: int,
                             params: Optional[SamplingParams] = None, *,
                             prompt_tokens: Optional[Sequence[int]] = None
                             ) -> int:
        """Queue a request whose prefill ran elsewhere (the P/D decode
        half): the shipped KV blob installs at admission time, through
        the SAME admission queue, page accounting and — when the real
        prompt tokens are supplied — prefix cache as locally-prefilled
        requests, so deadline expiry, pool pressure and cancellation
        behave identically."""
        self._dense_only("add_external_request")
        params = params or SamplingParams()
        S = int(kv_blob["len"])
        if S >= self.max_len:
            raise ValueError(f"prompt ({S}) >= max_len ({self.max_len})")
        prompt = (list(prompt_tokens) if prompt_tokens is not None
                  else [0] * S)
        if len(prompt) != S:
            raise ValueError(
                f"prompt_tokens length ({len(prompt)}) != kv blob length "
                f"({S})")
        req = _Request(self._next_id, prompt, params)
        req.no_cache = prompt_tokens is None
        req.kv_blob = kv_blob
        req.first_token = int(first_token)
        need = self._pages_needed(req)
        if need > self.n_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.n_pages - 1} — raise kv_pages or lower max_tokens")
        self._next_id += 1
        self._requests[req.req_id] = req
        self._waiting.append(req)
        return req.req_id

    def _norm_parts(self, parts, length: int, tag: str) -> List[dict]:
        """Validate + key a part list: contiguous spans covering
        [0, length), each entry {"span": (s, e), "handle": ...}."""
        pos = 0
        norm = []
        for i, part in enumerate(parts):
            s, e = part["span"]
            if s != pos or e <= s:
                raise ValueError(
                    f"KV parts must tile the context contiguously: part "
                    f"{i} spans [{s}, {e}) but {pos} tokens are covered")
            pos = e
            handle = part["handle"]
            key = part.get("key")
            if key is None:
                hx = getattr(handle, "hex", None)
                key = hx() if callable(hx) else f"{tag}:{i}"
            norm.append({"span": (int(s), int(e)), "handle": handle,
                         "key": key})
        if pos != length:
            raise ValueError(
                f"KV parts cover {pos} tokens, context is {length}")
        return norm

    def add_paged_request(self, parts, length: int, first_token: int,
                          params: Optional[SamplingParams] = None, *,
                          prompt_tokens: Optional[Sequence[int]] = None
                          ) -> int:
        """Queue a request whose prompt KV lives in external PARTS —
        (L, span, KV, D) stripes resident in arbitrary arenas (local
        dicts, or refs into REMOTE nodes' arenas published through the
        replica directory) — instead of this engine's pool.  This is the
        page-table location tier: only the decode tail occupies local
        pages, so the servable context length is bounded by the parts,
        not by max_len or this node's pool (the point of cross-host KV).
        Decode streams attention over the parts through the bounded
        gather window; a part whose host is lost mid-decode fails THIS
        request typed (KVGatherError → StreamBrokenError upstream),
        never emitting a wrong token."""
        self._dense_only("add_paged_request")
        params = params or SamplingParams()
        S = int(length)
        req = _Request(self._next_id,
                       list(prompt_tokens) if prompt_tokens else [],
                       params)
        req.kv_paged = True
        req.no_cache = True
        req.ext_len = S
        req.first_token = int(first_token)
        req.ext_parts = self._norm_parts(parts, S, f"req{req.req_id}")
        need = self._pages_needed(req)
        if need > min(self.pages_per_slot, self.n_pages - 1):
            raise ValueError(
                f"decode tail needs {need} KV pages but a slot holds "
                f"{self.pages_per_slot} and the pool {self.n_pages - 1} "
                f"— lower max_tokens or raise kv_pages/max_len")
        self._next_id += 1
        self._requests[req.req_id] = req
        self._waiting.append(req)
        return req.req_id

    def cancel_request(self, req_id: int) -> bool:
        """Retire a request mid-flight (client disconnect, deadline
        expiry): its pages return to the pool IMMEDIATELY — mid-decode,
        not at end of batch.  True if the request was live."""
        req = self._requests.get(req_id)
        if req is None:
            return False
        req.finished = True
        req.finish_reason = req.finish_reason or "cancelled"
        if req.slot >= 0 and self._slots.get(req.slot) is req:
            self._retire(req.slot)
        elif req.slot >= 0 and self._prefilling.get(req.slot) is req:
            del self._prefilling[req.slot]
            self._free_slot(req)
        else:
            try:
                self._waiting.remove(req)
            except ValueError:
                pass
            self._requests.pop(req_id, None)
        return True

    def take_tick_events(self) -> List[Tuple[int, int, bool]]:
        """(req_id, token, finished) tuples emitted by the last step() —
        admission first-tokens and decode tokens, in emission order.
        The serving layer drains these to fan tokens out to per-request
        streams."""
        ev = self._tick_events
        self._tick_events = []
        return ev

    def has_unfinished(self) -> bool:
        return bool(self._waiting or self._slots or self._prefilling)

    def kv_pages_free(self) -> int:
        return len(self._free_pages)

    @property
    def kv_pages_total(self) -> int:
        return self.n_pages - 1

    def kv_page_occupancy(self) -> float:
        if self.n_pages == 1:
            return 0.0                  # no pool
        return 1.0 - len(self._free_pages) / (self.n_pages - 1)

    @property
    def queue_depth(self) -> int:
        return len(self._waiting)

    @property
    def active_requests(self) -> int:
        return len(self._slots) + len(self._prefilling)

    def kv_gather_stats(self) -> Dict[str, Any]:
        """Remote-part gather counters (bytes, fetches, refetches,
        blocking wait) — exported as node-labeled gauges by the serving
        layer; `refetches` > 0 means the gather window is smaller than a
        live request's part count (counted, never silent)."""
        return self._kv_window.stats()

    def decode_stats(self) -> Dict[str, Any]:
        """What the batch decode step read: pages the active slots held
        (`lengths // page + 1` each) beside the pages their tables address,
        over all steps and in the last one, the attention path, and how
        the pool holds a token's row (`pool_row`: "heads" or "lanes").  And
        what the host wrote into the step's resident state: `state_syncs`
        counts the steps before which it wrote slot rows (one packed
        upload), `state_rows` the rows (`step_state_rows`: the last
        step's); `steps - state_syncs` steps uploaded nothing.
        `steps_queued` of the `steps` left for the device while the step
        before them was still unread (`_next_batch_if_queued`).  A step
        counts when it is read, for the rows that were still live then."""
        z = self.cfg.latent
        pooled = self._pk is not None
        per_step = self.max_batch * self.pages_per_slot if pooled else 0
        return {"path": decode_path(
                    (self.cfg.num_heads, self.cfg.head_dim_), self._pk.shape,
                    self._tables.shape, z.rank if z else 0)
                if pooled else "none",
                "pool_row": pool_row(*self.cfg.cache_row)
                if pooled else "none",
                "steps": self._decode_steps,
                "steps_queued": self._steps_queued,
                "pages_read": self._pages_read,
                "pages_addressable": self._decode_steps * per_step,
                "step_pages_read": self._step_pages_read,
                "step_pages_addressable": per_step,
                "state_syncs": self._state_syncs,
                "state_rows": self._state_rows,
                "step_state_rows": self._step_state_rows}

    def state_stats(self) -> Dict[str, Any]:
        """A model with recurrent layers: the checkpoint rows in use and in
        all, checkpoints kept and evicted, the prompt tokens recomputed
        behind a checkpoint beside the prompt tokens of the requests that
        hit, and the bytes of one row and of one slot's state."""
        if not self._every:
            return {"enabled": False}
        c = self._cache
        out = {"enabled": True, "every": self._every,
               "row_bytes": state_bytes(self.cfg),
               "slots": self.max_batch, "rows_total": 0, "rows_in_use": 0}
        if c is not None:
            out.update(rows_total=c.n_rows,
                       rows_in_use=c.n_rows - len(c.free_rows),
                       checkpoints_kept=c.rows_kept,
                       checkpoints_evicted=c.rows_evicted,
                       tokens_recomputed=c.recomputed,
                       hit_prompt_tokens=c.hit_tokens)
        return out

    def latent_stats(self) -> Dict[str, Any]:
        """A pattern of latent layers: the bytes of one token's cache row in
        one layer (its real values; `pool_row_bytes` as the pool pads it)
        and the pool's row form, the cache rows the decode steps read (a
        slot's live tokens, summed over the slots; in every latent layer
        alike), and the key rows the prefills attended, cached and new,
        with how many of them were up-projected to per-head keys and values
        and which form the last prefill took."""
        z = self.cfg.latent
        if not z:
            return {"enabled": False}
        act = jnp.dtype(self.cfg.dtype).itemsize
        return {"enabled": True, "layers": self.cfg.count("L"),
                "row_bytes": z.row * act,
                "pool_row_bytes": self._pk.shape[-1] * act,
                "pool_row": pool_row(*self.cfg.cache_row),
                "steps": self._decode_steps, **self._latent,
                "prefills": dict(self._latent["prefills"])}

    def retention_stats(self) -> Dict[str, Any]:
        """A pattern of power retention layers: the bytes of one sequence's
        state over all of them (`row_bytes`: a state row, and a checkpoint
        row) and phi's `block` and width `D`; the sequences the decode
        steps read and wrote the state of (`rows_stepped` over `steps`, and
        the last step's) and by which `path`; the prefills by form and the
        last one's; the checkpoint boundaries the admitted prompts passed
        and how many of them were kept (`_PrefixCache.boundaries`)."""
        z = self.cfg.retention
        if not z:
            return {"enabled": False}
        return {"enabled": True, "layers": self.cfg.count("P"),
                "row_bytes": state_bytes(self.cfg), "block": z.block,
                "D": z.expanded, "path": retention.step_path(z),
                "keep": self._keep, "steps": self._decode_steps,
                **self._retention,
                "prefills": dict(self._retention["prefills"])}

    def routed_stats(self) -> Dict[str, Any]:
        """What the routed layers' DECODE steps touched, a number a layer:
        distinct held experts that got a row and (token, expert) rows
        computed, summed over `steps` decode steps and in the last one.
        A step reads the weights of the experts it touched and no others."""
        if not len(self._routed):
            return {"enabled": False}
        r = self.cfg.routed
        return {"enabled": True, "steps": self._decode_steps,
                "held": r.held, "experts": r.experts, "top_k": r.top_k,
                "touched": self._routed[:, 0].tolist(),
                "rows": self._routed[:, 1].tolist(),
                "step_touched": self._step_routed[:, 0].tolist(),
                "step_rows": self._step_routed[:, 1].tolist()}

    def prefill_stats(self) -> Dict[str, Any]:
        """The attention form of the last prefill (`path`: "kernel" or
        "xla"), how many prefills took each, the key blocks they ran
        beside the blocks of the dense S x S form (`kv_blocks_dense`), and
        the row blocks a layer's row-wise halves ran beside those of the
        padded bucket (`row_blocks_dense`; the same below 2,048 padded
        rows), a dense decoder's and a pattern's alike."""
        return dict(self._prefill_stats)

    def _count_prefill(self, rows: int, padded: int,
                       prefix_len: Optional[int] = None) -> None:
        """Host-side count of one prefill of `rows` real rows in a
        `padded` bucket (`prefix_len` given: the suffix form), from the
        shapes alone, by the rule the programs themselves go by
        (`models/transformer.py:row_blocks`; a sequence-parallel prefill
        gives its halves no length): nothing is read back."""
        from ..ops.prefill_attention import kv_blocks
        row = () if prefix_len is None or self._pk is None \
            else (self.page, self.pages_per_slot)
        path = _prefill_path(self.cfg, padded, self._kv_shd, *row) \
            if self.sp_degree == 1 else "xla"
        table = math.prod(row) if row else 0    # cached rows a suffix sees
        run, dense = kv_blocks(rows, padded, prefix_len or 0, table)
        rows_run, rows_dense = row_blocks(
            rows if self.sp_degree == 1 else None, padded, every=self._every)
        self._prefill_ran = {"path": path,
                             "kv_blocks": run if path == "kernel" else dense,
                             "row_blocks": rows_run}
        if self.cfg.latent:
            # The form, by the rule the program went by; real rows.
            form = latent_form(table)
            attended = (prefix_len or 0) + rows
            expanded = attended if form == "expanded" else 0
            self._prefill_ran.update(form=form, expanded=expanded)
            lat = self._latent
            lat["form"] = form
            lat["prefills"][form] += 1
            lat["rows_attended"] += attended
            lat["rows_expanded"] += expanded
        if self.cfg.retention:
            # The form, by the host's mirror of the rule `retention.mixer`
            # goes by (the state's part is added where the state has read
            # anything, which on the device is `any(z != 0)` and is not
            # read back): a prefill from a checkpoint starts from such a
            # state, a whole prompt from the zero row.
            form = "chunked" if prefix_len else "attention"
            self._prefill_ran.update(form=form)
            self._retention["form"] = form
            self._retention["prefills"][form] += 1
        st = self._prefill_stats
        if self._pk is None:            # no layer attends: no attention form
            path, dense = "none", 0
            self._prefill_ran.update(path=path, kv_blocks=0)
        else:
            st[path + "_calls"] += 1
        st["path"] = path
        st["kv_blocks_run"] += self._prefill_ran["kv_blocks"]
        st["kv_blocks_dense"] += dense
        st["row_blocks_run"] += rows_run
        st["row_blocks_dense"] += rows_dense

    def prefix_cache_stats(self) -> Dict[str, Any]:
        if self._cache is None:
            return {"enabled": False}
        out = {"enabled": True, "entries": len(self._cache._entries),
               "hits": self._cache.hits, "misses": self._cache.misses,
               "hit_pages": self._cache.hit_pages,
               "evictions": self._cache.evictions,
               "allocated_pages": len(self._page_refs),
               "free_pages": len(self._free_pages),
               "ballast_pages": len(self._ballast_pages)}
        if self._demote is not None:
            out.update(self._demote.stats())
        return out

    # ---------------------------------------------------------------- step --
    def _bucket(self, n: int) -> int:
        # Floor at sp_degree (both pow-2): a short prompt's bucket must
        # still split over every sequence-parallel shard.
        b = max(_MIN_STATE_ROWS if self._every else 8, self.sp_degree)
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _run_prefill(self, prompt: Sequence[int]):
        """Bucketed, jit-cached prefill shared by admission and the P/D
        prefill half; returns (last_logits, ks, vs).  With sp_degree > 1
        dispatches to the sequence-parallel path (ring/Ulysses over the
        mesh's sp axis) — exact parity with the single-device kernel."""
        if self.cfg.pattern:
            return self._run_suffix(
                prompt, 0, np.zeros(self.pages_per_slot, np.int32))
        S = len(prompt)
        Sb = self._bucket(S)
        key = ("sp", Sb) if self.sp_degree > 1 else Sb
        if key not in self._prefill_jit:
            cfg = self.cfg
            if self.sp_degree > 1:
                mesh, strat = self.mesh, self.sp_strategy

                def sp_prefill(p, t, n):
                    return self._sp.sp_prefill_fn(p, t, n, cfg, mesh, strat)
                self._prefill_jit[key] = jax.jit(sp_prefill)
            else:
                kv_shd = self._kv_shd

                def prefill(p, t, n):
                    return _prefill_fn(p, t, n, cfg, kv_shd)
                self._prefill_jit[key] = jax.jit(prefill)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :S] = prompt
        self._count_prefill(S, Sb)
        return self._prefill_jit[key](self.params, jnp.asarray(toks), S)

    # ------------------------------------------------------ page refcounts --
    def _alloc_page(self) -> int:
        p = self._free_pages.pop(0)
        self._page_refs[p] = 1
        return p

    def _incref(self, p: int) -> None:
        self._page_refs[p] += 1

    def _decref(self, p: int) -> None:
        n = self._page_refs[p] - 1
        if n > 0:
            self._page_refs[p] = n
        else:
            del self._page_refs[p]
            self._free_pages.append(p)

    # ------------------------------------------------------- KV offload --
    def _demote_entry(self, key: bytes, pages: Sequence[int]) -> None:
        """Prefix-cache eviction hook: copy the evicted pages' contents
        device -> host into the demote store BEFORE the refs drop (after
        decref the pages rejoin the free list and any admission may
        overwrite them)."""
        idx = jnp.asarray(np.asarray(pages, np.int32))
        heads = (self.cfg.num_kv_heads, self.cfg.head_dim_)
        kk = np.asarray(head_rows(self._pk[:, idx], *heads))
        vv = np.asarray(head_rows(self._pv[:, idx], *heads))
        self._demote.put(key, kk, vv, len(pages))

    def _try_promote(self, req: _Request, c: int, shared: List[int],
                     total: int) -> Tuple[int, List[int]]:
        """Promote the longest demoted prefix usable by this prompt back
        into the pool, superseding any (shorter) resident hit.  Only
        fires when the pool can hold the promoted pages AND the
        request's remainder (`total` pages all told) — promotion must
        never starve the admission it serves.  Returns the possibly-
        updated (prefix_tokens, shared_pages)."""
        usable = (len(req.prompt) - 1) // self.page
        have = len(shared)
        if usable <= have:
            return c, shared
        keys = self._cache._keys(req.prompt, usable)
        for k in range(usable, have, -1):
            key = keys[k - 1]
            if not self._demote.contains(key):
                continue
            if len(self._free_pages) < total:
                break               # no headroom: admit on what we have
            part = self._demote.get(key)
            if part is None or int(part["len"]) != k:
                continue
            L, KV, D = (part["k"].shape[0], part["k"].shape[-2],
                        part["k"].shape[-1])
            kk = jnp.asarray(part["k"].reshape(L, k * self.page, KV, D),
                             self.cfg.dtype)
            vv = jnp.asarray(part["v"].reshape(L, k * self.page, KV, D),
                             self.cfg.dtype)
            new_pages = [self._alloc_page() for _ in range(k)]
            self._install_pages(new_pages, kk, vv)
            # Re-register under the same rolling-hash key: the alloc ref
            # is the cache's membership hold; the request holds one more
            # (exactly the lookup-hit refcount shape in _reserve).
            self._cache._entries[key] = [int(p) for p in new_pages]
            for p in new_pages:
                self._incref(p)
            for p in shared:
                self._decref(p)     # superseded shorter-prefix hold
            # The lookup above scored this admission a miss (or a
            # shorter hit) before the demoted tier resolved it: reclass
            # — the request's prefill IS skipped, same as a pool hit.
            if have == 0:
                self._cache.misses -= 1
                self._cache.hits += 1
            self._cache.hit_pages += k - have
            return k * self.page, new_pages
        return c, shared

    def apply_pool_pressure(self, frac: float) -> None:
        """Shrink (frac < 1) or restore (frac = 1) the usable page pool
        by parking free pages on a ballast list — the mem_chaos pool
        squeeze (and any external memory-pressure controller) drives
        this.  Admission then sees a smaller free list, evicts the
        prefix cache sooner, and the demotion path absorbs the evicted
        pages instead of discarding them.  Pages already allocated are
        never touched: the squeeze throttles NEW admissions only."""
        frac = min(1.0, max(0.0, float(frac)))
        parked_target = (self.n_pages - 1) - max(
            0, int((self.n_pages - 1) * frac))
        while len(self._ballast_pages) < parked_target and self._free_pages:
            self._ballast_pages.append(self._free_pages.pop())
        while len(self._ballast_pages) > parked_target:
            self._free_pages.append(self._ballast_pages.pop())

    def _report_pool_pressure(self) -> None:
        """Feed the node-shared PressureSignal: the KV pool is under
        pressure only when admission is actually blocked on pages (a
        hot pool with an empty queue is healthy, not pressured)."""
        try:
            from .._private.memory_monitor import pressure_signal
            sig = pressure_signal()
            total = max(1, self.n_pages - 1)
            if self._waiting and not self._free_pages and self.n_pages > 1:
                sig.report("kv_pool", 1.0 - len(self._free_pages) / total)
            else:
                sig.clear("kv_pool")
        except Exception:
            pass

    def _reserve(self, req: _Request) -> bool:
        """Reserve slot + pages for a request; False = wait for capacity.
        With the prefix cache on, shared prefix pages are reused
        (ref-counted, never re-allocated) and LRU entries are evicted
        under pool pressure before giving up."""
        if not self._free_slots:
            return False
        c, shared, marks = 0, [], []
        caching = self._cache is not None and not req.no_cache
        if caching:
            before = self._cache.recomputed
            c, shared, req.from_row = self._cache.lookup(req.prompt)
            req.recomputed = self._cache.recomputed - before
            marks = self._cache.boundaries(req.prompt, c, self._keep)
        total = self._pages_needed(req)
        need = total - len(shared)
        # Hold the shared pages, and the checkpoint row the prefill starts
        # from, before any eviction can touch them.
        for p in shared:
            self._incref(p)
        if caching:
            self._cache.hold_row(req.from_row)
        demote = self._demote_entry if self._demote is not None else None
        def short():                # of pages, or of rows for `marks`
            return len(self._free_pages) < need or (
                marks and len(self._cache.free_rows) < len(marks))
        while short() and self._cache is not None \
                and self._cache.evict_lru(self._decref, demote):
            pass
        if len(self._free_pages) < need:
            for p in shared:
                self._decref(p)
            if caching:
                self._cache.hold_row(req.from_row, -1)
            return False
        # Rows for the checkpoints this prefill passes; one that finds none
        # free is not kept.
        req.new_rows = {b: self._cache.free_rows.pop()
                        for b in marks if self._cache.free_rows}
        if self._demote is not None and not req.no_cache \
                and not req.kv_paged and len(self._demote):
            c, shared = self._try_promote(req, c, shared, total)
            need = total - len(shared)
        req.slot = self._free_slots.pop(0)
        req.pages = [self._alloc_page() for _ in range(need)]
        req.shared_pages = shared
        req.prefix_len = c
        row = np.zeros(self.pages_per_slot, np.int32)
        row[:len(shared)] = shared
        row[len(shared):total] = req.pages
        self._tables[req.slot] = row
        self._touched[req.slot] = True
        return True

    def _install(self, slot: int, ks, vs):
        pages = jnp.asarray(self._tables[slot])
        self._pk, self._pv = self._install_jit(
            self._pk, self._pv, ks, vs, pages)

    def _install_pages(self, page_ids: Sequence[int], ks, vs):
        """Install KV into specific pool pages (ks/vs start page-aligned
        on page_ids[0]; trailing scratch-page writes are masked reads by
        contract, same as _install)."""
        pages = np.zeros(self.pages_per_slot, np.int32)
        pages[:len(page_ids)] = page_ids
        self._pk, self._pv = self._install_jit(
            self._pk, self._pv, ks, vs, jnp.asarray(pages))

    def _install_state(self, req: _Request, end, kept) -> None:
        """A prefill's recurrent state into the request's slot, and the
        checkpoints it passed into the rows reserved for them
        (`_reserve`); the prefill's bucket may hold boundaries past the
        prompt, which go to the scratch row."""
        rows = np.ones(jax.tree.leaves(kept[0])[0].shape[1], np.int32)
        for b, row in req.new_rows.items():
            # (Boundary j of the prefill lies in slot (j - 1) % slots: a kind
            # that builds only the last `_keep` has as many slots.)
            rows[((b - req.prefix_len) // self._every - 1) % len(rows)] = row
        self._dev["rec"], self._ckpt = self._install_state_jit(
            self._dev["rec"], self._ckpt, req.slot, end, kept,
            jnp.asarray(rows))

    def _install_new_pages(self, req: _Request, ks, vs):
        """Install suffix KV into the request's NEWLY reserved pages (the
        suffix starts page-aligned at prefix_len, so it maps exactly onto
        them; the shared prefix pages are already resident and are never
        written)."""
        self._install_pages(req.pages, ks, vs)

    def _run_suffix(self, prompt: Sequence[int], prefix_len: int,
                    pages_row, upto: Optional[int] = None, from_row: int = 0):
        """Jit-cached suffix prefill against resident prefix pages.
        `upto` bounds the suffix (chunked prefill: one chunk per call).
        With sp_degree > 1 the suffix attention runs sequence-parallel
        (ring over the suffix KV, accumulator seeded by the resident
        prefix) so prefix-cache hits keep their compute skip under SP.
        A pattern of kinds runs every prefill through here, a whole prompt
        as the suffix of nothing, from checkpoint row `from_row`
        (`_state_prefill_fn`: two results more, the state and its
        checkpoints)."""
        suf = prompt[prefix_len:upto]
        S = len(suf)
        Sb = self._bucket(S)
        sp = self.sp_degree > 1
        key = ("sp-suffix", Sb) if sp else ("suffix", Sb)
        after = prefix_len              # what `_count_prefill` is told
        if self.cfg.latent and not prefix_len:
            # A whole prompt attends nothing cached, and is given no pages
            # to gather: another program (and, `latent_form`, the expanded
            # attention).
            key, pages_row, after = ("whole", Sb), None, None
        if self._pk is None:
            pages_row = None            # no pool: one program a bucket
        if key not in self._prefill_jit:
            cfg, page = self.cfg, self.page
            if cfg.pattern:
                every, keep = self._every, self._keep

                def state_prefill(p, pk, pv, pg, t, pl, n, ckpt, row):
                    return _state_prefill_fn(p, pk, pv, pg, t, pl, n, ckpt,
                                             row, cfg, page, every,
                                             keep=keep)
                self._prefill_jit[key] = jax.jit(state_prefill)
            elif sp:
                mesh = self.mesh

                def sp_suffix_prefill(p, pk, pv, pg, t, pl, n):
                    return self._sp.sp_suffix_prefill_fn(
                        p, pk, pv, pg, t, pl, n, cfg, page, mesh)
                self._prefill_jit[key] = jax.jit(sp_suffix_prefill)
            else:
                kv_shd = self._kv_shd

                def suffix_prefill(p, pk, pv, pg, t, pl, n):
                    return _suffix_prefill_fn(
                        p, pk, pv, pg, t, pl, n, cfg, page, kv_shd)
                self._prefill_jit[key] = jax.jit(suffix_prefill)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :S] = suf
        self._count_prefill(S, Sb, after)
        state = (self._ckpt, from_row) if self.cfg.pattern else ()
        return self._prefill_jit[key](
            self.params, self._pk, self._pv,
            None if pages_row is None else jnp.asarray(pages_row),
            jnp.asarray(toks), prefix_len, S, *state)

    def _prefill_slot(self, req: _Request):
        """Run a reserved request's prefill and install what it leaves:
        keys and values into its pages, a pattern's recurrent state into
        its slot and the checkpoints it passed into their rows.  Returns
        (last-token logits, the experts every row chose or None)."""
        if not (req.prefix_len or self.cfg.pattern):
            logits, ks, vs = self._run_prefill(req.prompt)
            self._install(req.slot, ks, vs)
            return logits, None
        logits, ks, vs, *state = self._run_suffix(
            req.prompt, req.prefix_len, self._tables[req.slot],
            from_row=req.from_row)
        if self._pk is not None:
            self._install_new_pages(req, ks, vs)
        if self._every:
            self._install_state(req, *state[:2])
        return logits, state[2] if state else None

    def _admit(self) -> int:
        """Admit what fits, in order of arrival; returns how many requests
        took a slot.  A tick admits the first that waits and then others
        while their prompts, cached or not, come to no more than `max_len`
        tokens in all, what one slot holds: the running sequences stand
        still for an admission's host work (a prompt's page keys, looked up
        and inserted: 9 ms for 6k tokens) and its prefill, so a tick's stop
        is bounded by one longest prompt's, and two long prompts that came
        in one tick leave a tick apart and do not come back together (a
        closed loop's callers, welded for a whole run by a millisecond of
        the ramp: PERF.md §6, PR 44)."""
        ph = self.phases
        admitted = []
        taken = 0
        room = self.max_len
        while self._waiting \
                and (not taken or len(self._waiting[0].prompt) <= room) \
                and self._reserve(self._waiting[0]):
            req = self._waiting.pop(0)
            taken += 1
            room -= len(req.prompt)
            if req.kv_paged:
                # External paged context: nothing to prefill — the
                # parts stay wherever they live (possibly remote); the
                # reserved pages are the decode tail.
                self._activate(req, 0)
                self._emit_first(req, req.first_token)
                continue
            S = len(req.prompt)
            if self.prefill_chunk and req.kv_blob is None \
                    and S - req.prefix_len > self.prefill_chunk:
                # Chunked prefill: advance per tick (in step()), so one
                # huge prompt neither compiles a giant bucket nor
                # starves the continuous-batching tick.
                req.prefilled = req.prefix_len
                self._prefilling[req.slot] = req
                continue
            active_before = len(self._slots)
            programs = len(self._prefill_jit)
            t0 = ph.enter("prefill")
            if req.kv_blob is not None:
                self._install_external(req)
            else:
                logits, _ = self._prefill_slot(req)
            ran = {} if req.kv_blob is not None else self._prefill_ran
            if self._every:
                ran = dict(ran, checkpoints=len(req.new_rows),
                           recomputed=req.recomputed)
            if self.cfg.retention:
                passed = (S - req.prefix_len) // self._every
                ran = dict(ran, kept=len(req.new_rows), passed=passed)
                self._retention["boundaries_passed"] += passed
                self._retention["boundaries_kept"] += len(req.new_rows)
            ph.leave(t0, "prefill", req.req_id.to_bytes(8, "little"),
                     tokens=S, cached_tokens=req.prefix_len,
                     active=active_before, n=ph.n,
                     new_program=len(self._prefill_jit) - programs, **ran)
            if self._cache is not None and not req.no_cache:
                self._cache.hold_row(req.from_row, -1)
                self._cache.insert(
                    req.prompt,
                    self._tables[req.slot] if self._pk is not None else None,
                    self._incref, req.new_rows)
            if self.sp_degree > 1:
                # Which pages each sequence-parallel shard installed —
                # the stripe accounting the cross-host handoff consumes.
                # Shard boundaries follow the kernel's PADDED bucket; a
                # prefix-cache hit stripes only the suffix's new pages
                # (the shared prefix was not computed by any shard).
                if req.prefix_len:
                    suf = S - req.prefix_len
                    req.sp_stripes = self._sp.sp_stripe_pages(
                        req.pages, suf, self.sp_degree, self.page,
                        padded=self._bucket(suf))
                else:
                    req.sp_stripes = self._sp.sp_stripe_pages(
                        self._tables[req.slot], S, self.sp_degree,
                        self.page, padded=self._bucket(S))
            self._activate(req, S)
            if req.kv_blob is not None:
                req.kv_blob = None          # release the host copy
                self._emit_first(req, req.first_token)
            else:
                admitted.append((req, logits))
        if admitted:
            firsts = self._sample_batch([lg for _, lg in admitted],
                                        [r.params for r, _ in admitted])
            for (req, _), first in zip(admitted, firsts):
                self._emit_first(req, first)
        self._report_pool_pressure()
        return taken

    def _activate(self, req: _Request, length: int) -> None:
        """The reserved slot joins the running set with `length` tokens in
        cache: the decode step's device state takes its row before the
        next step."""
        slot = req.slot
        self._lengths[slot] = length
        self._temps[slot] = req.params.temperature
        self._slots[slot] = req
        self._touched[slot] = True

    def _emit_first(self, req: _Request, token: int) -> None:
        """A request's first token, which no decode step produced: the
        next one starts from it."""
        self._last[req.slot] = token
        self._touched[req.slot] = True
        self._emit(req, int(token))

    def _install_external(self, req: _Request):
        """Install a shipped KV blob; on a prefix-cache hit only the
        suffix pages are written (the shared span is already resident)."""
        blob = req.kv_blob
        ks = jnp.asarray(blob["k"], self.cfg.dtype)
        vs = jnp.asarray(blob["v"], self.cfg.dtype)
        if req.prefix_len:
            self._install_new_pages(req, ks[:, req.prefix_len:],
                                    vs[:, req.prefix_len:])
        else:
            self._install(req.slot, ks, vs)

    def _sample_batch(self, logits_list, params_list) -> List[int]:
        """Sample first tokens for a whole admission wave in ONE
        device->host transfer (the previous per-request host pull was a
        blocking sync per request per tick); the sync cost is stamped as
        a `sample_sync` recorder span so the serving harness sees it."""
        t0 = self.phases.enter("sample_sync")
        lg = jnp.stack(logits_list)                       # (N, V) f32
        temps = np.asarray([p.temperature for p in params_list],
                           np.float32)
        greedy = jnp.argmax(lg, -1).astype(jnp.int32)
        if (temps > 0).any():
            # The one key stream, shared with the decode step, which
            # splits it on the device: this split's first half goes back
            # into the resident state.
            self._dev["rng"], key = jax.random.split(self._dev["rng"])
            keys = jax.random.split(key, len(params_list))
            tj = jnp.asarray(temps)
            sampled = jax.vmap(
                lambda k, l, t: jax.random.categorical(
                    k, l / jnp.maximum(t, 1e-6)))(keys, lg, tj)
            toks = jnp.where(tj > 0, sampled.astype(jnp.int32), greedy)
        else:
            toks = greedy
        out = np.asarray(toks)                            # the one sync
        self.phases.leave(t0, "sample_sync", batch=len(params_list))
        return [int(t) for t in out]

    def _sample_host(self, logits, params: SamplingParams) -> int:
        return self._sample_batch([logits], [params])[0]

    def sample_first(self, logits, params: Optional[SamplingParams] = None
                     ) -> int:
        """Sample a first token from prefill logits — the final step of a
        distributed paged prefill, where the LAST shard's chunk holds the
        prompt's real last-token logits (serve_patterns.LongContextApp)."""
        return self._sample_host(logits, params or SamplingParams())

    def _length_left(self, req: _Request) -> int:
        """The tokens `req` may still emit before it ends by length (none
        or fewer: it has ended): what the host knows of a reply's end
        without reading a token."""
        p = req.params
        if req.kv_paged:
            # Paged context: length is bounded by max_tokens and the
            # reserved decode-tail pages, never by max_len (the context
            # itself lives in external parts).
            return min(p.max_tokens - len(req.out),
                       len(req.pages) * self.page - req.ext_written - 1)
        return min(p.max_tokens,
                   self.max_len - 1 - len(req.prompt)) - len(req.out)

    def _emit(self, req: _Request, token: int):
        req.out.append(token)
        p = req.params
        if p.eos_id is not None and token == p.eos_id:
            req.finished = True
            req.finish_reason = req.finish_reason or "stop"
        elif self._length_left(req) <= 0:
            req.finished = True
            req.finish_reason = req.finish_reason or "length"
        self._tick_events.append((req.req_id, token, req.finished))

    def step(self) -> List[_Request]:
        """Admit waiting requests, advance chunked prefills by one chunk,
        run ONE decode step for all active slots (paged-context slots
        stream their attention over external parts), retire finished
        requests.  Returns requests finished in this step (vllm
        engine.step parity).

        The decode step's per-slot state is resident on the device
        (`self._dev`, see `_decode_fn`) and advanced by the step itself.
        The host's mirrors (`_tables`, `_last`, `_lengths`, `_temps`)
        remain the scheduler's truth and are advanced here from the tokens
        read back, every tick; a slot the host itself changed (reserved,
        activated, freed) is marked in `_touched`, and the marked rows
        ride to the device as ONE packed upload in the next step's `prep`.
        A step before which nothing was touched uploads nothing and runs
        no program but the decode step (`decode_stats()`).  Such a step
        needs nothing of the host, not even the tokens of the step before
        it, so host and device need not meet at every step.  Where the
        engine has an owner who can say that nobody is about to hand it
        work (`hold_ahead`):

          - a call that read its step and sees that the next one is of
            that kind sends it off before it returns
            (`_next_batch_if_ahead`), and the device runs it while the
            caller hands this step's tokens on and comes back;
          - a call that finds a step out (`_ahead`) sends the one AFTER it
            off first, if it may (`_next_batch_if_queued`), and only then
            blocks on the read-back: the device holds one step running and
            one queued, and the read-back's late return, `emit`, the
            loop's leaves and the next `prep` and `dispatch` all run under
            a busy chip.  A step that may not be queued (somebody waits,
            a slot was touched, this call will retire a reply that ends by
            length) is read first and the chain starts again at the end
            of a later call.

        A reply that ends by EOS is seen one step late: its row is still
        active in the step queued behind the one that sampled the EOS.
        That is a DEAD step for the row: it writes the row's own reserved
        page and its slot's state row and nothing else, its token is
        dropped, and the row counts in none of the step's numbers
        (`batch=`, `pages=`, `latent_rows=`, `state_rows=`; the routed
        layers' counts are the device's own and hold what it touched).  The
        host frees the slot and its pages when it reads the EOS; whatever
        is dispatched into them afterwards runs behind the dead step on
        the device, which runs in order.  A step none of whose rows is
        live any more is dropped unread and counts as no step.  A
        cancelled request's row is skipped the same way, in every step
        that was out when it went.

        What a call returns is what it returned before: one token for
        every slot of the step it read, and with greedy sampling every
        request's tokens are those of the lockstep order.  A tick's first
        tokens (`_admit`, a chunked prefill's last chunk) do not wait for
        the tick's decode step: once that step is dispatched the engine
        hands the events so far to its owner's hook (`hand_first`), on
        this thread, and `take_tick_events()` later returns the rest.

        Every instant of the call belongs to one phase of
        `tick_phases.TickPhases` (self.phases): `admit`, `chunk`, `emit`,
        then `ahead` (a step queued behind the one that is out: its `prep`
        and `dispatch`), the decode step's `prep`, `dispatch` and `wait`,
        then `emit` again (and `ahead`, where the next step leaves at the
        end of the call: the next call's `prep` and `dispatch` are then
        empty); it hands back to the replica's loop in `hop`."""
        ph = self.phases
        ph.in_step = True
        done: List[_Request] = []
        before = 0
        try:
            before = self._step(ph, done)
        finally:
            ph.in_step = False
            ph.to("hop" if ph.in_tick else None, retired=len(done) - before)
        return done

    def _step(self, ph: TickPhases, done: List[_Request]) -> int:
        """Fills `done`; returns how many of them retired before the
        decode step (the first `step:emit` piece has stamped those)."""
        self._tick_events = []
        t0 = ph.to("admit")
        admitted = self._admit()
        if admitted or self._prefilling:
            ph.admitting += 1
        if self._prefilling:
            t1 = ph.to("chunk")
            ph.span("step:admit", t0, t1, admitted=admitted)
            self._advance_prefilling()
            ph.span("step:chunk", t1, ph.to("emit"))
        else:
            ph.span("step:admit", t0, ph.to("emit"), admitted=admitted)
        # Retire requests that finished at admission (eos on first token).
        for slot, req in list(self._slots.items()):
            if req.finished:
                done.append(self._retire(slot))
        if not self._slots:
            return 0
        # Paged-context slots: one streamed-attention token each (their
        # KV spans external — possibly remote — parts; the compiled
        # batch step below cannot gather those).
        for slot, req in list(self._slots.items()):
            if not req.kv_paged or req.finished:
                continue
            try:
                tok = self._ext_decode_step(req)
            except KVGatherError as e:
                req.error = e
                req.finished = True
                req.finish_reason = "error"
                done.append(self._retire(slot))
                continue
            self._last[slot] = tok      # host only: never in the batch
            self._emit(req, tok)
            if req.finished:
                done.append(self._retire(slot))
        before = len(done)
        flight, self._ahead = self._ahead, None
        # Whom a step that is out still counts for: a row that ended by
        # eos in the step before it (a dead step) or was cancelled while
        # it was out is in none of its numbers, and a step with no such
        # row left is never read.
        live = flight and {s: r for s, r in flight.batch.items()
                           if self._slots.get(s) is r}
        if not live:
            live = {s: r for s, r in self._slots.items() if not r.kv_paged}
            if not live:
                return 0
            flight = self._dispatch_decode(ph, live, retired=before)
        else:
            # Sent off by an earlier call: nothing to prepare.  The step
            # after it leaves first, if it may.
            batch = self._next_batch_if_queued()
            if batch:
                self._ahead = self._dispatch_decode(
                    ph, batch, ahead=True, queued=True, retired=before)
                flight.t0 = ph.to("prep")
            else:
                flight.t0 = ph.to("prep", retired=before)
            ph.to("dispatch")
        if self.hand_first is not None and self._tick_events:
            # First tokens leave now, under a busy chip.
            self.hand_first(self.take_tick_events())
        ph.to("wait")
        nxt = np.asarray(flight.nxt)
        lengths = self._lengths[list(live)]
        pages = int((lengths // self.page + 1).sum()) \
            if self._pk is not None else 0
        self._decode_steps += 1
        self._steps_queued += flight.queued
        self._pages_read += pages
        self._step_pages_read = pages
        self._step_state_rows = flight.synced
        extra = {}
        if len(self._routed):
            # After the tokens, what the routed layers touched (`_decode_fn`).
            self._step_routed = nxt[self.max_batch:].reshape(-1, 2)
            self._routed += self._step_routed
            extra["experts"] = int(self._step_routed[:, 0].sum())
        if self.cfg.latent:
            rows = int((lengths + 1).sum())     # this step's token included
            self._latent["rows_read"] += rows
            self._latent["step_rows_read"] = extra["latent_rows"] = rows
        if self.cfg.retention:
            ret = self._retention
            ret["rows_stepped"] += len(live)
            ret["step_rows_stepped"] = extra["state_rows"] = len(live)
        ph.span("decode", flight.t0, ph.to("emit"), batch=len(live),
                pages=pages, synced=flight.synced,
                queued=int(flight.queued), **extra)
        # The host advances its mirrors as the step advanced the device's.
        for slot, req in live.items():
            self._lengths[slot] += 1          # the token we just attended
            tok = int(nxt[slot])
            self._last[slot] = tok
            self._emit(req, tok)
            if req.finished:
                done.append(self._retire(slot))
        if self._ahead is None:
            batch = self._next_batch_if_ahead()
            if batch:
                self._ahead = self._dispatch_decode(ph, batch, ahead=True)
                ph.to("emit")
        return before

    def _dispatch_decode(self, ph: TickPhases, batch: Dict[int, _Request],
                         ahead: bool = False, queued: bool = False,
                         **closing) -> _Flight:
        """One decode step for `batch` (slot -> request) leaves for the
        device: `prep` (the one packed upload of the rows the host
        touched, if any) and `dispatch`; both in the one leaf `ahead` for
        a step sent off before the call that will ask for it, at the end
        of a call or (`queued`) behind a step that is still unread.  What
        comes back is read in `_step`."""
        active = np.zeros(self.max_batch, bool)
        active[list(batch)] = True
        t0 = ph.to("ahead" if ahead else "prep", **closing)
        update, synced = self._no_rows, int(self._touched.sum())
        if synced:
            update = jax.device_put(_pack_rows(
                self._tables, self._last, self._lengths, active, self._temps,
                self._touched), self._state_shd)
            self._touched[:] = False
            self._state_syncs += 1
            self._state_rows += synced
        if not ahead:
            ph.to("dispatch")
        self._pk, self._pv, self._dev, nxt = self._decode_jit(
            self.params, self._pk, self._pv, self._dev, update)
        return _Flight(nxt, batch, t0, synced, queued)

    def _next_batch_if_ahead(self) -> Dict[int, _Request]:
        """Whom the NEXT decode step is for, if it may leave now, before
        the call that will ask for it, so that the device runs it while
        the host does everything else; nobody if it may not.  It may when
        the next call would dispatch exactly this step: every slot is the
        batch step's, nothing waits for admission, the engine's owner says
        that nobody is about to hand it work (`hold_ahead`), whose prefill
        would otherwise queue behind the step, and no row is touched:
        nobody retired in this call, nobody was admitted or cancelled
        since the last dispatch.  (So the host's mirrors and the device's
        rows agree but for the steps that are out, and the step uploads
        nothing.)  A caller whose answer has just ended comes back with
        its next request within the tick that follows, and that tick is
        left as long as it ever was: cut short by a step sent ahead, it
        ends a millisecond before a closed loop's request arrives about
        once in ten, the request joins a tick late, and callers laid ticks
        apart walk into each other's prefills (PERF.md §6, PR 38)."""
        if (self.hold_ahead is None or self._waiting or self._prefilling
                or self._touched.any()
                or any(r.kv_paged for r in self._slots.values())
                or self.hold_ahead()):
            return {}
        return dict(self._slots)

    def _next_batch_if_queued(self) -> Dict[int, _Request]:
        """Whom the step AFTER the one that is out is for, if it may leave
        while that one is still unread; nobody if it may not.  The rules
        are `_next_batch_if_ahead`'s, read at the start of the call (no row
        is touched, so the step that is out ran for these very slots), and
        one more, since "nobody retired in this call" is not known yet: no
        row will end by LENGTH when the step that is out is read, which
        the host knows by counting (`_length_left`).  The call that retires a reply
        so keeps PR 38's order: it reads its step with nothing behind it
        and sends none ahead, the tick after it is as long as it ever was,
        and the caller who comes back in it finds at most the one running
        step in front of its prefill.  An EOS cannot be foreseen: its row
        takes one dead step (`step`)."""
        batch = self._next_batch_if_ahead()
        if any(self._length_left(r) <= 1 for r in batch.values()):
            return {}
        return batch

    def _advance_prefilling(self) -> None:
        """Advance chunked prefills by AT MOST one chunk per tick: the
        decode tick's latency is bounded by one chunk's compile-stable
        compute, so a million-token prompt cannot starve the continuous
        batch.  The final chunk samples the first token and activates
        the slot for decode."""
        if not self._prefilling:
            return
        ph = self.phases
        for slot, req in sorted(self._prefilling.items()):
            S = len(req.prompt)
            nxt = min(req.prefilled + self.prefill_chunk, S)
            row = self._tables[slot]
            programs = len(self._prefill_jit)
            t0 = ph.enter("prefill")
            if req.prefilled == 0:
                logits, ks, vs = self._run_prefill(req.prompt[:nxt])
                self._install_pages(
                    row[:math.ceil(nxt / self.page)], ks, vs)
            else:
                logits, ks, vs = self._run_suffix(
                    req.prompt, req.prefilled, row, upto=nxt)
                self._install_pages(
                    row[req.prefilled // self.page:
                        math.ceil(nxt / self.page)], ks, vs)
            ph.leave(t0, "prefill", req.req_id.to_bytes(8, "little"),
                     tokens=nxt, cached_tokens=req.prefilled, chunked=True,
                     active=len(self._slots), n=ph.n,
                     new_program=len(self._prefill_jit) - programs,
                     **self._prefill_ran)
            req.prefilled = nxt
            if nxt >= S:
                del self._prefilling[slot]
                if self._cache is not None and not req.no_cache:
                    self._cache.insert(req.prompt, row, self._incref)
                # No sp_stripes for chunked prefills: every chunk was
                # its own SP pass with its own bucket, so a single
                # whole-prompt stripe attribution would lie; chunked
                # cross-host handoffs carry exact spans via the paged
                # parts path instead.
                self._activate(req, S)
                self._emit_first(
                    req, self._sample_batch([logits], [req.params])[0])
            break                       # one chunk per tick, total

    def _retire(self, slot: int) -> _Request:
        req = self._slots.pop(slot)
        self._free_slot(req)
        return req

    def _free_slot(self, req: _Request) -> None:
        """Return a reserved slot's pages + slot to the pool (shared by
        retirement and mid-prefill cancellation)."""
        slot = req.slot
        self._free_slots.append(slot)
        for p in req.pages:
            self._decref(p)
        for p in req.shared_pages:
            self._decref(p)
        req.pages = []
        req.shared_pages = []
        if req.ext_parts:
            self._kv_window.drop([p["key"] for p in req.ext_parts])
        self._tables[slot] = 0
        self._lengths[slot] = 0
        self._touched[slot] = True
        self._requests.pop(req.req_id, None)

    # ------------------------------------------- streamed cross-host KV ----
    def _part_layer(self, part: dict, li: int):
        """One layer's (k, v, valid_len) of an external part, through the
        gather window (a remote part's first touch this step blocks on
        the object-plane pull; prefetch usually got there first).

        The whole part uploads to device ONCE per window residency and
        is layer-sliced there — re-uploading per (token, layer) would
        re-transfer the entire resident window every decoded token.
        Device working set stays bounded by the same knob as host
        memory: O(kv_gather_window parts)."""
        data = self._kv_window.get(part["key"], part["handle"])
        kj = data.get("_kj")
        if kj is None:
            k_raw, v_raw = data["k"], data["v"]
            kj = data["_kj"] = jnp.asarray(k_raw, self.cfg.dtype)
            data["_vj"] = jnp.asarray(v_raw, self.cfg.dtype)
            if isinstance(k_raw, np.ndarray):
                # Host-resident part (legacy blob / cross-host pull that
                # landed as numpy): this upload is a transfer seam —
                # device-resident parts skip it entirely.
                from .._private import device_plane
                device_plane.record_h2d(kj.nbytes + data["_vj"].nbytes)
        valid = int(data.get("len", data["k"].shape[1]))
        return kj[li], data["_vj"][li], valid

    def _window_prefetch(self, parts) -> None:
        self._kv_window.prefetch(
            [(p["key"], p["handle"]) for p in parts])

    def _ext_decode_step(self, req: _Request) -> int:
        """One decode token for a paged-context slot: streamed online-
        softmax attention over the external parts (layers outer, parts
        inner — the device never holds more than one part), the pool-
        resident decode tail, and the incoming token itself; the new
        token's KV appends to the tail pages in one donated update.
        Raises KVGatherError if a part's bytes cannot be gathered."""
        sa = self._stream_attn
        S, t = req.ext_len, req.ext_written
        pos = S + t                       # absolute write/query position
        rec = flight_recorder.recorder()
        win = self._kv_window
        b0, w0, f0 = win.bytes_fetched, win.wait_s, win.fetches
        t0 = rec.begin()
        self._window_prefetch(req.ext_parts)
        x = sa.embed(self.params,
                     np.asarray([[self._last[req.slot]]], np.int32))
        pages_row = jnp.asarray(np.asarray(req.pages, np.int32))
        ks_new, vs_new = [], []
        for li in range(self.cfg.num_layers):
            q, k, v = sa.qkv(self.params["layers"], li, x, pos)
            m, l, acc = sa.init(1)
            for part in req.ext_parts:
                pk, pv, valid = self._part_layer(part, li)
                m, l, acc = sa.block(q, pk, pv, valid, pos,
                                     part["span"][0], m, l, acc)
            if t > 0:
                tk, tv = self._tail_gather_jit(self._pk, self._pv,
                                               jnp.int32(li), pages_row)
                m, l, acc = sa.block(q, tk, tv, t, pos, S, m, l, acc)
            m, l, acc = sa.block(q, k, v, 1, pos, pos, m, l, acc)
            x = sa.finish(self.params["layers"], li, x, l, acc)
            ks_new.append(k)
            vs_new.append(v)
        logits = sa.logits(self.params, x, 0)
        # The span covers prefetch-kick → last layer; gather_wait_us is
        # the BLOCKING portion (prefetch that got there first shows up
        # as bytes with ~zero wait — the gather/compute overlap signal).
        rec.end("request", "sp:gather", t0,
                id=req.req_id.to_bytes(8, "little"),
                parts=len(req.ext_parts),
                gather_bytes=win.bytes_fetched - b0,
                gather_wait_us=int((win.wait_s - w0) * 1e6),
                fetches=win.fetches - f0)
        page_id = req.pages[t // self.page]
        self._pk, self._pv = self._append_tail_jit(
            self._pk, self._pv, jnp.stack(ks_new)[:, 0],
            jnp.stack(vs_new)[:, 0], jnp.int32(page_id),
            jnp.int32(t % self.page))
        req.ext_written = t + 1
        return int(self._sample_batch([logits], [req.params])[0])

    def prefill_paged_chunk(self, chunk_tokens: Sequence[int], pos0: int,
                            ctx_parts, *, span: int, is_last: bool):
        """One streamed prefill chunk that NEVER touches the page pool:
        the chunk's queries attend to previously published context parts
        (gathered through the window — cross-host when a part lives in a
        peer's arena) plus the chunk itself causally, and the chunk's
        own KV comes back as a new part, padded to `span` with its real
        length in "len".  Returns (part, last_token_logits-or-None).

        This is the unit the serving layer round-robins across N
        sequence-parallel prefill shards: each shard computes its
        stripe and publishes it into ITS OWN node's arena, so no single
        node's pool (or arena) ever holds the whole context."""
        self._dense_only("prefill_paged_chunk")
        sa = self._stream_attn
        Sc = len(chunk_tokens)
        if not (0 < Sc <= span):
            raise ValueError(f"chunk of {Sc} tokens vs span {span}")
        ctx = self._norm_parts(
            ctx_parts, pos0, f"pf{self._part_seq}") if ctx_parts else []
        self._part_seq += 1
        rec = flight_recorder.recorder()
        win = self._kv_window
        b0, w0, f0 = win.bytes_fetched, win.wait_s, win.fetches
        t0 = rec.begin()
        self._window_prefetch(ctx)
        toks = np.zeros((1, span), np.int32)
        toks[0, :Sc] = chunk_tokens
        x = sa.embed(self.params, toks)
        ks_out, vs_out = [], []
        for li in range(self.cfg.num_layers):
            q, k, v = sa.qkv(self.params["layers"], li, x, pos0)
            m, l, acc = sa.init(span)
            for part in ctx:
                pk, pv, valid = self._part_layer(part, li)
                m, l, acc = sa.block(q, pk, pv, valid, pos0,
                                     part["span"][0], m, l, acc)
            m, l, acc = sa.block(q, k, v, Sc, pos0, pos0, m, l, acc)
            x = sa.finish(self.params["layers"], li, x, l, acc)
            ks_out.append(k)
            vs_out.append(v)
        rec.end("request", "sp:gather", t0, parts=len(ctx),
                gather_bytes=win.bytes_fetched - b0,
                gather_wait_us=int((win.wait_s - w0) * 1e6),
                fetches=win.fetches - f0, prefill_chunk=True)
        # The stripe stays DEVICE-RESIDENT: a same-process consumer
        # (chunk c+1 via the window, or a co-located decode engine)
        # attends to it with zero host copies, and publishing it stages
        # exactly once through the serializer's device plane — the old
        # np.asarray here paid a device->host sync per chunk even when
        # nothing ever left the process.
        part = {"k": jnp.stack(ks_out), "v": jnp.stack(vs_out), "len": Sc}
        logits = sa.logits(self.params, x, Sc - 1) if is_last else None
        return part, logits

    def prefill_paged(self, prompt_tokens: Sequence[int],
                      params: Optional[SamplingParams] = None, *,
                      span: int = 64, publish=None,
                      pipeline: bool = True,
                      host_staged: bool = False) -> dict:
        """Streamed chunked prefill of an arbitrarily long context with a
        bounded device working set: chunk c attends to the c already-
        published parts, then becomes part c itself.  `publish(part) ->
        handle` puts each stripe wherever it should live (the serving
        layer puts into the local arena — the handle is a 20-byte ref);
        without it parts travel by value (engine-standalone use).
        Returns the handoff ``{"parts": [{"span", "handle"}], "len",
        "first"}`` that add_paged_request / decode_paged consume.

        pipeline=True (default) overlaps chunk c's publish with chunk
        c+1's shard compute: publishes run on a background thread and
        the handles resolve only when the handoff is assembled — safe
        because chunk c+1 reads part c through the gather window (seeded
        locally), never through its handle.  host_staged=True forces the
        legacy downgrade — every stripe is materialized to host numpy
        before it travels — and exists for the device-vs-staged A/B
        (perf gate `long_context_ttft_ms` vs the informational
        `long_context_ttft_staged_ms`)."""
        params = params or SamplingParams()
        prompt = list(prompt_tokens)
        S = len(prompt)
        span = max(8, int(span))
        parts_meta: List[dict] = []
        n_chunks = math.ceil(S / span)
        logits = None
        pub_pool = None
        try:
            for c in range(n_chunks):
                s0 = c * span
                chunk = prompt[s0:s0 + span]
                part, logits = self.prefill_paged_chunk(
                    chunk, s0, parts_meta, span=span,
                    is_last=(c == n_chunks - 1))
                if host_staged:
                    from .._private import device_plane
                    hk = np.asarray(part["k"])
                    hv = np.asarray(part["v"])
                    device_plane.record_d2h(hk.nbytes + hv.nbytes)
                    part = {"k": hk, "v": hv, "len": part["len"]}
                key = f"pp{id(self) & 0xffff}:{self._part_seq}"
                self._part_seq += 1
                # Keep our own freshly produced stripe hot for chunk c+1.
                self._kv_window.put(key, part)
                if publish is None:
                    handle = part
                elif pipeline:
                    if pub_pool is None:
                        import concurrent.futures as _cf
                        pub_pool = _cf.ThreadPoolExecutor(
                            1, thread_name_prefix="kvpublish")
                    handle = pub_pool.submit(publish, part)
                else:
                    handle = publish(part)
                parts_meta.append({"span": (s0, s0 + len(chunk)),
                                   "handle": handle, "key": key})
            first = self._sample_batch([logits], [params])[0]
            if pub_pool is not None:
                # Resolve pipelined publishes (any failure surfaces here,
                # before the handoff can reference a phantom part).
                for m in parts_meta:
                    import concurrent.futures as _cf
                    if isinstance(m["handle"], _cf.Future):
                        m["handle"] = m["handle"].result()
        finally:
            if pub_pool is not None:
                pub_pool.shutdown(wait=True)
        return {"parts": [{"span": m["span"], "handle": m["handle"]}
                          for m in parts_meta],
                "len": S, "first": int(first)}

    def decode_paged(self, handoff: dict,
                     params: Optional[SamplingParams] = None) -> List[int]:
        """Closed-loop convenience over add_paged_request (the serving
        layer streams the same admission instead): decode a paged
        handoff to completion; re-raises the typed gather error if a
        part's host was lost mid-decode."""
        rid = self.add_paged_request(handoff["parts"], handoff["len"],
                                     handoff["first"], params,
                                     prompt_tokens=handoff.get("prompt"))
        while self.has_unfinished():
            for done in self.step():
                if done.req_id == rid:
                    if done.error is not None:
                        raise done.error
                    return done.out
        raise RuntimeError(
            f"paged request {rid} was dropped without finishing")

    # ------------------------------------------------------------ generate --
    def generate(self, prompts: Sequence[Sequence[int]],
                 params: Optional[SamplingParams] = None
                 ) -> List[List[int]]:
        """Batch API: returns generated token lists, in prompt order."""
        ids = [self.add_request(p, params) for p in prompts]
        results: Dict[int, List[int]] = {}
        while self.has_unfinished():
            for req in self.step():
                results[req.req_id] = req.out
        return [results[i] for i in ids]

    def trace_logits(self, prompt: Sequence[int], tokens: Sequence[int] = (),
                     cached: bool = False) -> Dict[str, Any]:
        """For a reference check that needs the path's own logits and what
        it decided (a model with routed experts): `prompt` prefilled into a
        free slot — cold, or with `cached` as a request would be, from
        whatever the prefix cache holds of it — then each of `tokens`
        decoded through the pool and the slot's state by the serving step's
        own model half (`_decode_logits_fn`).  Returns {"logits": (1 +
        len(tokens), V) float32, the prompt's last position and then each
        token's; "from": the cached tokens the prefill started after;
        "chosen": (routed layers, len(prompt) - from + len(tokens), K) the
        experts every computed position chose, or None}.  Needs a free slot
        and the pages; leaves nothing behind and adds no cache entry."""
        req = _Request(-1, list(prompt),
                       SamplingParams(max_tokens=len(tokens) + 1))
        req.no_cache = not cached
        if not self._reserve(req):
            raise RuntimeError("trace_logits: no free slot or pages")
        try:
            S, slot, picks = len(prompt), req.slot, []
            if self._cache is not None and cached:  # keeps no checkpoint
                self._cache.free_rows.extend(req.new_rows.values())
                req.new_rows = {}
            logits, chosen = self._prefill_slot(req)
            if chosen is not None:
                picks.append(chosen[:, 0, :S - req.prefix_len])
            if self._cache is not None and cached:
                self._cache.hold_row(req.from_row, -1)
            if self._trace_jit is None:
                cfg, page, kv_shd = self.cfg, self.page, self._kv_shd
                def decode_logits(p, pk, pv, tb, lt, ln, ac, rec):
                    return _decode_logits_fn(p, pk, pv, tb, lt, ln, ac, cfg,
                                             page, kv_shd, rec)
                self._trace_jit = jax.jit(decode_logits,
                                          donate_argnums=(1, 2, 7))
            rows = [logits]
            active = np.zeros(self.max_batch, bool)
            active[slot] = True
            at = np.arange(self.max_batch) == slot
            for i, tok in enumerate(tokens):
                # (Fresh arrays a step: the CPU backend may read a numpy
                # argument in place after the call has returned.)
                self._pk, self._pv, lg, *pattern = self._trace_jit(
                    self.params, self._pk, self._pv, self._tables.copy(),
                    np.where(at, tok, 0).astype(np.int32),
                    np.where(at, S + i, 0).astype(np.int32), active.copy(),
                    self._dev.get("rec", ()))
                if pattern:
                    self._dev["rec"], _, chosen = pattern
                    if chosen is not None:
                        picks.append(chosen[:, slot])
                rows.append(lg[slot])
        finally:
            self._free_slot(req)
        return {"logits": jnp.stack(rows), "from": req.prefix_len,
                "chosen": jnp.concatenate(picks, axis=1) if picks else None}

    # ------------------------------------------- prefill/decode disaggregation
    def prefill_only(self, prompt_tokens: Sequence[int],
                     params: Optional[SamplingParams] = None):
        """Prefill-node half of P/D disaggregation (reference pattern:
        llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py):
        returns (kv_blob, first_token) to ship to a decode node via the
        object store.  The blob's k/v stay DEVICE-RESIDENT jax arrays: a
        same-process decode engine installs them with no host round-trip,
        and shipping the blob stages it exactly once through the
        serializer's device plane (a multi-device tp-sharded cache falls
        back to a host gather there, counted as fallback bytes).  With
        the prefix cache on, a hit computes only the suffix and gathers
        the shared span straight out of the resident pages."""
        self._dense_only("prefill_only")
        params = params or SamplingParams()
        S = len(prompt_tokens)
        if S >= self.max_len:
            raise ValueError(f"prompt ({S}) >= max_len ({self.max_len})")
        prompt = list(prompt_tokens)
        rec = flight_recorder.recorder()
        t0 = rec.begin()
        c, shared = 0, []
        if self._cache is not None:
            c, shared, _ = self._cache.lookup(prompt)
        if c:
            row = np.zeros(self.pages_per_slot, np.int32)
            row[:len(shared)] = shared
            logits, ks, vs = self._run_suffix(prompt, c, row)
            heads = (self.cfg.num_kv_heads, self.cfg.head_dim_)
            idx = jnp.asarray(np.asarray(shared))
            ck = head_rows(self._pk[:, idx], *heads).reshape(
                self.cfg.num_layers, c, *heads)
            cv = head_rows(self._pv[:, idx], *heads).reshape(
                self.cfg.num_layers, c, *heads)
            k_full = jnp.concatenate([ck, ks[:, :S - c]], 1)
            v_full = jnp.concatenate([cv, vs[:, :S - c]], 1)
        else:
            logits, ks, vs = self._run_prefill(prompt)
            k_full = ks[:, :S]
            v_full = vs[:, :S]
        # Populate the cache from this prefill: a prefill-only engine
        # (the P/D prefill half) runs no admission, so this is its only
        # insertion point.  The full prompt pages beyond the cached
        # prefix install into fresh pool pages held alive by the cache
        # entries alone (skipped under pool pressure — eviction is the
        # admission path's call, not an insert's).
        full = S // self.page
        new_cnt = full - len(shared)
        if self._cache is not None and new_cnt > 0 \
                and len(self._free_pages) >= new_cnt:
            fresh = [self._alloc_page() for _ in range(new_cnt)]
            span = full * self.page - c       # tokens [c, full*page)
            self._install_pages(fresh, ks[:, :span], vs[:, :span])
            row = np.zeros(self.pages_per_slot, np.int32)
            row[:len(shared)] = shared
            row[len(shared):full] = fresh
            self._cache.insert(prompt, row, self._incref)
            for p in fresh:
                self._decref(p)               # cache refs keep them
        rec.end("request", "prefill", t0, tokens=S, cached_tokens=c,
                external=True)
        first = self._sample_host(logits, params)
        return {"k": k_full, "v": v_full, "len": S}, int(first)

    def decode_from(self, kv_blob: dict, first_token: int,
                    params: Optional[SamplingParams] = None, *,
                    prompt_tokens: Optional[Sequence[int]] = None
                    ) -> List[int]:
        """Decode-node half: install a shipped prefill and run decode to
        completion (closed-loop convenience over add_external_request —
        the serving layer streams the same admission instead)."""
        rid = self.add_external_request(kv_blob, first_token, params,
                                       prompt_tokens=prompt_tokens)
        req = self._requests[rid]
        while self.has_unfinished():
            for done in self.step():
                if done.req_id == rid:
                    return done.out
        raise RuntimeError(
            f"decode request {req.req_id} was dropped without finishing")
