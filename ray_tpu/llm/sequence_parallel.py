"""Sequence-parallel prefill attention + streamed paged-KV attention.

The reference Ray has no sequence/context parallelism anywhere (SURVEY.md
§5.7) — it orchestrates SPMD programs that implement SP themselves.  On
TPU we own the whole stack, so the LLM engine gets it natively, in two
halves that compose into the long-context serving path:

1. **SP prefill** (`sp_prefill_fn`, and its suffix form `cached=`): the
   engine's prefill attention with the sequence dim sharded over an
   ``sp`` mesh axis via shard_map — Ring Attention (Liu et al. 2023: KV
   blocks rotate around the axis with running log-sum-exp softmax
   rescaling, fully-masked causal blocks contribute nothing) or
   DeepSpeed-Ulysses (Jacobs et al. 2023: all-to-all reshards seq→heads,
   local full attention, reshard back).  Exact parity with the engine's
   `_prefill_fn` at every shard count: the K/V projections are per-token
   (identical by construction) and online softmax is associative in
   fp32, so logits match to fp32 tolerance.  The suffix variant seeds
   the ring accumulator with the pool-resident prefix contribution so
   prefix-cache hits keep skipping shared-page prefill under SP.

2. **Streamed paged-KV attention** (`StreamAttn`): attention over KV
   *parts* that are never resident in the device page pool — each part
   is a ``(L, span, KV, D)`` stripe living in some node's shm arena
   (possibly a REMOTE node's, published through the replica directory).
   The driver loops layers outer / parts inner, accumulating online
   softmax one part at a time, so the device working set is O(one part)
   regardless of context length.  This is what lets one request's KV
   span hosts: the engine's decode gathers parts through a bounded
   prefetch window (gather overlaps compute) and a prefill chunk
   attends to previously-published stripes the same way — a context
   that provably cannot fit any single node's page pool still serves.

Both run identically on the 8-device CPU test mesh and a TPU pod.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as model
from ..ops.paged_attention import head_rows
from ..ops.ring_attention import ring_attention, ulysses_attention

__all__ = ["sp_mesh", "sp_prefill_fn", "sp_stripe_pages", "StreamAttn",
           "validate_sp"]


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------

def sp_mesh(degree: int, devices=None) -> Mesh:
    """Build a local ``sp``-axis mesh over the first `degree` devices."""
    from ..parallel import MeshSpec, build_mesh
    devices = list(devices if devices is not None else jax.devices())
    if degree > len(devices):
        raise ValueError(
            f"sp_degree={degree} exceeds the {len(devices)} visible "
            f"devices (CPU tests: XLA_FLAGS=--xla_force_host_platform_"
            f"device_count)")
    return build_mesh(MeshSpec(sp=degree), devices=devices[:degree])


def validate_sp(cfg, degree: int, strategy: str) -> None:
    """Fail fast on layouts the shard_map bodies cannot express."""
    if strategy not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp strategy {strategy!r}")
    if degree < 2:
        return
    if strategy == "ulysses" and cfg.num_kv_heads % degree:
        raise ValueError(
            f"ulysses needs num_kv_heads ({cfg.num_kv_heads}) divisible "
            f"by sp_degree ({degree}); use strategy='ring'")


def sp_stripe_pages(pages, S: int, n_shards: int, page: int,
                    padded: Optional[int] = None) -> list:
    """Partition the pages an SP pass installed over the sp shards:
    shard i owns the pages whose FIRST token falls in its sequence
    stripe.  This is the install/handoff accounting the cross-host path
    consumes — each shard's stripe of a prefill is published/owned
    separately.

    `padded` is the kernel's PADDED sequence length (the pow-2 bucket):
    shard_map splits the padded axis evenly, so shard i computed tokens
    [i·padded/n, (i+1)·padded/n) — boundaries from the real length S
    would mis-attribute pages near the padded tail.  `pages` must be
    exactly the pages the pass wrote (for a prefix-cache-hit suffix
    pass: the NEW pages, not the shared prefix's)."""
    Sb = padded or S
    per = Sb // n_shards        # pow-2 bucket / pow-2 degree: exact
    n_pages = math.ceil(S / page)
    stripes = [[] for _ in range(n_shards)]
    for p in range(n_pages):
        shard = min((p * page) // per, n_shards - 1)
        stripes[shard].append(int(pages[p]))
    return stripes


# ---------------------------------------------------------------------------
# SP prefill (ring / Ulysses over a seq-sharded mesh)
# ---------------------------------------------------------------------------

def _seq_sharding(mesh: Mesh, rank: int):
    spec = [None] * rank
    spec[1] = "sp"
    return NamedSharding(mesh, P(*spec))


def _sp_suffix_shard(q, k, v, ck, cv, prefix_len, *, axis_name: str,
                     n_shards: int, scale: float):
    """shard_map body for SP suffix prefill: q/k/v are the suffix's
    local seq shards (rope already applied at absolute positions);
    ck/cv (T, KV, D) are the pool-resident prefix, REPLICATED — every
    shard reads the whole prefix (it is resident KV, no compute), and
    the suffix KV rotates around the ring exactly like full-prefill
    ring attention, with the online-softmax accumulator SEEDED by the
    prefix contribution (associativity makes the seed exact)."""
    B, Sloc, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sloc, Hkv, G, D)
    idx = jax.lax.axis_index(axis_name)
    q_pos = idx * Sloc + jnp.arange(Sloc)        # suffix-relative

    # Seed: attend to the resident prefix (valid keys: t < prefix_len).
    T = ck.shape[0]
    s_pre = jnp.einsum("bskgd,tkd->bkgst", qg, ck,
                       preferred_element_type=jnp.float32) * scale
    pvalid = (jnp.arange(T) < prefix_len)[None, None, None, None, :]
    s_pre = jnp.where(pvalid, s_pre, -1e30)
    m = jnp.max(s_pre, -1, keepdims=True)
    p = jnp.where(pvalid, jnp.exp(s_pre - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    acc = jnp.einsum("bkgst,tkd->bkgsd", p.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)

    def accumulate(k_blk, v_blk, m, l, acc, s):
        src = (idx - s) % n_shards
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_blk,
                            preferred_element_type=jnp.float32) * scale
        k_pos = src * Sloc + jnp.arange(Sloc)
        mask = (q_pos[:, None] >= k_pos[None, :])[None, None, None]
        scores = jnp.where(mask, scores, -1e30)
        m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, -1, keepdims=True)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, alpha * acc + pv

    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, s):
        k_blk, v_blk, m, l, acc = carry
        m, l, acc = accumulate(k_blk, v_blk, m, l, acc, s)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, m, l, acc), None

    if n_shards > 1:
        (k, v, m, l, acc), _ = jax.lax.scan(
            step, (k, v, m, l, acc), jnp.arange(n_shards - 1))
    m, l, acc = accumulate(k, v, m, l, acc, n_shards - 1)

    out = acc / jnp.maximum(l, 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sloc, Hq, D)
    return out.astype(q.dtype)


def sp_prefill_fn(params, tokens, length, cfg, mesh: Mesh,
                  strategy: str = "ring", cached=None):
    """Sequence-parallel twin of programs._prefill_fn: same contract —
    tokens (1, Sb) padded prompt → (last_logits (V,), ks, vs
    (L, Sb, KV, D)) — with the attention sharded over the mesh's ``sp``
    axis.  Sb must be divisible by the sp size (pow-2 buckets are).
    Heads ride a ``tp`` axis if the mesh has one; only the sequence
    axis communicates.  With `cached` = (pool_k, pool_v, pages,
    prefix_len, page), its suffix form (a prefix-cache hit): suffix
    queries sharded over ``sp``, resident prefix pages replicated, ring
    rotation over the suffix KV.  Always ring — Ulysses would have to
    split the resident prefix's KV heads across shards, which buys
    nothing for a memory-resident prefix."""
    S = tokens.shape[1]
    scale = cfg.score_scale
    tokens = jax.lax.with_sharding_constraint(tokens,
                                              _seq_sharding(mesh, 2))
    x = model.embed_tokens(params, tokens, cfg)
    x = jax.lax.with_sharding_constraint(x, _seq_sharding(mesh, 3))
    if cached is None:
        cos, sin = model.rope_angles(jnp.arange(0, S, dtype=jnp.float32),
                                     cfg)
        attn = ring_attention if strategy == "ring" else ulysses_attention
        per_layer = ()

        def attend(q, k, v):
            o = attn(q, k, v, mesh, axis_name="sp", causal=True,
                     scale=scale, batch_axes=(), heads_axis="tp")
            return o, (k[0], v[0])
    else:
        *per_layer, pages, prefix_len, page = cached
        T = pages.shape[0] * page
        # RoPE at absolute positions prefix_len + i (prefix_len is traced).
        cos, sin = model.rope_angles(
            prefix_len + jnp.arange(S, dtype=jnp.int32), cfg)
        spec = P(None, "sp", None, None)
        shard = jax.shard_map(
            functools.partial(_sp_suffix_shard, axis_name="sp",
                              n_shards=mesh.shape["sp"], scale=scale),
            mesh=mesh, in_specs=(spec, spec, spec, P(None, None, None),
                                 P(None, None, None), P()),
            out_specs=spec, check_vma=False)
        heads = (cfg.num_kv_heads, cfg.head_dim_)

        def attend(q, k, v, pk, pv):            # pk/pv: (N, page, *row)
            ck = head_rows(pk[pages], *heads).reshape(T, *heads)
            cv = head_rows(pv[pages], *heads).reshape(T, *heads)
            return shard(q, k, v, ck, cv, prefix_len), (k[0], v[0])

    x, (ks, vs) = model.scan_blocks(params["layers"], x, cos, sin, attend,
                                    cfg, per_layer)
    return model.lm_logits(params, x[0, length - 1], cfg), ks, vs


# ---------------------------------------------------------------------------
# Streamed paged-KV attention (cross-host KV location tiers)
# ---------------------------------------------------------------------------

def _stream_block_fn(q, k_blk, v_blk, k_valid, q_pos0, k_pos0, m, l, acc,
                     *, scale: float):
    """Online-softmax accumulate ONE KV block into a running (m, l, acc).

    q (Sq, Hq, D): rope'd queries at absolute positions q_pos0 + i.
    k_blk/v_blk (Sk, KV, D): rope'd keys/values at positions k_pos0 + j;
    key j participates iff j < k_valid AND k_pos <= q_pos (causality by
    absolute position — blocks strictly before the queries are fully
    valid, the self block is triangular, later blocks contribute 0).
    m/l (KV, G, Sq, 1) and acc (KV, G, Sq, D) are f32; associativity of
    the log-sum-exp merge means block order never changes the result."""
    Sq, Hq, D = q.shape
    Sk, Hkv, _ = k_blk.shape
    G = Hq // Hkv
    qg = q.reshape(Sq, Hkv, G, D)
    s = jnp.einsum("skgd,tkd->kgst", qg, k_blk,
                   preferred_element_type=jnp.float32) * scale
    j = jnp.arange(Sk)
    valid = ((j[None, :] < k_valid)
             & ((k_pos0 + j)[None, :] <= (q_pos0 + jnp.arange(Sq))[:, None]))
    s = jnp.where(valid[None, None], s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
    # Explicit re-mask of p: a fully-masked block leaves m at -1e30 and
    # exp(-1e30 - -1e30) would otherwise contribute 1.0 per masked key.
    p = jnp.where(valid[None, None], jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, -1, keepdims=True)
    pv = jnp.einsum("kgst,tkd->kgsd", p.astype(v_blk.dtype), v_blk,
                    preferred_element_type=jnp.float32)
    return m_new, l_new, alpha * acc + pv


def _layer(layers, i):
    """Layer `i` (traced) of the stacked layer parameters."""
    return jax.tree_util.tree_map(
        lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
        layers)


class StreamAttn:
    """Jit-cached kernel family for attention over streamed KV parts.

    The engine drives it layers-outer / parts-inner:

        x = sa.embed(params, tokens)
        for li in range(L):
            q, k, v = sa.qkv(params["layers"], li, x, pos0)
            m, l, acc = sa.init(Sq)
            for each KV block (remote part / pool tail / self):
                m, l, acc = sa.block(q, kb, vb, valid, q0, k0, m, l, acc)
            x = sa.finish(params["layers"], li, x, l, acc)
        logits = sa.logits(params, x, last_idx)

    Only one block is ever device-resident per call, so the device
    working set is O(part), not O(context).  Each piece is ONE jit, which
    compiles once an operand shape (chunk/part sizes are engine-static,
    so that stays a handful of programs)."""

    def __init__(self, cfg):
        self.cfg = cfg

        def qkv(layers, i, x, pos0):
            cos, sin = model.rope_angles(
                pos0 + jnp.arange(x.shape[1], dtype=jnp.int32), cfg)
            q, k, v = model.block_qkv(_layer(layers, i), x, cos, sin, cfg)
            return q[0], k[0], v[0]

        def finish(layers, i, x, l, acc):
            o = acc / jnp.maximum(l, 1e-30)        # (KV, G, Sq, D)
            o = o.transpose(2, 0, 1, 3).reshape(
                1, x.shape[1], -1, cfg.head_dim_).astype(cfg.dtype)
            return model.block_out(_layer(layers, i), x, o, cfg)

        def logits(params, x, idx):
            return model.lm_logits(params, x[0, idx], cfg)
        self._embed = jax.jit(lambda p, t: model.embed_tokens(p, t, cfg))
        self._qkv, self._finish = jax.jit(qkv), jax.jit(finish)
        self._block = jax.jit(functools.partial(
            _stream_block_fn, scale=cfg.score_scale))
        self._logits = jax.jit(logits)

    def init(self, sq: int):
        cfg = self.cfg
        shape = (cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, sq)
        m = jnp.full(shape + (1,), -1e30, jnp.float32)
        l = jnp.zeros(shape + (1,), jnp.float32)
        acc = jnp.zeros(shape + (cfg.head_dim_,), jnp.float32)
        return m, l, acc

    def embed(self, params, tokens):
        return self._embed(params, jnp.asarray(tokens))

    def qkv(self, layers, li: int, x, pos0: int):
        """→ (q (Sq, Hq, D), k, v (Sq, KV, D)), rope'd at pos0 + i."""
        return self._qkv(layers, jnp.int32(li), x, jnp.int32(pos0))

    def block(self, q, k_blk, v_blk, k_valid: int, q_pos0: int,
              k_pos0: int, m, l, acc):
        return self._block(q, k_blk, v_blk, jnp.int32(k_valid),
                           jnp.int32(q_pos0), jnp.int32(k_pos0), m, l, acc)

    def finish(self, layers, li: int, x, l, acc):
        return self._finish(layers, jnp.int32(li), x, l, acc)

    def logits(self, params, x, idx: int):
        return self._logits(params, x, jnp.int32(idx))


# ---------------------------------------------------------------------------
# Bench entry (perf gate: sp_prefill_tokens_per_s / long_context_ttft_ms)
# ---------------------------------------------------------------------------

def _bench_sp_prefill(degree: int, tokens: int, strategy: str,
                      iters: int) -> float:
    """Prefill tokens/s at a given sp degree (degree 1 = the engine's
    single-device _prefill_fn — the A/B base)."""
    import time

    from ..models import PRESETS
    from ..models.transformer import init_params
    from .programs import _prefill_fn
    cfg = PRESETS["tiny"]
    params = init_params(cfg, jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (1, tokens)), jnp.int32)
    if degree > 1:
        mesh = sp_mesh(degree)
        fn = jax.jit(lambda p, t, n: sp_prefill_fn(p, t, n, cfg, mesh,
                                                   strategy))
    else:
        fn = jax.jit(lambda p, t, n: _prefill_fn(p, t, n, cfg))
    out = fn(params, toks, tokens)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(params, toks, tokens))
    dt = (time.perf_counter() - t0) / iters
    return tokens / dt


def _bench_long_context_ttft(context: int, span: int,
                             host_staged: bool = False) -> float:
    """TTFT (ms) for a context served through the paged cross-host KV
    path: streamed chunked prefill (pool-free) + paged admission — the
    pool of BOTH engines is sized well below the context to prove the
    page-location tier carries it.  host_staged=True forces the legacy
    downgrade (every KV stripe round-trips through host numpy, publish
    pipelining off) — the informational A/B base for the device-direct
    data plane."""
    import time

    from ..models import PRESETS
    from .engine import LLMEngine, SamplingParams
    cfg = PRESETS["tiny"]
    pre = LLMEngine(cfg, max_batch=1, max_len=64, page_size=16,
                    kv_pages=4, seed=0)
    dec = LLMEngine(cfg, max_batch=1, max_len=64, page_size=16,
                    kv_pages=4, seed=0)
    prompt = list(np.random.default_rng(1).integers(
        1, cfg.vocab_size, context))
    sp = SamplingParams(max_tokens=4)
    kw = dict(span=span, host_staged=host_staged,
              pipeline=not host_staged)
    # Warm the compile caches so TTFT measures the serve path, not XLA.
    h = pre.prefill_paged(prompt, sp, **kw)
    dec.decode_paged(h, sp)
    best = None
    for _ in range(3):         # best-of: single-shot TTFT is co-tenant
        t0 = time.perf_counter()   # noise on a shared host
        handoff = pre.prefill_paged(prompt, sp, **kw)
        rid = dec.add_paged_request(handoff["parts"], handoff["len"],
                                    handoff["first"], sp)
        first_seen = None
        while dec.has_unfinished() and first_seen is None:
            dec.step()
            for ev_rid, _tok, _fin in dec.take_tick_events():
                if ev_rid == rid:
                    first_seen = time.perf_counter()
                    break
        dec.cancel_request(rid)
        ms = ((first_seen or time.perf_counter()) - t0) * 1e3
        best = ms if best is None else min(best, ms)
    return best


def _bench_main(argv=None) -> int:
    """`python -m ray_tpu.llm.sequence_parallel --bench` → one JSON line
    with the perf-gate rows (run by util/perf.py in a subprocess with
    forced host devices so the A/B is CPU-deterministic)."""
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", action="store_true")
    ap.add_argument("--degree", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--strategy", default="ring")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--context", type=int, default=384)
    ap.add_argument("--span", type=int, default=64)
    args = ap.parse_args(argv)
    base = _bench_sp_prefill(1, args.tokens, args.strategy, args.iters)
    spn = _bench_sp_prefill(args.degree, args.tokens, args.strategy,
                            args.iters)
    ttft = _bench_long_context_ttft(args.context, args.span)
    # Informational A/B base: same serve path with the legacy host-
    # staged KV downgrade (reported, never gated — see perf.py).
    ttft_staged = _bench_long_context_ttft(args.context, args.span,
                                           host_staged=True)
    print(json.dumps({
        "sp_prefill_tokens_per_s": round(spn, 1),
        "sp_prefill_tokens_per_s_base": round(base, 1),
        "sp_degree": args.degree,
        "sp_speedup": round(spn / base, 3) if base else 0.0,
        "long_context_ttft_ms": round(ttft, 2),
        "long_context_ttft_staged_ms": round(ttft_staged, 2),
    }))
    return 0


if __name__ == "__main__":   # pragma: no cover — exercised via perf.py
    import sys
    sys.exit(_bench_main())
