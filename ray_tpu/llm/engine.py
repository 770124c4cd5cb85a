"""Continuous-batching LLM generation engine, TPU-first: the SCHEDULER.

Reference surface: python/ray/llm/_internal — the reference wraps vLLM
(engines/vllm/) for batch inference and serving.  On TPU we own the whole
stack, so the engine is native JAX on the in-tree flagship transformer
(models/transformer.py) and is built around XLA's compilation model.  This
file holds requests, slots, pages, admission and the tick; what it compiles
is llm/programs.py (every traced function, and the table of what a kind of
layer caches, how it attends and what it counts: this file names no kind),
and its host-side stores are llm/kv_cache.py:

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
    a handful of compilations total, amortized across all requests.
    `prefill_stats()` says which attention form the prefills took.
  - KV pool lives on device between steps (no host round-trips in the
    decode loop); only sampled token ids come back per step.  So does the
    step's own state (page tables, last tokens, lengths, active mask,
    temperatures, sampling key): the step advances it, and the host
    writes to it only the slots it changed (`programs._decode_fn`).
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
import math
import os
import tempfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .._private import flight_recorder
from ..exceptions import KVGatherError
from ..models.transformer import (ROW_BLOCK, TransformerConfig, init_params,
                                  param_logical_axes, row_blocks, state_axis,
                                  state_bytes, state_chunk, zero_states)
from ..ops.paged_attention import (decode_path, head_rows, pool_row,
                                   pool_rows)
from . import programs
from .kv_cache import (_default_kv_fetch, _KVDemoteStore, _KVWindow,
                       _PrefixCache)
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
# Engine
# --------------------------------------------------------------------------

# A pattern with stateful layers keeps a state checkpoint every this many
# of their chunks (`state_chunk`: 4 x 128 = 512 tokens for the hybrid's
# scan chunk, and for the short convolution's), and no farther apart than
# `_CKPT_TOKENS` where a chunk divides that (a scan chunk of 256: every 512
# too, not 1,024: a re-ask needs a boundary inside what it shares), and
# pads no prefill below `_MIN_STATE_ROWS` rows: under that a prefill's time
# is the weights' read, and a bucket fewer is a program fewer to warm (a
# warm-up that reaches the suffix programs through one shared page of 16
# tokens starts at 24 rows).
_CKPT_CHUNKS = 4
_CKPT_TOKENS = 512
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
        # How this configuration caches (programs.CACHES), looked up once.
        # Where no layer attends there is NO pool: no array, no page to
        # reserve or to wait for, and the whole cache is the state rows below.
        self._cache_form = form = programs.cache_of(cfg)
        pages = kv_pages if kv_pages is not None \
            else max_batch * self.pages_per_slot
        # page 0 is scratch (inactive-slot writes land there); never handed out
        self.n_pages = 1 + (pages if form.pools else 0)
        kvh, d = cfg.cache_row
        # State checkpoints, every `_every` tokens (0: no recurrent layer).
        self._every = _CKPT_CHUNKS * state_chunk(cfg)
        if self._every > _CKPT_TOKENS and not _CKPT_TOKENS % state_chunk(cfg):
            self._every = _CKPT_TOKENS
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

        # The pools, of which a form may have two, one (the other None: an
        # empty tree) or none.
        self._pk, self._pv = programs.make_pools(
            cfg, self.n_pages, self.page, self._kv_shd)
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
        # (A tree for each stateful block of the period, a row a checkpoint,
        # behind the repeats where the period is scanned: `zero_states`.)
        self._ckpt = zero_states(cfg, 2 + n_rows)
        self._state_axis = state_axis(cfg)
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
            if _demo_on and not self._every and form.pools == 2:
                # (Demoted pages would leave their state checkpoints behind;
                # the store keeps K/V pairs.)
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
        none = programs._pack_rows(self._tables, self._last, self._lengths,
                                   idle, self._temps, idle)
        self._dev = jax.device_put(
            {"slots": none[:, :-1], "rng": jax.random.key(seed + 1)},
            self._state_shd)
        if self._every:
            # Per slot, the recurrent layers' state: resident with the rest.
            self._dev["rec"] = zero_states(cfg, max_batch)
        # What the host counts for the kinds of layer the configuration has
        # (programs.COUNTED): by name, the `<name>_stats()` below.
        self._counts = programs.counters(cfg, pool=self._pk, keep=self._keep,
                                         slots=max_batch)
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
            lambda p, pk, pv, state, update: programs._decode_fn(
                p, pk, pv, state, update, cfg, page, kv_shd),
            donate_argnums=(1, 2, 3))

        def install_kv(pk, pv, ks, vs, pages):
            return programs._install_fn(pk, pv, ks, vs, pages, page, kv_shd)
        self._install_jit = jax.jit(install_kv, donate_argnums=(0, 1))

        self._install_state_jit = jax.jit(programs._install_state_fn,
                                          donate_argnums=(0, 1),
                                          static_argnames="axis")
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
        if self._cache_form.pools == 1:
            raise ValueError(
                f"{what}: a shipped or streamed cache is a K/V pair, and "
                "this configuration caches one row a token in one pool")
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

    def _queue(self, req: _Request) -> int:
        """A request whose pages the pool can hold joins the waiting."""
        need = self._pages_needed(req)
        if need > self.n_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.n_pages - 1} — raise kv_pages or lower max_tokens")
        self._next_id += 1
        self._requests[req.req_id] = req
        self._waiting.append(req)
        return req.req_id

    def add_request(self, prompt_tokens: Sequence[int],
                    params: Optional[SamplingParams] = None, *,
                    no_cache: bool = False) -> int:
        if len(prompt_tokens) >= self.max_len:
            raise ValueError(
                f"prompt ({len(prompt_tokens)}) >= max_len ({self.max_len})")
        req = _Request(self._next_id, list(prompt_tokens),
                       params or SamplingParams())
        req.no_cache = no_cache
        return self._queue(req)

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
        return self._queue(req)

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
        return self._queue(req)

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
        cfg, pooled = self.cfg, self._pk is not None
        per_step = self.max_batch * self.pages_per_slot if pooled else 0
        return {"path": decode_path(
                    (cfg.num_heads, cfg.head_dim_), self._pk.shape,
                    self._tables.shape, self._cache_form.value_lanes(cfg))
                if pooled else "none",
                "pool_row": pool_row(*cfg.cache_row) if pooled else "none",
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
        """This and the two below: what `programs.COUNTED`'s row of that
        name counts (docs/serving.md has the keys), or {"enabled": False}."""
        return programs.report(self._counts, "latent", self._decode_steps)

    def retention_stats(self) -> Dict[str, Any]:
        return programs.report(self._counts, "retention", self._decode_steps)

    def routed_stats(self) -> Dict[str, Any]:
        return programs.report(self._counts, "routed", self._decode_steps)

    def mamba_stats(self) -> Dict[str, Any]:
        return programs.report(self._counts, "mamba", self._decode_steps)

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
        pooled = self._pk is not None
        row = () if prefix_len is None or not pooled \
            else (self.page, self.pages_per_slot)
        table = math.prod(row) if row else 0    # cached rows a suffix sees
        run, dense = kv_blocks(rows, padded, prefix_len or 0, table)
        if not pooled:                  # no layer attends: no attention form
            path, run, dense = "none", 0, 0
        elif self.sp_degree > 1:
            path = "xla"
        else:
            path = programs._prefill_path(self.cfg, padded, self._kv_shd,
                                          *row)
        rows_run, rows_dense = row_blocks(
            rows if self.sp_degree == 1 else None, padded, every=self._every)
        self._prefill_ran = {
            "path": path, "kv_blocks": run if path == "kernel" else dense,
            "row_blocks": rows_run,
            **programs.count(self._counts, "prefill", rows, prefix_len,
                             table, min(padded, rows_run * ROW_BLOCK))}
        st = self._prefill_stats
        if pooled:
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
                    return programs._prefill_fn(p, t, n, cfg, kv_shd)
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

    def _install_pages(self, page_ids: Sequence[int], ks, vs):
        """Install KV into specific pool pages, ks/vs starting page-aligned
        on page_ids[0]: a slot's whole table row, or the pages NEWLY
        reserved for a suffix (it starts page-aligned at prefix_len, so it
        maps exactly onto them; the shared prefix pages are already
        resident and are never written).  Trailing scratch-page writes are
        masked reads by contract."""
        pages = np.zeros(self.pages_per_slot, np.int32)
        pages[:len(page_ids)] = page_ids
        self._pk, self._pv = self._install_jit(
            self._pk, self._pv, ks, vs, jnp.asarray(pages))

    def _install_state(self, req: _Request, end, kept) -> None:
        """A prefill's recurrent state into the request's slot, and the
        checkpoints it passed into the rows reserved for them
        (`_reserve`); the prefill's bucket may hold boundaries past the
        prompt, which go to the scratch row."""
        rows = np.ones(jax.tree.leaves(kept[0])[0].shape[
            self._state_axis + 1], np.int32)
        for b, row in req.new_rows.items():
            # (Boundary j of the prefill lies in slot (j - 1) % slots: a kind
            # that builds only the last `_keep` has as many slots.)
            rows[((b - req.prefix_len) // self._every - 1) % len(rows)] = row
        self._dev["rec"], self._ckpt = self._install_state_jit(
            self._dev["rec"], self._ckpt, req.slot, end, kept,
            jnp.asarray(rows), axis=self._state_axis)

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
        if self._cache_form.whole_program and not prefix_len:
            # A whole prompt attends nothing cached, and is given no pages
            # to gather: another program.
            key, pages_row, after = ("whole", Sb), None, None
        if self._pk is None:
            pages_row = None            # no pool: one program a bucket
        if key not in self._prefill_jit:
            cfg, page = self.cfg, self.page
            if cfg.pattern:
                every, keep = self._every, self._keep

                def state_prefill(p, pk, pv, pg, t, pl, n, ckpt, row):
                    return programs._state_prefill_fn(
                        p, pk, pv, pg, t, pl, n, ckpt, row, cfg, page, every,
                        keep=keep)
                self._prefill_jit[key] = jax.jit(state_prefill)
            elif sp:
                mesh = self.mesh

                def sp_suffix_prefill(p, pk, pv, pg, t, pl, n):
                    return self._sp.sp_prefill_fn(
                        p, t, n, cfg, mesh, cached=(pk, pv, pg, pl, page))
                self._prefill_jit[key] = jax.jit(sp_suffix_prefill)
            else:
                kv_shd = self._kv_shd

                def suffix_prefill(p, pk, pv, pg, t, pl, n):
                    return programs._prefill_fn(
                        p, t, n, cfg, kv_shd, cached=(pk, pv, pg, pl, page))
                self._prefill_jit[key] = jax.jit(suffix_prefill)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :S] = suf
        self._count_prefill(S, Sb, after)
        state = (self._ckpt, from_row) if self.cfg.pattern else ()
        return self._prefill_jit[key](
            self.params, self._pk, self._pv,
            # (A copy: the row is a view of `_tables`, which the CPU
            # backend may still be reading after the slot has been freed.)
            None if pages_row is None else jnp.asarray(np.array(pages_row)),
            jnp.asarray(toks), prefix_len, S, *state)

    def _prefill_slot(self, req: _Request):
        """Run a reserved request's prefill and install what it leaves:
        keys and values into its pages, a pattern's recurrent state into
        its slot and the checkpoints it passed into their rows.  Returns
        (last-token logits, the experts every row chose or None)."""
        if not (req.prefix_len or self.cfg.pattern):
            logits, ks, vs = self._run_prefill(req.prompt)
            self._install_pages(self._tables[req.slot], ks, vs)
            return logits, None
        logits, ks, vs, *state = self._run_suffix(
            req.prompt, req.prefix_len, self._tables[req.slot],
            from_row=req.from_row)
        if self._pk is not None:
            self._install_pages(req.pages, ks, vs)
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
            compiled = len(self._prefill_jit)
            t0 = ph.enter("prefill")
            if req.kv_blob is not None:
                self._install_external(req)
            else:
                logits, _ = self._prefill_slot(req)
            ran = {} if req.kv_blob is not None else self._prefill_ran
            if self._every:
                ran = dict(ran, checkpoints=len(req.new_rows),
                           recomputed=req.recomputed, **programs.count(
                               self._counts, "admit",
                               (S - req.prefix_len) // self._every,
                               len(req.new_rows)))
            ph.leave(t0, "prefill", req.req_id.to_bytes(8, "little"),
                     tokens=S, cached_tokens=req.prefix_len,
                     active=active_before, n=ph.n,
                     new_program=len(self._prefill_jit) - compiled, **ran)
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
            self._install_pages(req.pages, ks[:, req.prefix_len:],
                                vs[:, req.prefix_len:])
        else:
            self._install_pages(self._tables[req.slot], ks, vs)

    def _sample_batch(self, logits_list, params_list) -> List[int]:
        """Sample first tokens for a whole admission wave in ONE
        device->host transfer (the previous per-request host pull was a
        blocking sync per request per tick); the sync cost is stamped as
        a `sample_sync` recorder span so the serving harness sees it."""
        t0 = self.phases.enter("sample_sync")
        # (The wave filled up to a power of two with its last row again:
        # the eager programs below are compiled for a wave's size, and a
        # tick that admits a size for the first time waits for them; up to
        # 32 slots that is six sizes, not thirty-two.)
        n = len(logits_list)
        fill = (1 << (n - 1).bit_length()) - n
        lg = jnp.stack(list(logits_list) + list(logits_list[-1:]) * fill)
        temps = np.asarray([p.temperature for p in params_list]
                           + [0.0] * fill, np.float32)
        greedy = jnp.argmax(lg, -1).astype(jnp.int32)
        if (temps > 0).any():
            # The one key stream, shared with the decode step, which
            # splits it on the device: this split's first half goes back
            # into the resident state.
            self._dev["rng"], key = jax.random.split(self._dev["rng"])
            keys = jax.random.split(key, len(temps))
            tj = jnp.asarray(temps)
            sampled = jax.vmap(
                lambda k, l, t: jax.random.categorical(
                    k, l / jnp.maximum(t, 1e-6)))(keys, lg, tj)
            toks = jnp.where(tj > 0, sampled.astype(jnp.int32), greedy)
        else:
            toks = greedy
        out = np.asarray(toks)                            # the one sync
        self.phases.leave(t0, "sample_sync", batch=n)
        return [int(t) for t in out[:n]]

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
        # (After the tokens, what the routed layers touched: `_decode_fn`.)
        extra = programs.count(self._counts, "decode", lengths,
                               nxt[self.max_batch:])
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
            update = jax.device_put(programs._pack_rows(
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
            compiled = len(self._prefill_jit)
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
                     new_program=len(self._prefill_jit) - compiled,
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

    def _stream_layers(self, toks, pos: int, parts, valid: int, tail=None,
                       **fields):
        """Streamed online-softmax attention (layers outer, parts inner —
        the device never holds more than one part): the rows `toks`, at
        absolute position `pos`, attend the external `parts`, then what
        `tail(li)` gives of the pool (k, v, valid, its position) and their
        own first `valid` rows.  Returns (x, ks, vs (L, rows, KV, D)).
        Raises KVGatherError if a part's bytes cannot be gathered."""
        sa = self._stream_attn
        rec = flight_recorder.recorder()
        win = self._kv_window
        b0, w0, f0 = win.bytes_fetched, win.wait_s, win.fetches
        t0 = rec.begin()
        win.prefetch([(p["key"], p["handle"]) for p in parts])
        x = sa.embed(self.params, toks)
        ks, vs = [], []
        for li in range(self.cfg.num_layers):
            q, k, v = sa.qkv(self.params["layers"], li, x, pos)
            m, l, acc = sa.init(toks.shape[1])
            for part in parts:
                pk, pv, n = self._part_layer(part, li)
                m, l, acc = sa.block(q, pk, pv, n, pos, part["span"][0],
                                     m, l, acc)
            if tail is not None:
                tk, tv, n, at = tail(li)
                m, l, acc = sa.block(q, tk, tv, n, pos, at, m, l, acc)
            m, l, acc = sa.block(q, k, v, valid, pos, pos, m, l, acc)
            x = sa.finish(self.params["layers"], li, x, l, acc)
            ks.append(k)
            vs.append(v)
        # The span covers prefetch-kick → last layer; gather_wait_us is
        # the BLOCKING portion (prefetch that got there first shows up
        # as bytes with ~zero wait — the gather/compute overlap signal).
        rec.end("request", "sp:gather", t0, parts=len(parts),
                gather_bytes=win.bytes_fetched - b0,
                gather_wait_us=int((win.wait_s - w0) * 1e6),
                fetches=win.fetches - f0, **fields)
        return x, jnp.stack(ks), jnp.stack(vs)

    def _ext_decode_step(self, req: _Request) -> int:
        """One decode token for a paged-context slot: it attends the
        external parts, the pool-resident decode tail, and itself
        (`_stream_layers`); the new token's KV appends to the tail pages
        in one donated update."""
        S, t = req.ext_len, req.ext_written
        pages_row = jnp.asarray(np.asarray(req.pages, np.int32))

        def tail(li):
            return (*self._tail_gather_jit(self._pk, self._pv, jnp.int32(li),
                                           pages_row), t, S)
        x, ks, vs = self._stream_layers(
            np.asarray([[self._last[req.slot]]], np.int32), S + t,
            req.ext_parts, 1, tail if t > 0 else None,
            id=req.req_id.to_bytes(8, "little"))
        logits = self._stream_attn.logits(self.params, x, 0)
        self._pk, self._pv = self._append_tail_jit(
            self._pk, self._pv, ks[:, 0], vs[:, 0],
            jnp.int32(req.pages[t // self.page]), jnp.int32(t % self.page))
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
        Sc = len(chunk_tokens)
        if not (0 < Sc <= span):
            raise ValueError(f"chunk of {Sc} tokens vs span {span}")
        ctx = self._norm_parts(
            ctx_parts, pos0, f"pf{self._part_seq}") if ctx_parts else []
        self._part_seq += 1
        toks = np.zeros((1, span), np.int32)
        toks[0, :Sc] = chunk_tokens
        x, ks, vs = self._stream_layers(toks, pos0, ctx, Sc,
                                        prefill_chunk=True)
        # The stripe stays DEVICE-RESIDENT: a same-process consumer
        # (chunk c+1 via the window, or a co-located decode engine)
        # attends to it with zero host copies, and publishing it stages
        # exactly once through the serializer's device plane — the old
        # np.asarray here paid a device->host sync per chunk even when
        # nothing ever left the process.
        part = {"k": ks, "v": vs, "len": Sc}
        logits = self._stream_attn.logits(self.params, x, Sc - 1) \
            if is_last else None
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
        handoff to completion."""
        return self._run_to_end(self.add_paged_request(
            handoff["parts"], handoff["len"], handoff["first"], params,
            prompt_tokens=handoff.get("prompt")))

    def _run_to_end(self, rid: int) -> List[int]:
        """Step until request `rid` is done; re-raises its typed error (the
        gather error of a part whose host was lost mid-decode)."""
        while self.has_unfinished():
            for done in self.step():
                if done.req_id == rid:
                    if done.error is not None:
                        raise done.error
                    return done.out
        raise RuntimeError(f"request {rid} was dropped without finishing")

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
                    return programs._decode_logits_fn(
                        p, pk, pv, tb, lt, ln, ac, cfg, page, kv_shd, rec)
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
        return self._run_to_end(self.add_external_request(
            kv_blob, first_token, params, prompt_tokens=prompt_tokens))
