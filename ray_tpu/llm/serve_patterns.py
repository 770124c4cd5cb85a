"""LLM serving patterns on Serve, built on the production serving core.

Reference: python/ray/llm/_internal/serve/serving_patterns/ —
data_parallel/dp_server.py (N identical engine replicas behind the
router) and prefill_decode/pd_server.py (prefill nodes compute the KV
cache, ship it, decode nodes stream tokens).

Every pattern deploys :class:`~ray_tpu.llm.serving.EngineReplica` — the
continuous-batching actor (per-tick admission/retirement, token
streaming, KV-prefix cache, deadline-aware shedding) — instead of a
closed-loop ``generate()`` server:

- ``build_llm_app``: THE production path — autoscaled data-parallel
  replicas (queue-depth × page-occupancy driven, scale-to-zero capable)
  with streaming via ``handle.options(stream=True,
  method_name="stream_generate")``.
- ``build_dp_deployment``: fixed-size data-parallel app (compat
  surface; same replica class).
- ``run_pd_app``: prefill/decode disaggregation — the KV blob rides
  the shared-memory object plane between replicas and enters the decode
  replica through the SAME admission queue as local requests, so
  deadlines and shedding compose.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

from .. import serve
from .serving import EngineReplica


def _replica_options(num_cpus: float, num_tpus: int) -> dict:
    """Actor options of one engine replica.  The engine is one process on
    whole chips: a fraction of a chip is refused here, before anything is
    deployed (the node agent refuses it too, for real chips)."""
    if num_tpus != int(num_tpus) or num_tpus < 0:
        raise ValueError(
            f"num_tpus={num_tpus}: an engine replica holds whole chips "
            "(one process per chip)")
    opts = {"num_cpus": num_cpus}
    if num_tpus:
        opts["resources"] = {"TPU": int(num_tpus)}
    return opts


def build_llm_app(preset: str = "tiny", *, name: Optional[str] = None,
                  min_replicas: int = 0, max_replicas: int = 4,
                  target_load: float = 4.0,
                  downscale_delay_s: float = 10.0,
                  max_batch: int = 4, max_len: int = 128,
                  page_size: int = 16, kv_pages: Optional[int] = None,
                  prefix_cache: bool = True, max_queue: int = 64,
                  max_tokens: int = 16, temperature: float = 0.0,
                  eos_id: Optional[int] = None, seed: int = 0,
                  num_cpus: float = 1.0, num_tpus: int = 0):
    """Autoscaled continuous-batching LLM app.

        handle = serve.run(build_llm_app("tiny"))
        for item in handle.options(
                stream=True, method_name="stream_generate").remote(
                prompt_tokens, {"max_tokens": 64}):
            ...  # int tokens, then {"finish_reason": ...}

    Replica count follows each replica's ``__serve_load__`` (admission
    queue depth × page-pool occupancy): bursts scale 1→N, idle decays to
    ``min_replicas`` (0 = scale-to-zero; router demand revives it)."""
    dep = serve.deployment(
        EngineReplica, name=name or f"llm-{preset}",
        ray_actor_options=_replica_options(num_cpus, num_tpus),
        autoscaling_config={
            "min_replicas": min_replicas,
            "max_replicas": max_replicas,
            "target_ongoing_requests": target_load,
            "upscale_delay_s": 0.0,
            "downscale_delay_s": downscale_delay_s,
        })
    return dep.bind(preset, max_batch=max_batch, max_len=max_len,
                    page_size=page_size, kv_pages=kv_pages,
                    prefix_cache=prefix_cache, max_queue=max_queue,
                    max_tokens=max_tokens, temperature=temperature,
                    eos_id=eos_id, seed=seed)


def build_dp_deployment(preset: str = "tiny", *, num_replicas: int = 1,
                        max_batch: int = 4, max_len: int = 128,
                        max_tokens: int = 16, temperature: float = 0.0,
                        eos_id: Optional[int] = None, seed: int = 0,
                        num_cpus: float = 1.0, num_tpus: int = 0,
                        prefix_cache: bool = True,
                        page_size: int = 16):
    """Fixed-size data-parallel LLM app: `serve.run(build_dp_deployment
    (...))`.  Each replica is a full continuous-batching engine —
    concurrent requests to one replica batch per decode tick instead of
    queueing behind a closed-loop generate call."""
    dep = serve.deployment(
        EngineReplica, name=f"llm-{preset}", num_replicas=num_replicas,
        ray_actor_options=_replica_options(num_cpus, num_tpus))
    return dep.bind(preset, max_batch=max_batch, max_len=max_len,
                    max_tokens=max_tokens, temperature=temperature,
                    eos_id=eos_id, seed=seed, prefix_cache=prefix_cache,
                    page_size=page_size)


class _PDIngress:
    """Front door chaining prefill → decode handles (reference:
    pd_server.py PDProxyServer).

    ``direct=True`` (default): the prefill replica returns a HANDOFF —
    the KV blob stays pinned in the prefill replica's arena and only its
    20-byte ref transits this proxy; the decode replica resolves the ref
    itself, pulling the pages arena-to-arena via the owner's replica
    directory (PR-5 location hints).  One transfer, zero blob bytes
    through the proxy process.

    ``direct=False`` (legacy A/B reference): the blob travels BY VALUE —
    prefill → proxy → decode, two object-plane transfers with the proxy
    materializing every byte.  Kept so the TTFT win is measurable
    (tests/test_pd_compiled.py A/Bs both modes).

    Either way the decode half enters the remote admission queue
    (deadline-aware, shed-bounded) and the real prompt tokens ride along
    so the decode replica's prefix cache learns the prompt."""

    def __init__(self, prefill_name: str, decode_name: str,
                 direct: bool = True):
        self.prefill = serve.get_deployment_handle(prefill_name)
        self.decode = serve.get_deployment_handle(decode_name)
        self.direct = direct

    async def __call__(self, prompt_tokens: Sequence[int],
                       max_tokens: int = 16, temperature: float = 0.0,
                       eos_id: Optional[int] = None) -> List[int]:
        opts = {"max_tokens": max_tokens, "temperature": temperature,
                "eos_id": eos_id}
        prompt = list(prompt_tokens)
        if self.direct:
            handoff = await self.prefill.prefill_handoff.remote(
                {"prompt": prompt, "opts": opts})
            res = await self.decode.decode_handoff.remote(handoff)
        else:
            blob, first = await self.prefill.prefill.remote(prompt, opts)
            res = await self.decode.decode.remote(blob, first, opts,
                                                  prompt)
        return res["tokens"]


def run_pd_app(preset: str = "tiny", *, prefill_replicas: int = 1,
               decode_replicas: int = 1, max_batch: int = 4,
               max_len: int = 128, seed: int = 0,
               prefix_cache: bool = True, direct: bool = True,
               name: Optional[str] = None):
    """Deploy the three-deployment P/D app; returns the ingress handle.
    Prefill and decode scale independently — the point of the pattern."""
    tag = name or preset
    serve.run(serve.deployment(
        EngineReplica, name=f"pd-prefill-{tag}",
        num_replicas=prefill_replicas).bind(
            preset, max_batch=1, max_len=max_len, seed=seed,
            prefix_cache=prefix_cache),
        name=f"pd-prefill-{tag}")
    serve.run(serve.deployment(
        EngineReplica, name=f"pd-decode-{tag}",
        num_replicas=decode_replicas).bind(
            preset, max_batch=max_batch, max_len=max_len, seed=seed,
            prefix_cache=prefix_cache),
        name=f"pd-decode-{tag}")
    return serve.run(serve.deployment(
        _PDIngress, name=f"pd-ingress-{tag}").bind(
            f"pd-prefill-{tag}", f"pd-decode-{tag}", direct),
        name=f"pd-ingress-{tag}")


class CompiledPDApp:
    """P/D disaggregation over a COMPILED actor pipeline — the flagship
    aDAG workload (reference: Ray LLM pd_server.py + Compiled Graphs).

    N prefill + M decode ``EngineReplica`` actors; each prefill is
    bound to a decode in a compiled two-stage DAG::

        (prompt, opts) ─ring→ prefill_handoff ─ring→ admit_external → rid

    Steady-state request dispatch therefore does NO per-request GCS or
    owner RPCs: the request rides the input ring and the KV pages ride
    the compiled channel itself — written once into the prefill node's
    arena by the ring's spill path, shipped arena-to-arena by the agent
    bridge when the pair spans nodes, reclaimed by last-reader delete
    (no ownership bookkeeping at all).  Admission is the DAG step — decode runs
    in the replica's continuous batch, so consecutive requests pipeline
    through prefill while earlier ones decode — and tokens stream back
    over the existing worker→owner stream frames (zero GCS work per
    token; pinned by test).

    Static by design: compiled graphs pre-resolve placement, so replica
    counts are fixed at build time.  For queue-driven autoscaling use
    ``build_llm_app`` / ``run_pd_app`` — this class is the peak-
    throughput, lowest-TTFT deployment for a known fleet size."""

    def __init__(self, preset: str = "tiny", *, prefill_replicas: int = 1,
                 decode_replicas: int = 1, max_batch: int = 4,
                 max_len: int = 128, page_size: int = 16, seed: int = 0,
                 prefix_cache: bool = True, max_queue: int = 64,
                 max_inflight: int = 8,
                 prefill_options: Optional[dict] = None,
                 decode_options: Optional[dict] = None):
        import threading

        import ray_tpu
        from ..dag import InputNode

        Rep = ray_tpu.remote(EngineReplica)
        self.prefills = [
            Rep.options(**(prefill_options or {})).remote(
                preset, max_batch=1, max_len=max_len,
                page_size=page_size, seed=seed,
                prefix_cache=prefix_cache, max_queue=max_queue)
            for _ in range(prefill_replicas)]
        self.decodes = [
            Rep.options(**(decode_options or {})).remote(
                preset, max_batch=max_batch, max_len=max_len,
                page_size=page_size, seed=seed,
                prefix_cache=prefix_cache, max_queue=max_queue)
            for _ in range(decode_replicas)]
        # One compiled pair-DAG per (prefill, decode) lane; requests
        # round-robin across lanes.  More decode than prefill replicas
        # (or vice versa) is the point of disaggregation — the lanes
        # cover every replica of the larger side.
        lanes = max(prefill_replicas, decode_replicas)
        self._lanes = []
        for i in range(lanes):
            p = self.prefills[i % prefill_replicas]
            d = self.decodes[i % decode_replicas]
            with InputNode() as inp:
                dag = d.admit_external.bind(
                    p.prefill_handoff_channel.bind(inp))
            self._lanes.append(
                (dag.experimental_compile(
                    _max_inflight_executions=max_inflight), d))
        self._rr = 0
        self._rr_lock = threading.Lock()
        self.num_replicas = decode_replicas

    def _next_lane(self):
        with self._rr_lock:
            lane = self._lanes[self._rr % len(self._lanes)]
            self._rr += 1
        return lane

    def generate(self, prompt_tokens: Sequence[int],
                 opts: Optional[dict] = None,
                 timeout: float = 120.0) -> dict:
        """Blocking completion: {"tokens": [...], "finish_reason": ...}."""
        import ray_tpu
        compiled, decode = self._next_lane()
        rid = compiled.execute(
            {"prompt": list(prompt_tokens), "opts": opts or {}}
        ).get(timeout=timeout)
        return ray_tpu.get(decode.collect.remote(rid), timeout=timeout)

    def stream(self, prompt_tokens: Sequence[int],
               opts: Optional[dict] = None, timeout: float = 120.0):
        """Generator of int tokens then one terminal dict — the
        run_open_loop submit contract."""
        import ray_tpu
        compiled, decode = self._next_lane()
        rid = compiled.execute(
            {"prompt": list(prompt_tokens), "opts": opts or {}}
        ).get(timeout=timeout)
        gen = decode.collect_stream.options(
            num_returns="streaming").remote(rid)
        for item_ref in gen:
            yield ray_tpu.get(item_ref, timeout=timeout)

    def shutdown(self) -> None:
        import ray_tpu
        for compiled, _ in self._lanes:
            try:
                compiled.teardown()
            except Exception:
                pass
        for h in self.prefills + self.decodes:
            try:
                ray_tpu.kill(h)
            except Exception:
                pass


def run_pd_compiled(preset: str = "tiny", **kwargs) -> CompiledPDApp:
    """Build the compiled P/D deployment (see :class:`CompiledPDApp`)."""
    return CompiledPDApp(preset, **kwargs)


class LongContextApp:
    """Long-context serving: N sequence-parallel prefill shards +
    cross-host paged KV decode — the million-token-context deployment
    shape (the capability the reference Ray does not have, SURVEY.md
    §5.7: it only orchestrates SPMD programs that implement SP
    themselves).

    Prefill: the prompt is cut into ``span``-token chunks and
    round-robined across N shard replicas.  Chunk c's queries attend to
    the c already-published parts (ring order is the causal order, so
    the online-softmax accumulation is exact — Liu et al. 2023) pulled
    through each shard's bounded gather window, and its own KV stripe
    is published into THAT shard's node arena; only 20-byte refs flow
    back.  Each shard can additionally run its intra-chunk attention
    sequence-parallel (``sp_degree`` > 1, ring/Ulysses over its local
    devices).  The handoff is the union of every shard's stripes — N
    prefill shards hand off to one decode replica without the proxy or
    owner ever touching KV bytes.

    Decode: :meth:`~ray_tpu.llm.serving.EngineReplica.admit_paged` — the
    context stays in the shard arenas (the page-table location tier);
    the decode replica streams attention over the parts through its
    prefetch window (gather overlaps compute) and only the decode tail
    occupies its local pool.  A context larger than ANY single node's
    page pool — or arena — still serves.

    Failure: losing a shard (or its node) mid-decode fails the affected
    streams typed (`StreamBrokenError` carrying ``tokens_emitted``,
    cause-chained `KVGatherError`); pages and window state reclaim
    immediately and other requests keep decoding."""

    def __init__(self, preset: str = "tiny", *, prefill_shards: int = 2,
                 decode_replicas: int = 1, span: int = 64,
                 max_batch: int = 2, max_len: int = 128,
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 kv_gather_window: int = 4,
                 sp_degree: Optional[int] = None,
                 sp_strategy: str = "ring", max_tokens: int = 16,
                 seed: int = 0, prefill_options: Optional[dict] = None,
                 decode_options: Optional[dict] = None):
        import threading

        import ray_tpu
        Rep = ray_tpu.remote(EngineReplica)
        self.span = int(span)
        # Shards never admit decode requests — their pool only backs the
        # prefix cache / scratch, so kv_pages can be tiny.
        self.shards = [
            Rep.options(**(prefill_options or {})).remote(
                preset, max_batch=1, max_len=max_len,
                page_size=page_size, kv_pages=kv_pages,
                prefix_cache=False, sp_degree=sp_degree,
                sp_strategy=sp_strategy, paged_span=span,
                kv_gather_window=kv_gather_window, seed=seed)
            for _ in range(prefill_shards)]
        self.decodes = [
            Rep.options(**(decode_options or {})).remote(
                preset, max_batch=max_batch, max_len=max_len,
                page_size=page_size, kv_pages=kv_pages,
                prefix_cache=False, max_tokens=max_tokens,
                kv_gather_window=kv_gather_window, seed=seed)
            for _ in range(decode_replicas)]
        self._rr = 0
        self._rr_lock = threading.Lock()
        self.num_replicas = decode_replicas

    def _next_decode(self):
        with self._rr_lock:
            d = self.decodes[self._rr % len(self.decodes)]
            self._rr += 1
        return d

    def prefill(self, prompt_tokens: Sequence[int],
                opts: Optional[dict] = None,
                timeout: float = 120.0) -> dict:
        """Run the sharded paged prefill; returns the decode handoff
        ``{"parts": [{"span", "handle"}], "len", "first", "opts"}``.
        Chunks are sequential by causality (chunk c attends to parts
        0..c-1) but stripe STORAGE is spread across every shard's node —
        the property the cluster test pins."""
        import ray_tpu
        prompt = list(prompt_tokens)
        S = len(prompt)
        n = max(1, -(-S // self.span))
        parts: List[dict] = []
        first = None
        for c in range(n):
            shard = self.shards[c % len(self.shards)]
            res = ray_tpu.get(shard.prefill_paged_chunk.remote({
                "chunk": prompt[c * self.span:(c + 1) * self.span],
                "pos0": c * self.span, "parts": parts,
                "span": self.span, "is_last": c == n - 1,
                "opts": opts or {}}), timeout=timeout)
            parts.append({"span": res["span"], "handle": res["handle"]})
            first = res.get("first", first)
        return {"parts": parts, "len": S, "first": int(first),
                "opts": opts or {}}

    def generate(self, prompt_tokens: Sequence[int],
                 opts: Optional[dict] = None,
                 timeout: float = 120.0) -> dict:
        """Blocking completion: {"tokens": [...], "finish_reason": ...}."""
        import ray_tpu
        handoff = self.prefill(prompt_tokens, opts, timeout)
        dec = self._next_decode()
        return ray_tpu.get(dec.decode_paged.remote(handoff),
                           timeout=timeout)

    def stream(self, prompt_tokens: Sequence[int],
               opts: Optional[dict] = None, timeout: float = 120.0):
        """Generator of int tokens then one terminal dict — the
        run_open_loop submit contract.  Mid-decode KV loss raises
        StreamBrokenError out of the iteration, typed."""
        import ray_tpu
        handoff = self.prefill(prompt_tokens, opts, timeout)
        dec = self._next_decode()
        rid = ray_tpu.get(dec.admit_paged.remote(handoff),
                          timeout=timeout)
        gen = dec.collect_stream.options(
            num_returns="streaming").remote(rid)
        for item_ref in gen:
            yield ray_tpu.get(item_ref, timeout=timeout)

    def debug_stats(self, timeout: float = 30.0) -> dict:
        import ray_tpu
        return {"shards": ray_tpu.get(
                    [s.debug_stats.remote() for s in self.shards],
                    timeout=timeout),
                "decodes": ray_tpu.get(
                    [d.debug_stats.remote() for d in self.decodes],
                    timeout=timeout)}

    def shutdown(self) -> None:
        import ray_tpu
        for h in self.shards + self.decodes:
            try:
                ray_tpu.kill(h)
            except Exception:
                pass


def run_long_context_app(preset: str = "tiny", **kwargs) -> LongContextApp:
    """Build the sharded long-context deployment (see
    :class:`LongContextApp`)."""
    return LongContextApp(preset, **kwargs)
