"""A serving cell, driver side: deploy the replica, warm the cell's own
shapes, check correctness, offer the traffic, and collect what the readers
need.  Every clock here is the client's.

Phases (all but the window are set-up): runtime and replica ready -> warm-up
-> correctness check -> ramp -> WINDOW (`--seconds`) -> drain (`drain_s`:
load goes on, nothing new is counted except the first tokens and gaps of
requests that were due inside the window) -> with `--trace 1`, a traced
stretch under the same load -> stop.
"""

from __future__ import annotations

import concurrent.futures
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import cluster, traffic

APP = "bench-llm"
STREAM = dict(stream=True, method_name="stream_generate")


# ------------------------------------------------------- one request ------

def _offer(handle, req: traffic.Request, due_wall: float, stop,
           records: List[dict], lock, at_token=None) -> None:
    """Send one request and stamp every token's arrival (client clock).
    `at_token` = (n, event): set the event when the n-th token is here, or
    when the stream ends without it."""
    rec: Dict[str, Any] = {
        "index": req.index, "phase": req.phase, "due": due_wall,
        "sent": time.time(), "prompt_len": req.prompt_len,
        "asked": req.output_len, "doc": req.doc, "token_times": [],
        "finish": None, "error": None, "cut": False}
    with lock:
        records.append(rec)
    stream = handle.options(**STREAM).remote(
        req.tokens, {"max_tokens": req.output_len})
    try:
        for item in stream:
            if isinstance(item, dict):
                rec["finish"] = item
                break
            rec["token_times"].append(time.time())
            if at_token and len(rec["token_times"]) == at_token[0]:
                at_token[1].set()
            if stop.is_set():
                rec["cut"] = True
                stream.cancel()
                break
    except Exception as e:              # the harness reports, never dies
        rec["error"] = repr(e)
    if at_token:
        at_token[1].set()
    rec["end"] = time.time()


def _closed_loop(handle, reqs, callers: int, stagger_tokens: int, stop,
                 records, lock) -> List[threading.Thread]:
    """`callers` threads over one list: each takes the list's next request
    when its last answer ends, so a request is due when it is sent.

    With equal output lengths a caller keeps its PHASE for the whole run:
    the tick, modulo the ticks a request takes, in which it is admitted.
    Callers that start together, or while another's whole-prompt prefill
    holds the engine, are admitted in one tick or in neighbouring ones and
    stay so: every miss of one then waits for, or stalls, the other, and
    which pairs stick is decided by a millisecond in the ramp (PERF.md §6,
    PR 29).  So the phases are laid down by the engine's own clock: caller
    i+1 sends its first request when caller i's first answer has its
    `stagger_tokens`-th token, which puts the callers that many ticks (and
    a round trip) apart whatever a tick lasts."""
    it = iter(reqs)
    go = [threading.Event() for _ in range(callers + 1)]
    go[0].set()

    def caller(i: int):
        while not go[i].wait(0.05):
            if stop.is_set():
                return
        first = True
        while not stop.is_set():
            with lock:
                req = next(it, None)
            if req is None:
                break
            _offer(handle, req, time.time(), stop, records, lock,
                   (min(stagger_tokens, req.output_len), go[i + 1])
                   if first else None)
            first = False
        go[i + 1].set()

    threads = [threading.Thread(target=caller, args=(i,), daemon=True)
               for i in range(callers)]
    for t in threads:
        t.start()
    return threads


def _open_loop(handle, reqs, t0: float, stop, records, lock,
               pool: concurrent.futures.ThreadPoolExecutor
               ) -> threading.Thread:
    """One scheduler thread: each request is handed to the pool when it is
    due, whether or not earlier ones have finished."""
    def schedule():
        for req in reqs:
            delay = t0 + req.due - time.time()
            if (delay > 0 and stop.wait(delay)) or stop.is_set():
                return
            pool.submit(_offer, handle, req, t0 + req.due, stop, records,
                        lock)

    t = threading.Thread(target=schedule, daemon=True)
    t.start()
    return t


# ------------------------------------------------------------ phases ------

def _warm(handle, replica, spec, engine, vocab: int, seed: int
          ) -> Dict[str, Any]:
    """Reach every program the window can: one prompt per prefill bucket,
    one prefix-sharing prompt per suffix bucket, all decoding together."""
    rng = np.random.default_rng([seed, 4])
    shapes = traffic.warm_shapes(spec, engine["max_len"])
    room = engine["max_len"] - 4        # prompt + 2 tokens < max_len
    page = engine["page_size"]
    base = rng.integers(1, vocab, page).tolist()     # one shared page
    prompts = [rng.integers(1, vocab, min(b, room)).tolist()
               for b in shapes["prefill"]]
    suffixes = [base + rng.integers(1, vocab, min(b, room - page)).tolist()
                for b in shapes["suffix"]]
    t0 = time.time()

    def one(p):
        return [x for x in handle.options(**STREAM).remote(
            p, {"max_tokens": 2})]
    if suffixes:
        one(base + rng.integers(1, vocab, 4).tolist())   # seeds the page
    with concurrent.futures.ThreadPoolExecutor(16) as pool:
        for wave in (prompts, suffixes):
            done = list(pool.map(one, wave))
            if any(len(d) != 3 for d in done):
                raise RuntimeError(f"warm-up stream ended wrong: {done}")
    cluster.ask(replica, "bench_warm_sampler")
    return {"shapes": shapes, "seconds": time.time() - t0}


def _check(handle, replica, spec, vocab: int, seed: int) -> Dict[str, Any]:
    """The same seeded prompt twice through the normal path — cold, then as
    a prefix-cache hit — and the replica holds both to the reference."""
    rng = np.random.default_rng([seed, 5])
    prompt = rng.integers(1, vocab, spec["check_prompt_len"]).tolist()
    opts = {"max_tokens": spec["check_output_tokens"]}
    served, hits = [], []
    for _ in range(2):
        items = list(handle.options(**STREAM).remote(prompt, opts))
        served.append([int(x) for x in items[:-1]])
        if items[-1].get("finish_reason") != "length" \
                or len(served[-1]) != opts["max_tokens"]:
            raise RuntimeError(f"check stream ended wrong: {items[-1]}")
        hits.append(cluster.ask(replica, "debug_stats")
                    ["prefix_cache"].get("hits", 0))
    report = cluster.ask(replica, "bench_check", prompt, served)
    report["cache_hit_on_second"] = hits[1] - hits[0] == 1
    report["ok"] = bool(report["ok"] and report["cache_hit_on_second"])
    return report


def run(cell: Dict[str, Any], *, seed: int, seconds: float, traced: bool,
        out_dir: str, t_proc: float, require_tpu: bool = True,
        rate_hz: Optional[float] = None
        ) -> Dict[str, Any]:
    """One run of a serving cell.  Returns the context the readers and the
    end-to-end arithmetic read (benchmark/run.py)."""
    from ray_tpu import serve

    from .replica import BenchReplica

    config, spec = cell["config"], cell["traffic"]
    engine = dict(spec["engine"])
    vocab = config["vocab_size"]
    reqs = traffic.requests(spec, seed, seconds, rate_hz)
    traffic.fill_tokens(reqs, seed, vocab)
    ctx: Dict[str, Any] = {"kind": spec["kind"], "engine": engine,
                           "seconds": seconds}

    with cluster.runtime(
            cell["chips"], out_dir, require_tpu,
            config["deployment"].get("runtime_config")) as rt:
        ctx["init_s"] = time.time() - t_proc    # runtime up; detail only
        dep = serve.deployment(
            BenchReplica, name=APP, num_replicas=1,
            ray_actor_options={"num_cpus": 1.0, **(
                {"resources": {"TPU": cell["chips"]}} if require_tpu
                else {})})
        handle = serve.run(dep.bind(
            config, seed=seed, prefix_cache=True, **engine), name=APP)
        replica = cluster.replicas(APP)[0]
        device = cluster.ask(replica, "device_info")
        if require_tpu:
            cluster.require_tpu("EngineReplica", device, cell["chips"])
        ctx["ready_s"] = time.time() - t_proc
        ctx["warm"] = _warm(handle, replica, spec, engine, vocab, seed)
        ctx["check"] = _check(handle, replica, spec, vocab, seed)

        records: List[dict] = []
        lock, stop = threading.Lock(), threading.Event()
        pool = concurrent.futures.ThreadPoolExecutor(64)
        t_ramp = time.time()
        if spec["kind"] == "closed":
            workers = _closed_loop(handle, reqs, spec["callers"],
                                   spec["stagger_tokens"], stop, records,
                                   lock)
        else:
            workers = [_open_loop(handle, reqs, t_ramp, stop, records,
                                  lock, pool)]
        time.sleep(max(0.0, t_ramp + spec["ramp_s"] - time.time()))
        compiles0 = cluster.ask(replica, "device_info")["compile_cache"]
        ctx["stats_before"] = cluster.ask(replica, "debug_stats")
        t0 = time.time()                        # the window opens
        time.sleep(max(0.0, t0 + seconds - time.time()))
        t1 = time.time()                        # the window closes
        ctx["setup_s"] = t0 - t_proc
        ctx["stats_after"] = cluster.ask(replica, "debug_stats")
        time.sleep(spec["drain_s"])
        t_cut = time.time()
        if traced:
            ctx["trace"] = cluster.ask(
                replica, "bench_trace", float(spec["trace_s"]), out_dir,
                20000, timeout_s=300)
        stop.set()
        for w in workers:
            w.join(30)
        pool.shutdown(wait=True, cancel_futures=True)
        device = cluster.ask(replica, "device_info")
        ctx["compiles_in_window"] = device["compile_cache"]["requests"] \
            - compiles0["requests"]
        ctx["device"] = device
        ctx["memory"] = cluster.ask(replica, "bench_memory")["stats"]
        time.sleep(1.2)                 # one telemetry flush of the spans
        ctx["spans"] = cluster.request_spans(t0, t1)
        ctx["list_exhausted"] = spec["kind"] == "closed" \
            and len(records) >= len(reqs)
        ctx["offered"] = len(reqs)
        serve.delete(APP)
    ctx.update(window=[t0, t1], cut=t_cut, records=records,
               session=rt.session_dir)
    return ctx
