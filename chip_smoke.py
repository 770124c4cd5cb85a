"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the runtime's main path once, through the entry points a user calls,
from a driver that never initialises a JAX backend:

  train:  ray_tpu.init() -> JaxTrainer(use_tpu=True) -> one TrainWorker actor
          holds the host's chips and takes optimizer steps with
          make_train_step on a configuration sized for a 16 GB chip
          (hidden 2048, 16 heads x 128, 10 layers, batch 8 x 2048, bf16,
          Pallas flash attention), reporting through train.report.
  serve:  as soon as that worker has exited, serve.run(build_llm_app("1b",
          num_tpus=1, ...)) -> streamed requests through
          handle.options(stream=True, method_name="stream_generate").

There is no CPU path here: no TPU in the worker, or any phase failing, is a
non-zero exit with no result line.  Tests rehearse the phase functions at
the `tiny` preset (tests/test_chip_smoke.py).  On a four-chip host the same
command shards the train step fsdp=2 x tp=2 over the four chips and serves
from four one-chip replicas.

Stdout: a few summary lines, then ONE JSON object as the last line.  Detail
goes to --out (default chiprun_out/chip_smoke/).
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import sys
import time
from typing import Any, Dict, List, Sequence, Union

# Sized for a 16 GB chip with its optimizer state.
MODEL = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5504,
             num_layers=10, num_heads=16, num_kv_heads=16, max_seq_len=2048)
# Mesh of the one TrainWorker, by the number of chips it holds.
MESHES = {1: {}, 4: {"fsdp": 2, "tp": 2}}
APP = "llm-smoke"
# Prompt lengths per wave of concurrent requests.  Wave 2 repeats wave 1's
# first prompt (a prefix-cache hit); wave 3 repeats wave 2, so nothing in it
# compiles.  300 and 908 leave the same 12-token suffix past their last full
# 16-token page, so both hits share one compiled suffix bucket.
WAVES = ((300, 420, 1500), (300, 908), (300, 908))


# --------------------------------------------------------------- train ----

def train_loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: runs in the TrainWorker, the process that
    holds the chips."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu._private.compile_cache import enable_compile_cache
    from ray_tpu.models import PRESETS, TransformerConfig, make_train_step
    from ray_tpu.models.train_step import make_optimizer
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import shard_batch
    from ray_tpu.tpu.accelerator import device_report

    enable_compile_cache()
    model = config["model"]
    cfg = PRESETS[model] if isinstance(model, str) \
        else TransformerConfig(**model)
    cfg = dataclasses.replace(cfg, attention_impl="flash")
    mesh = build_mesh(MeshSpec(**config["mesh"]))
    # A fixed batch and a one-step warm-up (as train/examples/
    # transformer_example.py sets): the default schedule's 100-step warm-up
    # barely moves in a handful of steps.
    bundle = make_train_step(cfg, mesh, optimizer=make_optimizer(
        learning_rate=config["lr"], warmup_steps=1, decay_steps=10_000))

    t0 = time.perf_counter()
    state = jax.block_until_ready(bundle.init(jax.random.key(0)))
    init_s = time.perf_counter() - t0
    batch = shard_batch({"tokens": jnp.asarray(
        np.random.default_rng(0).integers(
            1, cfg.vocab_size, (config["batch"], config["seq"] + 1)),
        jnp.int32)}, mesh)
    pallas = "tpu_custom_call" in bundle.step.lower(state, batch).as_text()

    for i in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = bundle.step(state, batch)
        loss = float(metrics["loss"])       # host read-back: step is done
        train.report({"step": i, "loss": loss,
                      "grad_norm": float(metrics["grad_norm"]),
                      "step_s": time.perf_counter() - t0})

    params = jax.tree.leaves(state["params"])
    by_device: Dict[int, int] = {}
    for leaf in params:
        for shard in leaf.addressable_shards:
            by_device[shard.device.id] = \
                by_device.get(shard.device.id, 0) + shard.data.nbytes
    train.report({"done": True, "device": device_report(),
                  "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
                  "init_s": init_s, "pallas_in_step": pallas,
                  "param_bytes": sum(leaf.nbytes for leaf in params),
                  "param_bytes_by_device": by_device})


def train_phase(model: Union[str, Dict[str, Any]], *, batch: int, seq: int,
                steps: int, devices: int, use_tpu: bool, out_dir: str,
                lr: float = 1e-3) -> Dict[str, Any]:
    """JaxTrainer -> one TrainWorker on `devices` chips (use_tpu) or
    virtual CPU devices (the rehearsal) -> `steps` optimizer steps.
    Returns the worker's report; raises unless every step's loss is finite,
    the last is lower than the first, and the parameters are spread evenly
    over every device of the mesh."""
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config={"model": model, "batch": batch, "seq": seq,
                           "steps": steps, "lr": lr,
                           "mesh": MESHES[devices]},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=use_tpu,
            resources_per_worker=None if use_tpu else {"CPU": 1.0}),
        jax_config=JaxConfig(use_tpu=use_tpu,
                             cpu_devices_per_process=devices),
        run_config=RunConfig(name="chip_smoke_train",
                             storage_path=os.path.join(out_dir, "train")),
    ).fit()
    if result.error:
        raise RuntimeError(f"train phase failed:\n{result.error}")
    reports = result.metrics_history
    final, step_reports = reports[-1], reports[:-1]
    losses = [r["loss"] for r in step_reports]
    if not final.get("done") or len(losses) != steps:
        raise RuntimeError(f"train phase: {len(losses)}/{steps} steps "
                           f"reported, final={final}")
    if not all(l == l and abs(l) != float("inf") for l in losses):
        raise RuntimeError(f"train phase: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train phase: loss did not fall: {losses}")
    held = final["param_bytes_by_device"]
    if len(held) != devices or \
            max(held.values()) * devices > 1.1 * final["param_bytes"]:
        raise RuntimeError(
            f"train phase: parameters are not spread over {devices} "
            f"devices: {held} of {final['param_bytes']} bytes")
    return {**final, "steps": steps, "losses": losses,
            "first_step_s": step_reports[0]["step_s"],
            "steady_step_s": [r["step_s"] for r in step_reports[1:]],
            "wall_s": time.perf_counter() - t0}


# --------------------------------------------------------------- serve ----

def _replicas(name: str) -> List[Any]:
    """The deployment's replica actors, from the controller's routing
    table (what serve.run itself waits on)."""
    import ray_tpu
    from ray_tpu.serve._private.controller import CONTROLLER_NAME
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.get_routing_table.remote(name, -1, 0.0),
                       timeout=60)["replicas"]


def _ask_replicas(replicas: Sequence[Any], method: str,
                  timeout_s: float = 900.0) -> List[Any]:
    import ray_tpu
    return ray_tpu.get([r.handle_request.remote(method, (), {})
                        for r in replicas], timeout=timeout_s)


def _stream(handle, prompt: List[int], max_tokens: int) -> Dict[str, Any]:
    """One streamed request through the handle: int tokens, then the finish
    record.  Raises unless the stream ends with that record and carries
    exactly `max_tokens` tokens."""
    t0 = time.perf_counter()
    first_s = None
    tokens: List[int] = []
    finish = None
    for item in handle.options(
            stream=True, method_name="stream_generate").remote(
            prompt, {"max_tokens": max_tokens}):
        if finish is not None:
            raise RuntimeError(f"item after the finish record: {item!r}")
        if isinstance(item, dict):
            finish = item
        else:
            if first_s is None:
                first_s = time.perf_counter() - t0
            tokens.append(int(item))
    if finish is None or finish.get("n_tokens") != len(tokens) \
            or len(tokens) != max_tokens:
        raise RuntimeError(
            f"stream of a {len(prompt)}-token prompt ended with "
            f"finish={finish} after {len(tokens)} tokens "
            f"(wanted {max_tokens})")
    return {"prompt_len": len(prompt), "tokens": len(tokens),
            "finish_reason": finish["finish_reason"],
            "first_token_s": first_s, "total_s": time.perf_counter() - t0}


def serve_phase(preset: str, *, num_tpus: int, num_replicas: int,
                max_len: int, max_batch: int, waves: Sequence[Sequence[int]],
                max_tokens: int, page_size: int = 16) -> Dict[str, Any]:
    """serve.run(build_llm_app(...)) -> waves of concurrent streamed
    requests.  Returns per-replica device reports and engine counters;
    raises unless every stream ends right, no replica was restarted, and
    (one replica) a decode tick batched and the prefix cache was hit."""
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.llm.serve_patterns import build_llm_app
    from ray_tpu.models import PRESETS

    t0 = time.perf_counter()
    handle = serve.run(build_llm_app(
        preset, name=APP, num_tpus=num_tpus, min_replicas=num_replicas,
        max_replicas=num_replicas, max_len=max_len, max_batch=max_batch,
        max_tokens=max_tokens, page_size=page_size), name=APP)
    try:
        replicas = _replicas(APP)
        while len(replicas) < num_replicas:     # scale-up runs async
            if time.perf_counter() - t0 > 60:
                raise RuntimeError(f"{len(replicas)}/{num_replicas} "
                                   "replicas in the routing table after 60s")
            time.sleep(0.2)
            replicas = _replicas(APP)
        before = _ask_replicas(replicas, "device_info")
        startup_s = time.perf_counter() - t0

        rng = np.random.default_rng(0)
        vocab = PRESETS[preset].vocab_size
        prompts: Dict[int, List[int]] = {}      # by length: repeats repeat
        wave_reports = []
        for lens in waves:
            for n in lens:
                if n not in prompts:
                    prompts[n] = rng.integers(1, vocab, n).tolist()
            compiles = sum(d["compile_cache"]["requests"]
                           for d in _ask_replicas(replicas, "device_info"))
            tw = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(len(lens)) as pool:
                futs = [pool.submit(_stream, handle, prompts[n], max_tokens)
                        for n in lens]
                streams = [f.result() for f in futs]
            wave_reports.append({
                "wall_s": time.perf_counter() - tw, "streams": streams,
                "compiles": sum(
                    d["compile_cache"]["requests"] for d in
                    _ask_replicas(replicas, "device_info")) - compiles})

        after = _ask_replicas(replicas, "device_info")
        stats = _ask_replicas(replicas, "debug_stats")
        n_requests = sum(len(lens) for lens in waves)
        if [d["pid"] for d in after] != [d["pid"] for d in before] or \
                [r._actor_id for r in _replicas(APP)] != \
                [r._actor_id for r in replicas]:
            raise RuntimeError("a replica was restarted during the phase: "
                               f"{before} -> {after}")
        if sum(s["completed"] for s in stats) != n_requests:
            raise RuntimeError(f"{n_requests} requests sent, engine "
                               f"counters say {stats}")
        hits = sum(s["prefix_cache"]["hits"] for s in stats)
        if num_replicas == 1 and (stats[0]["max_active"] < 2 or hits < 1):
            raise RuntimeError(
                "no batched decode tick or no prefix-cache hit: "
                f"max_active={stats[0]['max_active']} hits={hits}")
        return {"replicas": after, "startup_s": startup_s,
                "waves": wave_reports, "requests": n_requests,
                "tokens_out": sum(s["tokens_out"] for s in stats),
                "max_active": max(s["max_active"] for s in stats),
                "prefix_cache_hits": hits, "engine": stats,
                "wall_s": time.perf_counter() - t0}
    finally:
        serve.delete(APP)


# ---------------------------------------------------------------- main ----

def _keep(session_dir: str, out: str, sub: str) -> None:
    """Copy a session sub-directory (worker logs, the black-box bundles
    anomaly detectors captured) to the output directory."""
    src = os.path.join(session_dir, sub)
    if os.path.isdir(src):
        shutil.copytree(src, os.path.join(out, sub), dirs_exist_ok=True)


def _adopt_orphans() -> None:
    """Make this process the reaper of all its descendants, so a worker
    whose agent died is re-parented here — where _stop_descendants finds
    it — and not to init."""
    import ctypes
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(36, 1, 0, 0, 0) != 0:         # PR_SET_CHILD_SUBREAPER
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_CHILD_SUBREAPER)")


def _stop_descendants(grace_s: float = 10.0) -> List[int]:
    """After the runtime's own shutdown: wait for every process this one
    started, directly or not, to be gone, and kill what outlives the
    grace.  Returns the pids killed."""
    me, killed = os.getpid(), []
    deadline = time.monotonic() + grace_s
    while True:
        children = []
        for entry in os.listdir("/proc"):
            if entry.isdigit():
                try:
                    with open(f"/proc/{entry}/stat") as f:
                        ppid = int(f.read().rsplit(")", 1)[1].split()[1])
                except (OSError, IndexError):     # exited under us
                    continue
                if ppid == me:
                    children.append(int(entry))
        if not children:
            return killed
        for pid in children:
            if time.monotonic() > deadline and pid not in killed:
                os.kill(pid, signal.SIGKILL)
                killed.append(pid)
            try:
                os.waitpid(pid, os.WNOHANG)       # reap once it has exited
            except ChildProcessError:
                pass
        time.sleep(0.1)


def _require_tpu(where: str, device: Dict[str, Any], count: int) -> None:
    if device["platform"] != "tpu" or device["device_count"] != count \
            or len(device["leased_chips"]) != count:
        sys.exit(f"chip_smoke: the {where} did not run on {count} leased "
                 f"TPU chip(s); it reports {device}")


def run(args: argparse.Namespace) -> Dict[str, Any]:
    """Both phases and the summary lines; returns the device of the result
    line.  Any failure raises (SystemExit included)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import rpcframe

    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    t0 = time.perf_counter()
    rt = ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if chips not in MESHES or args.chips not in (None, chips):
            sys.exit(f"chip_smoke: found {chips} TPU chip(s) on this host "
                     f"(wanted {args.chips or sorted(MESHES)}); chips are "
                     "discovered from TPU_VISIBLE_CHIPS, /dev/accel<N>, "
                     "/dev/vfio/<N>")
        try:
            train = train_phase(MODEL, batch=8, seq=2048, steps=6,
                                devices=chips, use_tpu=True, out_dir=out)
            _require_tpu("TrainWorker", train["device"], chips)
            if not train["pallas_in_step"]:
                sys.exit("chip_smoke: no Mosaic custom call in the lowered "
                         "train step — the Pallas kernel is not on the path")
            # No sleep: the agent grants TPU again only once the train
            # worker's process has exited.
            served = serve_phase("1b", num_tpus=1, num_replicas=chips,
                                 max_len=2048, max_batch=8, waves=WAVES,
                                 max_tokens=32)
            for rep in served["replicas"]:
                _require_tpu("EngineReplica", rep, 1)
            leased = [rep["leased_chips"][0] for rep in served["replicas"]]
            if len(set(leased)) != chips:
                sys.exit(f"chip_smoke: replicas share chips: {leased}")
        except BaseException:
            _keep(rt.session_dir, out, "logs")
            raise
        finally:
            _keep(rt.session_dir, out, "diagnosis")
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            sys.exit("chip_smoke: the driver initialised a JAX backend")
        bundles = os.path.join(rt.session_dir, "diagnosis")
        anomalies = sorted(os.listdir(bundles)) \
            if os.path.isdir(bundles) else []
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    with open(os.path.join(out, "result.json"), "w") as f:
        json.dump({"train": train, "serve": served, "anomalies": anomalies,
                   "native_framer": rpcframe.available(),
                   "wall_s": time.perf_counter() - t0}, f, indent=1)
    dev, gib = train["device"], float(1 << 30)
    cache = dev["compile_cache"]
    print(f"train: {dev['platform']} {dev['device_kind']} x"
          f"{dev['device_count']} mesh={train['mesh']} "
          f"{train['steps']} steps loss {train['losses'][0]:.3f} -> "
          f"{train['losses'][-1]:.3f}; first step (compile) "
          f"{train['first_step_s']:.1f}s, steady "
          f"{min(train['steady_step_s']):.2f}s; peak HBM "
          f"{max(dev['peak_bytes_in_use']) / gib:.2f} GiB/chip; "
          f"cache {cache['hits']} hits / {cache['requests']} compiles")
    for rep in served["replicas"]:
        cache = rep["compile_cache"]
        print(f"serve: {rep['platform']} {rep['device_kind']} x"
              f"{rep['device_count']} chip {rep['leased_chips']} peak HBM "
              f"{max(rep['peak_bytes_in_use']) / gib:.2f} GiB; cache "
              f"{cache['hits']} hits / {cache['requests']} compiles")
    print(f"serve: {served['requests']} streams, {served['tokens_out']} "
          f"tokens, max batch {served['max_active']}, "
          f"{served['prefix_cache_hits']} prefix hits; start-up "
          f"{served['startup_s']:.1f}s, waves "
          + ", ".join(f"{w['wall_s']:.1f}s/{w['compiles']} compiles"
                      for w in served["waves"]))
    print(f"native framer: {rpcframe.available()}; anomalies: "
          f"{anomalies or 'none'}; total {time.perf_counter() - t0:.0f}s; "
          f"detail in {os.path.relpath(out)}/result.json")
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["device_count"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(MESHES),
                    help="fail unless the host has exactly this many chips")
    ap.add_argument("--out", default="chiprun_out/chip_smoke")
    args = ap.parse_args()

    _adopt_orphans()
    try:
        device = run(args)
    finally:
        # Whether the run passed or raised: no process outlives the script.
        killed = _stop_descendants()
        if killed:
            print(f"chip_smoke: killed {len(killed)} process(es) that "
                  f"outlived shutdown: {killed}", file=sys.stderr)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
