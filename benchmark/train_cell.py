"""A training cell: `JaxTrainer` with one `TrainWorker` that holds the
cell's chips.  `train_loop` runs in that worker — the process that holds
the chips — and is the benchmark's own code: it reads the clock around the
steps, checks the program against the reference, and traces the device.
The driver side only starts it and reads its last report.

Phases in the worker (all but the window are set-up): build the step and
the seeded state -> the reference on batch 0 -> warm-up steps (compile;
step 0's own loss is held to the reference's) -> WINDOW: whole optimizer
steps, each ended by `block_until_ready` on its loss, until `--seconds`
have passed; the window closes with the step that crosses that mark, so
the rate is over all the work and all the time -> with `--trace 1`, a few
more steps under the profiler.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

from . import cluster


def train_loop(c: Dict[str, Any]) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu._private.compile_cache import enable_compile_cache
    from ray_tpu.models import make_train_step
    from ray_tpu.models.train_step import make_optimizer
    from ray_tpu.models.transformer import forward
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.tpu.accelerator import device_report

    from . import families, trace as trace_mod

    loop_wall = time.time()
    enable_compile_cache()
    config, spec, seed = c["config"], c["traffic"], c["seed"]
    family = families.load(config["family"])
    B, S = spec["batch"], spec["seq"]
    cfg = dataclasses.replace(
        family.program_config(config, attention=spec["attention"],
                              max_seq_len=S), remat=True)
    mesh = build_mesh(MeshSpec(**spec["mesh"]))
    # The program's own optimizer (gradient clipping, AdamW with cosine
    # schedule; moments in the parameters' type), updated inside the one
    # jitted step.
    tx = make_optimizer(learning_rate=spec["lr"],
                        warmup_steps=spec["warmup_steps"],
                        decay_steps=100_000)
    bundle = make_train_step(cfg, mesh, optimizer=tx)
    state = jax.block_until_ready(bundle.init(jax.random.key(seed)))
    ready_wall = time.time()

    # Token batches made on the device from the seed: `distinct_batches`
    # different ones in rotation, so that the loss falls as they are learnt.
    batch_shd = bundle.rules.sharding(("batch", None), mesh)
    data_key = jax.random.key(seed + 1)
    vocab, pool = config["vocab_size"], spec["distinct_batches"]
    make_batch = jax.jit(
        lambda i: {"tokens": jax.random.randint(
            jax.random.fold_in(data_key, i % pool), (B, S + 1), 1, vocab,
            jnp.int32)}, out_shardings={"tokens": batch_shd})

    def step(i):
        nonlocal state
        state, m = bundle.step(state, make_batch(i))
        return float(jax.block_until_ready(m["loss"]))

    # ---- correct, before any step: the reference on batch 0 ------------
    # The plain float32 forward of the seeded weights over the whole of
    # batch 0 at the cell's own length, `check.batch` sequences a call (a
    # call holds S x S float32 scores).  Its loss is what the program's
    # step must report as its own loss of step 0 (below); the program's
    # forward pass is held to its logits on the first call's sequences.
    t_check = time.time()
    tol, cb = family.TOLERANCE, spec["check"]["batch"]
    tokens0 = make_batch(0)["tokens"]
    ref_fn = jax.jit(lambda p, t: family.reference_logits(p, t, config))
    loss_of = jax.jit(family.reference_loss)
    ref_losses = []
    for i in range(0, B, cb):
        chunk = jax.device_put(tokens0[i:i + cb], batch_shd)
        ref = ref_fn(state["params"], chunk[:, :-1])
        ref_losses.append(float(loss_of(ref, chunk)))
        if i == 0:
            got = jax.jit(lambda p, t: forward(
                p, t, cfg, mesh, bundle.rules))(state["params"],
                                                chunk[:, :-1])
            worst, rms = jax.jit(lambda a, b: (
                jnp.max(jnp.abs(a.astype(jnp.float32) - b)),
                jnp.sqrt(jnp.mean((a.astype(jnp.float32) - b) ** 2))))(
                    got, ref)
            check = {"logit_max": float(worst), "logit_rms": float(rms)}
            del got
        del ref
    check.update(ref_loss=sum(ref_losses) / len(ref_losses),
                 tolerance=tol, seconds=time.time() - t_check)

    pallas = "tpu_custom_call" in bundle.step.lower(
        state, make_batch(0)).as_text()
    t_warm = time.time()
    warm_losses = [step(i) for i in range(spec["warm_steps"])]
    warm_s = time.time() - t_warm
    n = spec["warm_steps"]
    # Step 0 ran on batch 0 with the seeded weights: the loss it reported
    # is the one the step optimises, held here to the reference's.
    check["loss"] = warm_losses[0]
    check["ok"] = bool(check["logit_max"] <= tol["logit_max"]
                       and check["logit_rms"] <= tol["logit_rms"]
                       and abs(check["loss"] - check["ref_loss"])
                       <= tol["loss"])
    compiles0 = device_report()["compile_cache"]["requests"]

    # ---- the window ----------------------------------------------------
    losses, ends, raised = [], [], 0
    t0 = time.time()
    while not ends or ends[-1] - t0 < c["seconds"]:
        try:
            losses.append(step(n))
        except Exception:                   # a step that raised: counted
            raised += 1
            if raised > 3:
                raise
        n += 1
        ends.append(time.time())
        train.report({"step": n, "loss": losses[-1] if losses else None})
    compiles = device_report()["compile_cache"]["requests"] - compiles0

    trace = None
    if c["traced"]:
        def few():
            nonlocal n
            for _ in range(spec["trace_steps"]):
                step(n)
                n += 1
        trace = trace_mod.traced(few, c["out_dir"], 20000)
        trace.pop("result", None)

    train.report({
        "done": True, "device": device_report(), "check": check,
        "loop_wall": loop_wall, "ready_wall": ready_wall,
        "window": [t0, ends[-1]],
        "step_ends": ends, "losses": losses, "raised": raised,
        "warm_losses": warm_losses, "warm_s": warm_s,
        "tokens_per_step": B * S, "pallas_in_step": pallas,
        "compiles_in_window": compiles, "trace": trace,
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "memory": [d.memory_stats() or {} for d in jax.local_devices()]})


def run(cell: Dict[str, Any], *, seed: int, seconds: float, traced: bool,
        out_dir: str, t_proc: float, require_tpu: bool = True
        ) -> Dict[str, Any]:
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    chips = cell["chips"]
    with cluster.runtime(
            chips, out_dir, require_tpu,
            cell["config"]["deployment"].get("runtime_config")) as rt:
        init_s = time.time() - t_proc           # runtime up; detail only
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "config": cell["config"], "traffic": cell["traffic"],
                "seed": seed, "seconds": seconds, "traced": traced,
                "out_dir": out_dir},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=require_tpu,
                resources_per_worker=None if require_tpu else {"CPU": 1.0}),
            jax_config=JaxConfig(use_tpu=require_tpu,
                                 cpu_devices_per_process=chips),
            run_config=RunConfig(name="bench_train",
                                 storage_path=os.path.join(out_dir, "train")),
        ).fit()
        if result.error:
            raise RuntimeError(f"training failed:\n{result.error}")
        final = result.metrics_history[-1]
        if not final.get("done"):
            raise RuntimeError(f"training ended without its last report: "
                               f"{final}")
        if require_tpu:
            cluster.require_tpu("TrainWorker", final["device"], chips)
            if cell["traffic"]["attention"] == "flash" \
                    and not final["pallas_in_step"]:
                raise RuntimeError("no Mosaic custom call in the lowered "
                                   "step: the Pallas kernel is not on the "
                                   "path")
    t0, t1 = final["window"]
    return {**final, "kind": "train_steps", "chips": chips,
            "seconds": seconds, "init_s": init_s,
            "loop_s": final["loop_wall"] - t_proc,
            "ready_s": final["ready_wall"] - t_proc,
            "setup_s": t0 - t_proc, "session": rt.session_dir,
            "reports": len(result.metrics_history)}
