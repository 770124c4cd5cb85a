"""Headline benchmark: flagship-model training throughput on one TPU chip.

Prints ONE short JSON line:
  {"metric": "train_mfu_pct", "value": <MFU %>, "unit": "% of chip peak",
   "vs_baseline": <MFU / 0.40 north-star>, "device": {...}}
and exits non-zero, printing no result, when JAX finds no TPU or the
device kind has no peak in PEAK_FLOPS.  The host-runtime suite is its own
command (`python -m ray_tpu.util.perf`), run on the CPU, never from here:
this process holds the chip, and a child could not have it.

The north-star (BASELINE.json) is Llama-2-7B fine-tune at >=40% MFU on
v5e-64; a single chip can't hold 7B + Adam state, so the bench runs the
largest preset that fits one chip's HBM and reports model-FLOPs utilization,
which is chip-count invariant for this SPMD design (per-chip shapes match the
pod-scale per-chip shapes).  vs_baseline = achieved MFU / 40%.
"""

import json
import sys
import time


# bf16 peak FLOP/s per chip by device kind (public spec sheets).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
    "TPU7x": 2307e12,
}


def pick_config(hbm_bytes: float):
    import dataclasses

    from ray_tpu.models import PRESETS, TransformerConfig
    # Adam fp32 moments dominate: ~18 bytes/param (bf16 p + g, 2x f32 m),
    # so 7B needs ~126 GB + activations.
    if hbm_bytes > 140e9:
        cfg, batch, seq = PRESETS["7b"], 8, 2048
    elif hbm_bytes > 24e9:
        cfg, batch, seq = PRESETS["1b"], 8, 2048
    else:
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_layers=10, num_heads=16, num_kv_heads=16, max_seq_len=2048)
        batch, seq = 8, 2048
    return dataclasses.replace(cfg, attention_impl="flash"), batch, seq


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu._private.compile_cache import enable_compile_cache
    from ray_tpu.models import make_train_step
    from ray_tpu.parallel import MeshSpec, build_mesh

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures a TPU; JAX found platform="
                 f"{dev.platform!r} ({dev.device_kind})")
    if dev.device_kind not in PEAK_FLOPS:
        sys.exit(f"bench.py has no peak FLOP/s for device kind "
                 f"{dev.device_kind!r}; add it to PEAK_FLOPS with its source")
    hbm = dev.memory_stats()["bytes_limit"]
    cfg, batch, seq = pick_config(hbm)

    mesh = build_mesh(MeshSpec(), devices=[dev])
    bundle = make_train_step(cfg, mesh)
    state = bundle.init(jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size,
                                          (batch, seq + 1)), jnp.int32)
    data = {"tokens": tokens}

    state, metrics = bundle.step(state, data)       # warmup/compile
    jax.block_until_ready(metrics)

    n_steps = 10
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = bundle.step(state, data)
    loss = float(metrics["loss"])  # steps chain through donated state
    dt = (time.perf_counter() - t0) / n_steps
    if not np.isfinite(loss):
        sys.exit(f"bench.py: loss is {loss}")

    tok_s = batch * seq / dt
    mfu = tok_s * cfg.flops_per_token(seq) / PEAK_FLOPS[dev.device_kind] \
        * 100.0
    print(json.dumps({
        "metric": "train_mfu_pct",
        "value": round(mfu, 2),
        "unit": "%% of chip peak (tokens/s/chip=%d, model=%dM params)" % (
            int(tok_s), cfg.param_count() // 1_000_000),
        "vs_baseline": round(mfu / 40.0, 3),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))


if __name__ == "__main__":
    main()
