"""The readings the `brumby` family's limits are set between, on the chip,
at the cell's own sizes:

    python3 -m benchmark.tests.retention_control --workload serve_doc_reask_retention --seeds 1,2,3

An engine of the cell's configuration and slots with seeded weights serves
the cell's check prompt twice, cold and as a hit from a state checkpoint,
`check_output_tokens` tokens each, and every row the path computes is read
against the family's float32 reference as the family's `check` reads it
(`families/brumby.py:read`): `logit_max`, `logit_rms` (the worst row's), the
reference's `margin` for the served tokens, `by_row` the rows' rms at a few
places.  Sound; then two controls, each an engine of its own that serves
the same prompt the same way and is read against the SOUND weights'
reference: the program's weight matrices rounded to float8_e4m3fn, and the
program's STATE held in bfloat16 (slot rows and checkpoints: a cheaper
cache).  `fails_by` names the limits of the family's TOLERANCE a reading
passed: none for a sound one, at least one for a control's (a lower
precision is another result, not a faster one).  One JSON line a seed.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

KEYS = ("logit_max", "logit_rms", "margin", "traced_from", "by_row")


def control_engine(family, config, params, control: str, **engine):
    """An engine in a precision below: `state`: its state (slot rows and
    checkpoints) in bfloat16, over `params` as they are; `weights`: its
    matrices rounded to float8_e4m3fn and back, IN PLACE (`params` is
    donated: two copies of the weights do not fit the chip)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import LLMEngine
    if control == "weights":
        # (The barrier keeps the compiler from taking the two conversions
        # for one that it may skip: `xla_allow_excess_precision`.)
        params = jax.jit(lambda p: jax.tree.map(
            lambda a: jax.lax.optimization_barrier(
                a.astype(jnp.float8_e4m3fn)).astype(a.dtype)
            if a.ndim > 1 else a, p), donate_argnums=0)(params)
    cfg = family.program_config(
        config, max_seq_len=engine["max_len"],
        state_dtype="bfloat16" if control == "state" else None)
    return LLMEngine(cfg, params, **engine)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    from ray_tpu.llm.engine import LLMEngine, SamplingParams
    from ray_tpu.models.transformer import init_params

    from .. import selftest
    from ..run import load_cell
    cell = load_cell(args.workload)
    if args.rehearse:
        selftest.shrink(cell)
    family, config, spec = cell["family"], cell["config"], cell["traffic"]
    eng = dict(spec["engine"], prefix_cache=True)
    cfg = family.program_config(config, max_seq_len=eng["max_len"])
    opts = SamplingParams(max_tokens=spec["check_output_tokens"])
    for seed in (int(s) for s in args.seeds.split(",")):
        params = jax.jit(init_params, static_argnums=0)(
            cfg, jax.random.key(seed))
        engine = LLMEngine(cfg, params, seed=seed, **eng)
        prompt = np.random.default_rng([seed, 5]).integers(
            1, config["vocab_size"], spec["check_prompt_len"]).tolist()
        served = [engine.generate([prompt], opts)[0] for _ in range(2)]
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "hit_on_second": engine.prefix_cache_stats()["hits"] == 1,
                "retention": engine.retention_stats()}
        refs = family.reference_rows(params, prompt, served, config)
        tol = family.TOLERANCE

        def reading(r):
            return {**{k: r[k] for k in KEYS},
                    "fails_by": [k for k in tol if r[k] > tol[k]]}
        line["sound"] = reading(family.read(engine, prompt, served, refs))
        for control in ("state", "weights"):    # the weights' rounds `params`
            del engine              # one engine's state at a time on the chip
            engine = control_engine(family, config, params, control,
                                    seed=seed, **eng)
            for _ in range(2):
                engine.generate([prompt], opts)
            line[control] = reading(
                family.read(engine, prompt, served, refs))
        print(json.dumps(line), flush=True)
        del engine, params
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
