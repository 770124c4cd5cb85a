"""The readings the `granite_hybrid` family's limits are set between, on
the chip, at the cell's own sizes:

    python3 -m benchmark.tests.granite_control --workload serve_chat_ssm --seeds 1,2,3

An engine of the cell's configuration and slots with seeded weights serves
the cell's check prompt twice, cold and as a hit from a state checkpoint,
`check_output_tokens` tokens each, and every row the path computes is read
against the family's float32 reference as the family's `check` reads it
(`families/granite_hybrid.py:read`): `logit_max`, `logit_rms` (the worst
row's), the reference's `margin` for the served tokens, `by_row` the rows'
rms at a few places.  Sound; then three controls, each read against the
SOUND weights' reference on the tokens the sound engine served:

- `cache`: the sound engine's key and value rows rounded to float8_e4m3fn
  where they lie in the pools (a cheaper cache: the hit's suffix and its
  decode steps then attend rounded rows);
- `state`: an engine whose SSM state, slot rows and checkpoints, is held in
  bfloat16 (the nearest type below the float32 the configuration states);
- `weights`: an engine whose matrices are rounded to float8_e4m3fn.

`fails_by` names the limits of the family's TOLERANCE a reading passed:
none for a sound one, at least one for each control's (a lower precision
is another result, not a faster one).  `--controls` picks some.  One JSON
line a seed.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from .retention_control import control_engine

KEYS = ("logit_max", "logit_rms", "margin", "traced_from", "by_row")
CONTROLS = ("cache", "state", "weights")


def round_cache(engine, dtype: str = "float8_e4m3fn") -> None:
    """Every row of the engine's two pools rounded to `dtype` and back, in
    place.  Leans on the private `LLMEngine._pk` / `_pv`: the control
    reaches under the engine on purpose, the check it controls does not."""
    import jax
    import jax.numpy as jnp
    low = jax.jit(lambda a: jax.lax.optimization_barrier(
        a.astype(getattr(jnp, dtype))).astype(a.dtype), donate_argnums=0)
    engine._pk, engine._pv = low(engine._pk), low(engine._pv)


def readings(family, config, spec, seed: int, controls=CONTROLS) -> dict:
    """One seed's line: the sound reading, then each of `controls`."""
    import jax

    from ray_tpu.llm.engine import LLMEngine, SamplingParams
    from ray_tpu.models.transformer import init_params
    eng = dict(spec["engine"], prefix_cache=True)
    cfg = family.program_config(config, max_seq_len=eng["max_len"])
    opts = SamplingParams(max_tokens=spec["check_output_tokens"])
    params = jax.jit(init_params, static_argnums=0)(cfg, jax.random.key(seed))
    engine = LLMEngine(cfg, params, seed=seed, **eng)
    prompt = np.random.default_rng([seed, 5]).integers(
        1, config["vocab_size"], spec["check_prompt_len"]).tolist()
    served = [engine.generate([prompt], opts)[0] for _ in range(2)]
    line = {"seed": seed, "device": jax.devices()[0].device_kind,
            "hit_on_second": engine.prefix_cache_stats()["hits"] == 1,
            "mamba": engine.mamba_stats()}
    refs = family.reference_rows(params, prompt, served, config)
    tol = family.TOLERANCE

    def reading(r):
        return {**{k: r[k] for k in KEYS},
                "fails_by": [k for k in tol if r[k] > tol[k]]}
    line["sound"] = reading(family.read(engine, prompt, served, refs))
    if "cache" in controls:             # (last on this engine: it changes it)
        round_cache(engine)
        line["cache"] = reading(family.read(engine, prompt, served, refs))
    for control in ("state", "weights"):    # the weights' rounds `params`
        if control not in controls:
            continue
        del engine                  # one engine's state at a time on the chip
        engine = control_engine(family, config, params, control, seed=seed,
                                **eng)
        for _ in range(2):
            engine.generate([prompt], opts)
        line[control] = reading(family.read(engine, prompt, served, refs))
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--check-tokens", type=int, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from .. import selftest
    from ..run import load_cell
    cell = load_cell(args.workload)
    if args.rehearse:
        selftest.shrink(cell)
    if args.check_tokens:
        cell["traffic"]["check_output_tokens"] = args.check_tokens
    controls = tuple(c for c in args.controls.split(",") if c)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell["family"], cell["config"],
                                  cell["traffic"], seed, controls)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
