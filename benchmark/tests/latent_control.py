"""The readings the `deepseek_v3` family's limits are set between, on the
chip, at the cell's own sizes:

    python3 -m benchmark.tests.latent_control --workload serve_doc_reask_mla --seeds 1,2,3

`precision_control`'s procedure (an engine of the cell's configuration and
slots with seeded weights serves the cell's check prompt twice, cold and as
a prefix-cache hit, and the family's `check` reads it sound and with the
reference's weight matrices rounded to float8_e4m3fn), and one control
more, which only a latent cache has: the PROGRAM's cache rows rounded to
float8_e4m3fn where they lie in the pool (`round_cache`), after which the
hit's suffix and its decode steps read a cheaper cache.  That must come out
as not ok: a cache in the precision below is another result, not a faster
one.  One JSON line a seed.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

KEYS = ("ok", "logit_max", "logit_rms", "margin", "forced_share")


def round_cache(engine, dtype: str = "float8_e4m3fn") -> None:
    """Every row of the engine's latent pool rounded to `dtype` and back, in
    place.  Leans on the private `LLMEngine._pk`: the control reaches under
    the engine on purpose, the check it controls does not."""
    import jax.numpy as jnp
    pool = engine._pk
    engine._pk = pool.astype(getattr(jnp, dtype)).astype(pool.dtype)


def readings(family, engine, prompt, served, config,
             weights: str = "float8_e4m3fn") -> dict:
    """The check sound, then against the weights control, then with the
    cache rounded (last: it changes the engine)."""
    out = {}
    for name, low in (("sound", ""), ("control", weights)):
        out[name] = family.check(engine, prompt, served, config, low)
    round_cache(engine)
    out["cache"] = family.check(engine, prompt, served, config)
    return out


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
    eng = spec["engine"]
    cfg = family.program_config(config, max_seq_len=eng["max_len"])
    for seed in (int(s) for s in args.seeds.split(",")):
        params = jax.jit(init_params, static_argnums=0)(
            cfg, jax.random.key(seed))
        engine = LLMEngine(cfg, params, max_batch=eng["max_batch"],
                           max_len=eng["max_len"], page_size=eng["page_size"],
                           kv_pages=eng["kv_pages"], prefix_cache=True,
                           seed=seed)
        prompt = np.random.default_rng([seed, 5]).integers(
            1, config["vocab_size"], spec["check_prompt_len"]).tolist()
        served = [engine.generate([prompt], SamplingParams(
            max_tokens=spec["check_output_tokens"]))[0] for _ in range(2)]
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "hit_on_second": engine.prefix_cache_stats()["hits"] == 1,
                "latent": engine.latent_stats()}
        line["served_is_traced"] = [
            np.asarray(engine.trace_logits(prompt, out[:-1], cached=i > 0)
                       ["logits"]).argmax(-1).tolist() == out
            for i, out in enumerate(served)]
        for name, r in readings(family, engine, prompt, served,
                                config).items():
            line[name] = {k: r[k] for k in KEYS}
            line[name].update(outside_zone=r["forgiven"]["outside_zone"],
                              shortfall=r["forgiven"]["shortfall"])
        print(json.dumps(line), flush=True)
        del engine, params
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
