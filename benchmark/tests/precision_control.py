"""The two readings a family-owned check's limits are set between, on the
chip, at the cell's own sizes:

    python3 -m benchmark.tests.precision_control --workload <cell> --seeds 1,2,3

For each seed: an engine of the cell's configuration and slots with seeded
weights serves the cell's check prompt twice (cold, then as a prefix-cache
hit), and the family's `check` reads it twice: sound, and with the
reference's weight matrices rounded to `--weights` (float8_e4m3fn: the
precision below the one the configuration states), which must come out as
not ok.  One JSON line a seed.  No runtime and no Serve: the comparison is
`refcheck`'s, on the engine the serving cell would deploy.  (The rounding
is on the reference's side because two copies of the weights do not fit the
chip; the difference between the two sides is the same either way.)
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--weights", default="float8_e4m3fn")
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
                "hit_on_second": engine.prefix_cache_stats()["hits"] == 1}
        # Greedy: what was served is the argmax of what the trace reads, if
        # the serving step and the trace decide alike (they run one model
        # half in two programs).
        line["served_is_traced"] = [
            np.asarray(engine.trace_logits(prompt, out[:-1], cached=i > 0)
                       ["logits"]).argmax(-1).tolist() == out
            for i, out in enumerate(served)]
        for name, low in (("sound", ""), ("control", args.weights)):
            r = family.check(engine, prompt, served, config, low)
            line[name] = {k: r[k] for k in (
                "ok", "logit_max", "logit_rms", "margin", "forced_share")}
            line[name].update(outside_zone=r["forgiven"]["outside_zone"],
                              shortfall=r["forgiven"]["shortfall"])
        print(json.dumps(line), flush=True)
        del engine, params
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
