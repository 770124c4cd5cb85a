"""`BenchReplica._bench_check` as the parent commit (PR 28, 1733212) had
it, word for word but for `self`: the oracle that shows the family-owned
path left a dense family's report alone."""

from __future__ import annotations

import time
from typing import Any, Dict


def bench_check(engine, family, config, prompt, served) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    t0 = time.time()
    tol = family.TOLERANCE
    ref = jax.jit(lambda p, t: family.reference_logits(p, t, config))
    n = len(prompt)
    margins = []
    ref_last = None
    for out in served:
        toks = jnp.asarray([list(prompt) + list(out[:-1])], jnp.int32)
        logits = np.asarray(ref(engine.params, toks)[0])
        at = logits[n - 1:]             # rows predicting out[0..]
        margins.append(float(max(
            row.max() - row[tok] for row, tok in zip(at, out))))
        if ref_last is None:
            ref_last = logits[n - 1]
    got = np.asarray(engine._run_prefill(list(prompt))[0],
                     np.float32)
    diff = got - ref_last
    report = {"prefill_logit_max": float(np.abs(diff).max()),
              "prefill_logit_rms": float(np.sqrt(np.mean(diff ** 2))),
              "ref_logit_std": float(ref_last.std()),
              "margins": margins, "tolerance": tol,
              "seconds": time.time() - t0}
    report["ok"] = bool(
        report["prefill_logit_max"] <= tol["logit_max"]
        and report["prefill_logit_rms"] <= tol["logit_rms"]
        and max(margins) <= tol["margin"])
    return report
