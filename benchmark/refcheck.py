"""The comparison that decides `correct` in a serving cell, run by the
replica that holds the chip (`replica.BenchReplica._bench_check`).

Two parts.  `plain` is the comparison every family gets: the engine's
prefill logits for the prompt's last position and the reference's margin
for each served token, against `family.TOLERANCE`.  It is sound for a model
whose every operation is continuous in its inputs.  A family with discrete
decisions inside (top-k routed experts: the float32 reference and the bf16
program pick a different expert where two scores are nearly equal) cannot
be held to it: one changed expert moves a logit by several times the dense
limits, on a coin's toss per run.  Such a family defines

    check(engine, prompt, served, config) -> {"ok", "tolerance", "forgiven", ...}

and that report decides (benchmark/README.md, "Adding a configuration",
says what it owes).  The plain statistics are computed and kept under
either, so that an owned check can always be read against the plain one.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List


def plain(engine, family, config: Dict[str, Any], prompt: List[int],
          served: List[List[int]]) -> Dict[str, Any]:
    """Each of `served` (the tokens streamed for a cold request, then for
    the same prompt as a prefix-cache hit) is teacher-forced through the
    reference's full forward pass, and the engine's own prefill logits are
    compared value by value."""
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
    got = np.asarray(engine._run_prefill(list(prompt))[0], np.float32)
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


def report(engine, family, config: Dict[str, Any], prompt: List[int],
           served: List[List[int]]) -> Dict[str, Any]:
    """The check's report.  A family without `check`: the plain report, as
    it always was.  A family with one: its own report decides, with the
    plain report beside it under `plain` (read, never judged)."""
    base = plain(engine, family, config, prompt, served)
    own = getattr(family, "check", None)
    if own is None:
        return base
    t0 = time.time()
    owned = dict(own(engine, prompt, served, config))
    for key in ("ok", "tolerance", "forgiven"):
        if key not in owned:
            raise KeyError(f"{family.__name__}.check returned no {key!r}")
    owned["ok"] = bool(owned["ok"])
    owned.update(plain=base, owned_by=family.__name__,
                 seconds=base["seconds"] + time.time() - t0)
    return owned
