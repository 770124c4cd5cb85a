"""A toy routed (mixture-of-experts) family with a check of its own, and a
toy program to hold to it.  Not a configuration of the benchmark: it exists
so that `refcheck.report`'s family-owned path, and what README.md says such
a check owes, are exercised by tests that need no chip and no runtime.

The model: hidden 64, causal attention of 4 heads of 16, and per layer 16
routed experts in 4 groups beside one shared expert.  A token keeps the 2
groups whose best expert scores highest, then the 2 best experts inside
them (`group_limited_greedy`: softmax scores, not renormalised, scaled).
The reference is float32; the program (`ToyEngine`) computes in bf16
values with float32 accumulation, prefill and then decoding through a
cache, and reports every decision it made.

Module surface of a family: `TINY`, `TOLERANCE`, `reference_logits`,
`check`.  The rest is the toy program.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

CONFIG = {"family": "toy_routed", "vocab_size": 256, "hidden_size": 64,
          "num_hidden_layers": 4, "num_attention_heads": 4,
          "n_routed_experts": 16, "n_group": 4, "topk_group": 2,
          "num_experts_per_tok": 2, "moe_intermediate_size": 32,
          "n_shared_experts": 1, "routed_scaling_factor": 8.0,
          "rms_norm_eps": 1e-6}

# What `selftest.shrink` applies after its own dense keys: a routed config
# keeps its published inner widths in the rehearsal otherwise.
TINY = {"moe_intermediate_size": 32, "n_routed_experts": 16, "n_group": 4,
        "topk_group": 2, "num_experts_per_tok": 2}

# Logit limits as tight as the dense family's.  `tie_zone`: how far, in the
# REFERENCE's float32 scores, a decision of the program may lie below the
# reference's own cut and still be forgiven (forced onto the reference);
# `forced_share`: the most decisions (of tokens x layers) a run may have
# forgiven.  Set from 44 seeds of the bf16 program against 14 of the fp8
# control (CPU, PR 29): logit max <= 0.132 against >= 0.407, rms <= 0.034
# against >= 0.128, shortfall of a differing decision <= 0.0020 against
# >= 0.022, forced share <= 2.7%; the control also has 10-36 decisions
# outside the zone where the program has none.
TOLERANCE = {"logit_max": 0.25, "logit_rms": 0.045, "margin": 0.25,
             "tie_zone": 0.006, "forced_share": 0.08}


def init_params(seed: int, cfg: Dict[str, Any] = CONFIG) -> Dict[str, Any]:
    """Seeded weights, bf16 as served (the reference casts them up)."""
    h, v, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    e, m = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    rng = np.random.default_rng([seed, 29])

    def w(*shape, fan_in):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(fan_in),
                           jnp.bfloat16)
    layers = [{"wq": w(h, h, fan_in=h), "wk": w(h, h, fan_in=h),
               "wv": w(h, h, fan_in=h), "wo": w(h, h, fan_in=h),
               "router": w(h, e, fan_in=h),
               "gate": w(e + 1, h, m, fan_in=h), "up": w(e + 1, h, m, fan_in=h),
               "down": w(e + 1, m, h, fan_in=m)} for _ in range(L)]
    return {"embed": w(v, h, fan_in=1), "layers": layers,
            "head": w(h, v, fan_in=h)}


def _decide(scores, cfg):
    """group_limited_greedy on (S, E) scores -> (S, E) bool, (S, G) bool."""
    e, g = cfg["n_routed_experts"], cfg["n_group"]
    kg, k = cfg["topk_group"], cfg["num_experts_per_tok"]
    by_group = scores.reshape(-1, g, e // g).max(-1)
    cut = jnp.sort(by_group, -1)[:, g - kg][:, None]
    groups = by_group >= cut
    inside = jnp.where(jnp.repeat(groups, e // g, -1), scores, -1.0)
    cut = jnp.sort(inside, -1)[:, e - k][:, None]
    return inside >= cut, groups


def _forward(params, tokens, cfg, dt, forced=None, cache=None):
    """One forward pass in values of type `dt` with float32 accumulation.
    tokens (S,).  `cache`: per layer (k, v) of the positions before these.
    `forced`: per layer an (S, E) bool of decisions to hold the float32
    scores to (the reference's path).  Returns logits (S, V) float32, the
    decisions, the new cache and, when forced, what was forgiven."""
    f32 = jnp.float32
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    e, g = cfg["n_routed_experts"], cfg["n_group"]
    eps, zone = cfg["rms_norm_eps"], TOLERANCE["tie_zone"]

    def acc(eq, a, b):      # values of type dt, float32 accumulation
        return jnp.einsum(eq, a.astype(dt).astype(f32),
                          b.astype(dt).astype(f32), precision="highest")

    def mm(eq, a, b):
        return acc(eq, a, b).astype(dt)

    def norm(x):
        x = x.astype(f32)
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                ).astype(dt)

    S = tokens.shape[0]
    x = params["embed"].astype(dt)[tokens]
    decisions, new_cache = [], []
    forgiven = {"forced": 0, "outside_zone": 0, "decisions": 0,
                "shortfall": 0.0}
    for li, lp in enumerate(params["layers"]):
        a = norm(x)
        q = mm("se,ef->sf", a, lp["wq"]).reshape(S, nh, h // nh)
        k = mm("se,ef->sf", a, lp["wk"]).reshape(S, nh, h // nh)
        val = mm("se,ef->sf", a, lp["wv"]).reshape(S, nh, h // nh)
        if cache is not None:
            k = jnp.concatenate([cache[li][0], k])
            val = jnp.concatenate([cache[li][1], val])
        new_cache.append((k, val))
        T = k.shape[0]
        sc = acc("shd,thd->hst", q, k) / np.sqrt(h // nh)
        causal = jnp.arange(T)[None] <= (jnp.arange(S) + T - S)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        o = mm("hst,thd->shd", p, val).reshape(S, h)
        x = x + mm("sf,fe->se", o, lp["wo"])
        a = norm(x)
        scores = jax.nn.softmax(acc("se,ex->sx", a, lp["router"]), -1)
        own, own_groups = _decide(scores, cfg)
        chosen = own
        if forced is not None:
            chosen = forced[li]
            differs = (chosen != own).any(-1)
            # In the zone: every group the program kept, and every expert
            # it chose inside those groups, scores within `zone` of the
            # reference's own cut.
            by_group = scores.reshape(S, g, e // g).max(-1)
            kept = chosen.reshape(S, g, e // g).any(-1)
            group_cut = jnp.where(own_groups, by_group, 2.0).min(-1)
            inside = jnp.where(jnp.repeat(kept, e // g, -1), scores, -1.0)
            kth = jnp.sort(inside, -1)[:, e - cfg["num_experts_per_tok"]]
            short = jnp.maximum(
                group_cut - jnp.where(kept, by_group, 2.0).min(-1),
                kth - jnp.where(chosen, scores, 2.0).min(-1))
            ok = (short <= zone) & (chosen.sum(-1) == own.sum(-1))
            forgiven["forced"] += (differs & ok).sum()
            forgiven["outside_zone"] += (differs & ~ok).sum()
            forgiven["decisions"] += S
            forgiven["shortfall"] = jnp.maximum(
                forgiven["shortfall"], jnp.where(differs, short, 0.0).max())
        decisions.append(chosen)
        weight = jnp.where(chosen, scores, 0.0) * cfg["routed_scaling_factor"]
        weight = jnp.concatenate([weight, jnp.ones((S, 1), f32)], -1)
        hid = jax.nn.silu(mm("se,xem->sxm", a, lp["gate"]).astype(f32)) \
            * mm("se,xem->sxm", a, lp["up"]).astype(f32)
        out = mm("sxm,xme->sxe", hid, lp["down"])
        x = x + jnp.einsum("sxe,sx->se", out.astype(f32), weight).astype(dt)
    logits = acc("se,ev->sv", norm(x), params["head"])
    return logits, decisions, new_cache, forgiven


_JIT: Dict[Any, Any] = {}


def _run(params, tokens, cfg, dt, forced=None, cache=None):
    """`_forward`, compiled once per configuration, type and shape."""
    key = (json.dumps(cfg, sort_keys=True), jnp.dtype(dt).name)
    if key not in _JIT:
        _JIT[key] = jax.jit(lambda p, t, f, c: _forward(p, t, cfg, dt, f, c))
    return _JIT[key](params, jnp.asarray(tokens, jnp.int32), forced, cache)


def reference_logits(params, tokens, cfg: Dict[str, Any] = CONFIG):
    """The plain float32 reference, its own decisions: tokens (B, S) ->
    logits (B, S, V).  What `refcheck.plain` holds the program to."""
    return jnp.stack([_forward(params, row, cfg, jnp.float32)[0]
                      for row in tokens])


class ToyEngine:
    """The toy program: bf16 values (or, as the control, weights rounded to
    `weights`), prefill and then decoding through a cache.  Public names a
    family's check may use: `params`, `prefill`, `decode`."""

    def __init__(self, params, cfg: Dict[str, Any] = CONFIG,
                 weights: str = "bfloat16", flip: Optional[int] = None):
        self.params, self.cfg = params, cfg
        low = getattr(jnp, weights)
        self._weights = jax.tree.map(
            lambda a: a.astype(low).astype(jnp.bfloat16), params)
        self._flip = flip       # a fault: this decode step picks other experts
        self._cache, self._steps = None, 0

    def prefill(self, prompt: List[int]):
        """-> (last position's logits, per layer (S, E) decisions)."""
        logits, dec, self._cache, _ = _run(
            self._weights, prompt, self.cfg, jnp.bfloat16)
        self._steps = 0
        return logits[-1], dec

    def decode(self, token: int):
        """One token through the cache -> (logits, per layer (1, E))."""
        forced = None
        if self._flip == self._steps:       # the worst two experts instead
            e = self.cfg["n_routed_experts"]
            forced = [jnp.zeros((1, e), bool).at[0, [3, 7]].set(True)
                      ] * len(self.params["layers"])
        logits, dec, self._cache, _ = _run(
            self._weights, [token], self.cfg, jnp.bfloat16, forced,
            self._cache)
        self._steps += 1
        return logits[0], dec

    def generate(self, prompt: List[int], n: int) -> List[int]:
        """Greedy tokens, as the serving path would stream them."""
        logits, _ = self.prefill(prompt)
        out = [int(jnp.argmax(logits))]
        while len(out) < n:
            out.append(int(jnp.argmax(self.decode(out[-1])[0])))
        return out

    def _run_prefill(self, prompt):     # what `refcheck.plain` leans on
        return (self.prefill(list(prompt))[0],)


def check(engine, prompt: List[int], served: List[List[int]],
          config: Dict[str, Any]) -> Dict[str, Any]:
    """The family-owned check, as README.md asks of one: logits of the
    prefill and of every decode step through the cache, against a float32
    reference that is forced to the program's decisions inside the tie
    zone; a decision outside it, or too many forced, fails the run."""
    tol, n = TOLERANCE, len(prompt)
    worst = {"logit_max": 0.0, "logit_rms": 0.0, "margin": 0.0}
    forgiven = {"forced": 0, "outside_zone": 0, "decisions": 0,
                "shortfall": 0.0, "why": "the program's experts differ from the reference's "
                       "top-k within tie_zone of its float32 scores"}
    for out in served:
        logits, dec = engine.prefill(prompt)
        rows, decs = [logits], [dec]
        for tok in out[:-1]:
            logits, dec = engine.decode(tok)
            rows.append(logits)
            decs.append(dec)
        forced = [jnp.concatenate([d[li] for d in decs])
                  for li in range(len(decs[0]))]
        ref, _, _, f = _run(engine.params, list(prompt) + list(out[:-1]),
                            config, jnp.float32, forced)
        for key in ("forced", "outside_zone", "decisions"):
            forgiven[key] += int(f[key])
        forgiven["shortfall"] = max(forgiven["shortfall"],
                                    float(f["shortfall"]))
        ref = np.asarray(ref[n - 1:])
        diff = np.asarray(jnp.stack(rows), np.float32) - ref
        worst["logit_max"] = max(worst["logit_max"],
                                 float(np.abs(diff).max()))
        worst["logit_rms"] = max(worst["logit_rms"], float(
            np.sqrt((diff ** 2).mean(-1)).max()))
        worst["margin"] = max(worst["margin"], float(max(
            row.max() - row[tok] for row, tok in zip(ref, out))))
    share = forgiven["forced"] / max(1, forgiven["decisions"])
    return {**worst, "forced_share": share, "forgiven": forgiven,
            "tolerance": tol,
            "ok": bool(all(worst[k] <= tol[k] for k in worst)
                       and forgiven["outside_zone"] == 0
                       and share <= tol["forced_share"])}
