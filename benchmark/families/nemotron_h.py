"""Nemotron-H family (`model_type: nemotron_h`; NVIDIA-Nemotron-3-Super-120B-
A12B): a stack that is a pattern of three kinds of block, each
`x <- x + mixer(rms_norm(x))`, a final norm and an untied head.

- `M`, Mamba-2: `[z | xBC | dt] = x W_in`; `xBC <- silu(causal depthwise
  conv(xBC))`, split into x (heads x head_dim), B, C (groups x state);
  `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; per head, in float32,
  `h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`, `y_t = h_t C_t + D x_t`; then
  `rms_norm_grouped(y * silu(z))` with a learned scale, and `W_out`.
- `E`, latent routed experts: `s = sigmoid(x W_r)` over ALL experts in
  float32; the `num_experts_per_tok` largest `s + e_score_correction_bias`
  are chosen (`n_group` 1: no group limit); weights = their `s`, normalised
  over the chosen, times `routed_scaling_factor`; `u = x W_down_latent`;
  expert e gives `relu(u W1_e)^2 W2_e`; the weighted sum goes through
  `W_up_latent`; one shared expert `relu(x Ws1)^2 Ws2` is added.
- `*`, attention: grouped-query, causal, no bias, no window, NO rotary
  embedding (`assumed` in the configuration file says why).

A configuration of this family is ONE CHIP'S SHARE of an expert-parallel
group: `n_routed_experts` counts the experts held here (`expert_offset` the
first of them), `router_experts` the router's outputs (every expert of the
model), `vocab_size` the slice of the vocabulary.  What the experts held
elsewhere would add is left out, in the program and here alike.

What a later family needs to know of the two mechanisms this one brought:

- STATE CHECKPOINTS.  The engine's prefix cache serves a model with
  recurrent layers only from a boundary at which the recurrent state was
  kept: every `4 x chunk_size` tokens (`llm/engine.py:_CKPT_CHUNKS`), in a
  pool of rows sized from the page pool.  A hit is cut back to the last kept
  boundary, and the tokens between are recomputed:
  `debug_stats()["state"]` has `tokens_recomputed` beside
  `hit_prompt_tokens` (reader `state_recompute`), rows in use and in all,
  checkpoints kept and evicted.  A check prompt must be longer than one
  spacing, or its second asking is a miss.
- THE TOUCHED-EXPERT COUNTER.  A routed decode step reads the weights of the
  experts its batch touched and no others, which no shape gives:
  `debug_stats()["routed"]` counts, for each routed layer, the distinct held
  experts that got a row and the (token, expert) rows computed, summed over
  `steps` decode steps.  `decode_step_bytes` below takes the mean count;
  reader `decode_roofline_hybrid` returns nothing where the counter is
  missing.

All of this is the yardstick's: the mapping onto the program's config, the
plain float32 reference (the recurrence as a `lax.scan` over tokens, every
held expert in turn), the check this family owns, the required bytes.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Optional

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}

# What `selftest.shrink` applies after its own dense keys (hidden 128, 8
# heads, vocabulary 512, float32): one of each kind and a second `M` and `E`.
TINY = {"num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
        "num_key_value_heads": 2, "mamba_num_heads": 8, "mamba_head_dim": 32,
        "ssm_state_size": 16, "n_groups": 2, "chunk_size": 16,
        "n_routed_experts": 4, "router_experts": 16, "expert_offset": 4,
        "num_experts_per_tok": 4, "moe_intermediate_size": 64,
        "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 128}

# Limits of the check this family owns (`check`).  With the reference forced
# to the program's experts inside the tie zone, what is left is the rounding
# of continuous operations.  `tie_zone`: how far, in the REFERENCE's float32
# `s + bias`, an expert the program chose may lie below the reference's own
# cut and be forgiven; `forced_share`: the most of the chosen experts
# (positions x routed layers x experts per token) a run may have forgiven.
# Each lies between its two readings on the chip at the cell's sizes, the
# geometric mean of them (PERF.md §2: 23 sound seeds, and the control with
# the reference's weights rounded to float8_e4m3fn, 3 seeds): `logit_max`
# 0.052-0.065 against 0.180-0.205, `logit_rms` 0.0123-0.0136 against
# 0.0416-0.0435, the largest shortfall below the cut 0.0048-0.0073 against
# 0.0212-0.0253 (`tie_zone`), `forced_share` 0.0094-0.0110 against
# 0.0266-0.0276.  `margin` (how far below the reference's best the served
# token may rank) is the dense family's: precision hardly moves it (0-0.016
# sound, 0.005-0.054 control), it catches a token that was not the model's.
TOLERANCE = {"logit_max": 0.11, "logit_rms": 0.024, "margin": 0.25,
             "tie_zone": 0.0125, "forced_share": 0.017}


def program_config(cfg: Dict[str, Any], *, attention: str = "xla",
                   max_seq_len: Optional[int] = None):
    """The program's TransformerConfig for a configuration file.  Refuses
    what the pattern's three kinds cannot express."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import (Mamba2Dims, RoutedDims,
                                            TransformerConfig)
    pattern = cfg["hybrid_override_pattern"]
    want = {"n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2",
            "mamba_hidden_act": "silu", "use_conv_bias": True,
            "mamba_proj_bias": False, "attention_bias": False,
            "mlp_bias": False, "tie_word_embeddings": False,
            "norm_topk_prob": True, "n_shared_experts": 1,
            "num_nextn_predict_layers": 0, "sliding_window": None}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"nemotron_h: {key} = {cfg[key]!r}, not {value!r}")
    if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - set("ME*"):
        raise ValueError(f"nemotron_h: pattern {pattern!r}")
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    mamba = Mamba2Dims(
        num_heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"],
        state=cfg["ssm_state_size"], groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        norm_eps=float(cfg["layer_norm_epsilon"]))
    if mamba.inner != cfg["expand"] * hidden:
        raise ValueError("nemotron_h: mamba heads x head_dim != expand x hidden")
    routed = RoutedDims(
        experts=cfg["router_experts"], held=cfg["n_routed_experts"],
        held_from=cfg["expert_offset"], top_k=cfg["num_experts_per_tok"],
        latent=cfg["moe_latent_size"], width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        scale=float(cfg["routed_scaling_factor"]))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=hidden,
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or hidden // heads,
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["layer_norm_epsilon"]),
        dtype=getattr(jnp, _DTYPES[cfg.get("torch_dtype", "bfloat16")]),
        attention_impl=attention, pattern=pattern, mamba=mamba, routed=routed,
        rope=False)


# ------------------------------------------------------------ sizes -------

def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the configuration's keys alone."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    mamba_mm = h * (inner + conv + cfg["mamba_num_heads"]) + inner * h
    mamba = mamba_mm + conv * (cfg["conv_kernel"] + 1) \
        + 3 * cfg["mamba_num_heads"] + inner + h
    lat = cfg["moe_latent_size"]
    routed_mm = h * cfg["router_experts"] + 2 * h * lat \
        + 2 * h * cfg["moe_shared_expert_intermediate_size"]
    attn_mm = h * d * (2 * cfg["num_attention_heads"]
                       + 2 * cfg["num_key_value_heads"])
    return {"mamba": mamba, "mamba_mm": mamba_mm,
            "routed": routed_mm + cfg["router_experts"] + h,
            "routed_mm": routed_mm,
            "expert": 2 * lat * cfg["moe_intermediate_size"],
            "attn": attn_mm + h, "attn_mm": attn_mm,
            "head": h * cfg["vocab_size"]}


def param_count(cfg: Dict[str, Any], active: bool = False) -> int:
    """Parameters of the configuration as the file has it: with
    `n_routed_experts` experts a routed layer (or, `active`, the
    `num_experts_per_tok` a token meets), embedding and head."""
    z, p = _sizes(cfg), cfg["hybrid_override_pattern"]
    experts = cfg["num_experts_per_tok"] if active else cfg["n_routed_experts"]
    return (p.count("M") * z["mamba"] + p.count("*") * z["attn"]
            + p.count("E") * (z["routed"] + experts * z["expert"])
            + 2 * z["head"] + cfg["hidden_size"])


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return 2 * param_count(cfg)         # bf16 as served


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One sequence's recurrent state over all `M` layers: the SSM state in
    float32 and the convolution's tail in bf16."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return cfg["hybrid_override_pattern"].count("M") * (
        inner * cfg["ssm_state_size"] * 4 + (cfg["conv_kernel"] - 1) * conv * 2)


def decode_step_bytes(cfg: Dict[str, Any], live_kv_tokens: float,
                      touched: float, live_seqs: float) -> float:
    """Bytes ONE decode step must move: every matmul weight outside the
    routed experts once (the Mamba, attention, router, latent and shared-
    expert projections and the head); `touched` x one expert's weights,
    `touched` being the distinct held experts the step's batch met, summed
    over the routed layers (the program's counter: no shape gives it); the
    keys and values of the live tokens in the attention layers; each live
    sequence's recurrent state read and written.  Bandwidth is the bound:
    a step does 2 FLOP per weight byte per sequence."""
    z, p = _sizes(cfg), cfg["hybrid_override_pattern"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    outside = p.count("M") * z["mamba_mm"] + p.count("E") * z["routed_mm"] \
        + p.count("*") * z["attn_mm"] + z["head"]
    kv_per_token = 2 * p.count("*") * cfg["num_key_value_heads"] * d * 2
    return (2.0 * outside + 2.0 * touched * z["expert"]
            + live_kv_tokens * kv_per_token
            + 2.0 * live_seqs * state_bytes(cfg))


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        "nemotron_h is a serving family: no cut of it trains on these chips "
        "with its routed experts at work (PERF.md §4)")


reference_loss = None


# -------------------------------------------------------- reference -------

def _forward(params, tokens, cfg: Dict[str, Any], chosen=None,
             weights: str = ""):
    """Plain float32 forward pass of one sequence: tokens (S,) -> (logits
    (S, V), forgiven).  Straightforward jax.numpy, `highest` matmul
    precision; the recurrence is a `lax.scan` over tokens, the held experts
    are taken one after the other.  `params` is the program's tree (one
    tree a layer, bf16); every layer is cast up as it is used.

    `chosen` (routed layers, S, K) int32, or None: the experts the PROGRAM
    chose.  Where they are not the reference's own top-k, and every one of
    them scores within TOLERANCE["tie_zone"] of the reference's own cut (in
    its float32 `s + bias`), the reference takes the program's; `forgiven`
    counts those, and the positions outside the zone.

    `weights`: a type to round every weight matrix to before it is cast up
    ("float8_e4m3fn": the control, the precision below the one the
    configuration states; `check` must then fail)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    pattern = cfg["hybrid_override_pattern"]
    eps = float(cfg["layer_norm_epsilon"])
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    top_k, first = cfg["num_experts_per_tok"], cfg["expert_offset"]
    held = cfg["n_routed_experts"]
    zone = TOLERANCE["tie_zone"]
    S = tokens.shape[0]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def cast(a):
        if weights and a.ndim > 1:
            a = a.astype(getattr(jnp, weights))
        return a.astype(f32)

    def up(lp):
        return jax.tree.map(cast, lp)

    def mamba(x, lp):
        inner = H * P
        proj = x @ lp["w_in"]
        z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * G * N], -1)
        padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), f32), xbc])
        xbc = lp["conv_b"] + sum(padded[k:k + S] * lp["conv_w"][k]
                                 for k in range(K))
        xbc = jax.nn.silu(xbc)
        xs = xbc[:, :inner].reshape(S, H, P)
        B = jnp.repeat(xbc[:, inner:inner + G * N].reshape(S, G, N), H // G, 1)
        C = jnp.repeat(xbc[:, inner + G * N:].reshape(S, G, N), H // G, 1)
        dt = jax.nn.softplus(dt + lp["dt_bias"])                # (S, H)
        A = -jnp.exp(lp["A_log"])

        def step(h, t):
            x_t, b_t, c_t, dt_t = t
            h = jnp.exp(dt_t * A)[:, None, None] * h \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, c_t)
        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32), (xs, B, C, dt))
        y = (y + lp["D"][:, None] * xs).reshape(S, inner) * jax.nn.silu(z)
        yg = y.reshape(S, G, inner // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        return (yg.reshape(S, inner) * lp["norm"]) @ lp["w_out"]

    def attention(x, lp):
        d = lp["wq"].shape[-1]
        q = jnp.einsum("se,ehd->shd", x, lp["wq"])
        k = jnp.repeat(jnp.einsum("se,ekd->skd", x, lp["wk"]), nh // nkv, 1)
        v = jnp.repeat(jnp.einsum("se,ekd->skd", x, lp["wv"]), nh // nkv, 1)
        sc = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(f32(d))
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("shd,hde->se", jnp.einsum("hst,thd->shd", p, v),
                          lp["wo"])

    def routed(x, lp, theirs, forgiven):
        s = jax.nn.sigmoid(x @ cast(lp["router"]))              # (S, X)
        ranked = s + lp["router_bias"].astype(f32)
        own = jax.lax.top_k(ranked, top_k)[1]
        take = own
        if theirs is not None:
            cut = jnp.sort(ranked, -1)[:, -top_k]
            short = cut - jnp.take_along_axis(ranked, theirs, -1).min(-1)
            other = (theirs[:, :, None] != own[:, None, :]).all(-1)  # (S, K)
            inside = short <= zone
            take = jnp.where((other.any(-1) & inside)[:, None], theirs, own)
            forgiven["forced"] += jnp.where(inside, other.sum(-1), 0).sum()
            forgiven["outside_zone"] += (other.any(-1) & ~inside).sum()
            forgiven["shortfall"] = jnp.maximum(
                forgiven["shortfall"], jnp.where(other.any(-1), short, 0).max())
            forgiven["decisions"] += S * top_k
        w = jnp.take_along_axis(s, take, -1)
        w = w / w.sum(-1, keepdims=True) * f32(cfg["routed_scaling_factor"])
        u = x @ cast(lp["w_down"])

        def expert(acc, e):
            w1, w2, i = e
            mine = jnp.where(take == first + i, w, 0.0).sum(-1)     # (S,)
            hid = jnp.square(jax.nn.relu(u @ cast(w1)))
            return acc + mine[:, None] * (hid @ cast(w2)), None
        mix, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                              (lp["w1"], lp["w2"], jnp.arange(held)))
        shared = jnp.square(jax.nn.relu(x @ cast(lp["ws1"]))) \
            @ cast(lp["ws2"])
        return mix @ cast(lp["w_up"]) + shared

    forgiven = {"forced": 0, "outside_zone": 0, "decisions": 0,
                "shortfall": f32(0)}
    with jax.default_matmul_precision("highest"):
        x = cast(params["embed"])[tokens]
        e = 0
        for kind, lp in zip(pattern, params["layers"]):
            if kind == "M":
                lp = up(lp)
                x = x + mamba(norm(x, lp["ln"]), lp)
            elif kind == "*":
                lp = up(lp)
                x = x + attention(norm(x, lp["ln_attn"]), lp["attn"])
            else:
                x = x + routed(norm(x, lp["ln"]), lp,
                               None if chosen is None else chosen[e], forgiven)
                e += 1
        x = norm(x, params["ln_f"])
        return x @ cast(params["lm_head"]), forgiven


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, forced: bool, weights: str = ""):
    import jax
    cfg = json.loads(cfg_json)
    if forced:
        return jax.jit(lambda p, t, c: _forward(p, t, cfg, c, weights))
    return jax.jit(lambda p, t: _forward(p, t, cfg)[0])


def _shape_keys(cfg: Dict[str, Any]) -> str:
    return json.dumps({k: v for k, v in cfg.items()
                       if not isinstance(v, (dict, list))}, sort_keys=True)


def reference_logits(params, tokens, cfg: Dict[str, Any]):
    """The plain float32 reference with its OWN decisions: tokens (B, S)
    int32 -> logits (B, S, V).  What `refcheck.plain` reads; a routed model
    cannot be held to it (a near-tie decided differently moves a logit by
    several times the dense limits), so `check` below decides."""
    import jax.numpy as jnp
    run = _jitted(_shape_keys(cfg), False)
    return jnp.stack([run(params, row) for row in jnp.asarray(tokens)])


def check(engine, prompt: List[int], served: List[List[int]],
          config: Dict[str, Any], weights: str = "") -> Dict[str, Any]:
    """The check this family owns (benchmark/README.md, "A family that owns
    its reference check").  For each served stream: the engine's own logits
    for the prompt's last position and for every token decoded through the
    pool and the slot's recurrent state (`LLMEngine.trace_logits`), against
    the float32 reference forced to the experts the engine chose inside the
    tie zone.  The first stream was served cold and the second as a
    prefix-cache hit, and each is traced the way it was served: the second
    from the state checkpoint its hit was cut back to (the positions before
    it keep the cold trace's experts, whose pages and state it reads), so
    the checkpoint's path is held to the same limits, and a near-tie the
    two paths decide differently is each path's own.  An expert outside the zone, too many forced, a logit past
    the dense limits, or a served token the reference ranks too low fails
    the run.  `weights`: the control (`_forward`), which must fail; the
    limits were set between its readings and the sound ones
    (`benchmark/tests/precision_control.py` takes both on the chip)."""
    import jax.numpy as jnp
    import numpy as np
    tol, n = TOLERANCE, len(prompt)
    run = _jitted(_shape_keys(config), True, weights)
    worst = {"logit_max": 0.0, "logit_rms": 0.0, "margin": 0.0}
    forgiven = {"forced": 0, "outside_zone": 0, "decisions": 0,
                "shortfall": 0.0,
                "why": "experts the program chose that the reference's "
                       "float32 top-k did not, within tie_zone of its cut"}
    cold, traced_from = None, []
    for i, out in enumerate(served):
        got = engine.trace_logits(prompt, out[:-1], cached=i > 0)
        traced_from.append(int(got["from"]))
        chosen = got["chosen"]
        if got["from"]:
            chosen = jnp.concatenate([cold[:, :got["from"]], chosen], axis=1)
        cold = chosen if cold is None else cold
        toks = jnp.asarray(list(prompt) + list(out[:-1]), jnp.int32)
        ref, f = run(engine.params, toks, chosen)
        mine, ref = np.asarray(got["logits"], np.float32), np.asarray(ref[n - 1:])
        for key in ("forced", "outside_zone", "decisions"):
            forgiven[key] += int(f[key])
        forgiven["shortfall"] = max(forgiven["shortfall"],
                                    float(f["shortfall"]))
        diff = mine - ref
        worst["logit_max"] = max(worst["logit_max"], float(np.abs(diff).max()))
        worst["logit_rms"] = max(worst["logit_rms"], float(
            np.sqrt((diff ** 2).mean(-1)).max()))
        worst["margin"] = max(worst["margin"], float(max(
            row.max() - row[tok] for row, tok in zip(ref, out))))
    share = forgiven["forced"] / max(1, forgiven["decisions"])
    return {**worst, "forced_share": share, "forgiven": forgiven,
            "traced_from": traced_from, "tolerance": tol,
            "ok": bool(all(worst[k] <= tol[k] for k in worst)
                       and forgiven["outside_zone"] == 0
                       and share <= tol["forced_share"])}
