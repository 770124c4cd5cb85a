"""DeepSeek-V3 family (`model_type: deepseek_v3`) as Moonlight-16B-A3B
publishes it: no compressed query (`q_lora_rank` null), one expert group
(`n_group` 1).  Every layer is two residual halves behind RMS norms with a
learned scale (`rms_norm_eps`):

    x = x + attention_i(norm_a(x));    x = x + ffn_i(norm_f(x))

- Latent attention (MLA), every layer.  With H = `num_attention_heads`, n =
  `qk_nope_head_dim`, r = `qk_rope_head_dim`, v = `v_head_dim`, R =
  `kv_lora_rank`, no bias (`attention_bias` false):

      q = h W_q                  -> H x [q_n (n) | q_r (r)];  q_r rotated
      [c | k_r] = h W_kva        -> c (R), k_r (r): ONE a token, all heads'
      c = rms_norm_R(c);  k_r rotated            the cache row [c | k_r]
      [k_n,h | v_h] = c W_kvb,h  (R -> n + v a head)
      s_h(t,u) = (q_n,h(t).k_n,h(u) + q_r,h(t).k_r(u)) / sqrt(n + r)
      o_h = sum_u softmax_u(s_h)(t,u) v_h(u)   (causal);  out = concat(o) W_o

  Rotary angles `rope_theta` over the r values alone, no scaling.  This is
  the EXPANDED form, the only one the reference knows; the program also
  attends the cached rows as they lie (the absorbed form: W_kvb's key half
  folded into the query, its value half applied after the sum), which is
  the same function of the same weights.
- `i < first_k_dense_replace`: `ffn(h) = (silu(h W1) * (h W3)) W2`, width
  `intermediate_size`.
- otherwise `n_routed_experts` experts of width `moe_intermediate_size` and
  `n_shared_experts` shared ones, which are one SwiGLU of `n_shared_experts
  x moe_intermediate_size` on every token: `s = sigmoid(h W_r)` in float32;
  the `num_experts_per_tok` largest `s + e_score_correction_bias` are
  chosen (`topk_method` noaux_tc; one group, so no group limit); weights =
  their `s`, normalised over the chosen (`norm_topk_prob`), times
  `routed_scaling_factor`; `ffn(h) = sum_k w_k expert_k(h) + shared(h)`.
- After the last layer an RMS norm and an untied head.

Departures from the published modelling code, each also under `assumed` in
the configuration's file: a rotated pair is split in halves, not
interleaved (with seeded weights a permutation of columns); W1 and W3 of an
expert, and of the shared expert, lie side by side in one array (gate
first), which is storage and changes no number.

What the engine keeps for this family: ONE cache row a token a layer, 576
values, in a page pool of one array (ray_tpu/ops/paged_attention.py: the
"latent" row, padded to 640 lanes on the device); its prefix cache is page-
granular with no recurrent state; the TOUCHED-EXPERT COUNTER of the routed
layers' decode steps, which `decode_step_bytes` takes
(`benchmark/families/nemotron_h.py` says how that mechanism works).

All of this is the yardstick's: the mapping onto the program's config, the
plain float32 reference (a loop over layers, the experts one after the
other, scores built whole a head at a time), the check this family owns, the
required bytes.
It reads the program's parameter tree and shares no code with `ray_tpu/`.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Optional

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}

# What `selftest.shrink` applies after its own dense keys (hidden 128, 8
# heads, vocabulary 512, float32): a leading dense layer and two routed
# ones; a cache row of 144 + 16 = 160 values, which like the published 576
# is more than one lane row and no whole number of them.
TINY = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 64, "kv_lora_rank": 144,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32}

# Limits of the check this family owns (`check`), set as lfm2_moe's were:
# each lies between its two readings on the chip at the cell's sizes
# (`python3 -m benchmark.tests.latent_control --workload
# serve_doc_reask_mla`; my chip runs, PR 44: 12 seeds, each read sound and
# under both controls, a 2,600-token prompt served cold and as a hit), the
# largest over the sound seeds and the smallest over the controls', about
# their geometric mean.  The controls: the reference's matrices rounded to
# float8_e4m3fn (the precision below the configuration's), and the
# PROGRAM's cache rows rounded to float8_e4m3fn where they lie in the pool,
# a cheaper cache, which must read as another result.  `logit_max`
# 0.109-0.137 sound against 0.352-1.94 (cache rows) and 1.46-4.73
# (weights): 0.22; `logit_rms` 0.0222-0.0252 against 0.0743-0.374 and
# 0.310-0.941: 0.043; `forced_share` 0.0129-0.0145 against 0.0673-0.0702
# (weights; rounded cache rows move no router: 0.0130-0.0146): 0.031.
# `tie_zone`: ONE decision in 250,000 outside it fails a run, so it wants
# the most room of the five.  The largest shortfall below the reference's
# cut reads 0.0109-0.0262 sound (those 12 seeds and 26 runs of the cell:
# the largest of a quarter of a million decisions, a tail that reached
# 0.0214 twice and 0.0262 once in 38), 0.43-0.69 with rounded weights
# (thousands of decisions outside any zone) and 0.0236-0.0926 with rounded
# cache rows, a range that touches the sound one: so 0.06, between the
# sound readings and the weights control's (2.3 times the largest, 7 times
# under the least); the rounded cache is caught by the logits, not here:
# both controls fail by `logit_max` and by `logit_rms` on every seed.
# `margin` is the dense family's: precision hardly moves it (0-0.043 sound,
# 0.69-3.7 under either control), it catches a token that was not the
# model's.
TOLERANCE = {"logit_max": 0.22, "logit_rms": 0.043, "margin": 0.25,
             "tie_zone": 0.06, "forced_share": 0.031}


def _dense_layers(cfg: Dict[str, Any]) -> int:
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError(f"deepseek_v3: moe_layer_freq "
                         f"{cfg['moe_layer_freq']!r}, not 1")
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def _kinds(cfg: Dict[str, Any]) -> List[str]:
    """Each layer as two letters of the program's pattern: `L` latent
    attention, then the feed-forward (`F` dense, `E` routed)."""
    dense = _dense_layers(cfg)
    return ["L" + ("F" if i < dense else "E")
            for i in range(cfg["num_hidden_layers"])]


def program_config(cfg: Dict[str, Any], *, attention: str = "xla",
                   max_seq_len: Optional[int] = None):
    """The program's TransformerConfig for a configuration file.  Refuses
    what the pattern's kinds cannot express."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import (LatentDims, RoutedDims,
                                            TransformerConfig)
    want = {"q_lora_rank": None, "attention_bias": False, "n_group": 1,
            "topk_group": 1, "norm_topk_prob": True,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "hidden_act": "silu", "tie_word_embeddings": False,
            "rope_scaling": None, "num_nextn_predict_layers": 0}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(
                f"deepseek_v3: {key} = {cfg[key]!r}, not {value!r}")
    heads = cfg["num_attention_heads"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=heads,
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        dtype=getattr(jnp, _DTYPES[cfg.get("torch_dtype", "bfloat16")]),
        attention_impl=attention, pattern=" ".join(_kinds(cfg)),
        latent=LatentDims(rank=cfg["kv_lora_rank"],
                          nope=cfg["qk_nope_head_dim"],
                          rope=cfg["qk_rope_head_dim"],
                          value=cfg["v_head_dim"]),
        routed=RoutedDims(
            experts=cfg["n_routed_experts"], held=cfg["n_routed_experts"],
            held_from=0, top_k=cfg["num_experts_per_tok"], latent=0,
            width=cfg["moe_intermediate_size"],
            shared_width=cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"],
            scale=float(cfg["routed_scaling_factor"]), gated=True))


# ------------------------------------------------------------ sizes -------

def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the configuration's keys alone (`_mm`: the
    matrices a decode step reads, without norms and biases)."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    R, n = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    r, v = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    attn_mm = h * H * (n + r) + h * (R + r) + R * H * (n + v) + H * v * h
    shared = 3 * h * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return {"attn_mm": attn_mm, "attn": attn_mm + R + h,
            "router_mm": h * cfg["n_routed_experts"] + shared,
            "router": h * cfg["n_routed_experts"] + cfg["n_routed_experts"]
            + shared + h,
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "dense_mm": 3 * h * cfg["intermediate_size"],
            "dense": 3 * h * cfg["intermediate_size"] + h,
            "embed": h * cfg["vocab_size"]}


def _counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    dense = _dense_layers(cfg)
    return {"attn": cfg["num_hidden_layers"], "dense": dense,
            "routed": cfg["num_hidden_layers"] - dense}


def param_count(cfg: Dict[str, Any], active: bool = False) -> int:
    """Parameters of the configuration as the file has it (embedding and
    untied head both); `active`: with the `num_experts_per_tok` routed
    experts a token meets in place of all `n_routed_experts` (the shared
    expert is always met)."""
    z, n = _sizes(cfg), _counts(cfg)
    experts = cfg["num_experts_per_tok"] if active \
        else cfg["n_routed_experts"]
    return (n["attn"] * z["attn"] + n["dense"] * z["dense"]
            + n["routed"] * (z["router"] + experts * z["expert"])
            + 2 * z["embed"] + cfg["hidden_size"])


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return 2 * param_count(cfg)         # bf16 as served


def cache_row_bytes(cfg: Dict[str, Any]) -> int:
    """One token's cache row in one layer, in bf16: [c | k_r] (what the
    algorithm needs; the device pads the row to whole 128-lane rows)."""
    return 2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def latent_decode_bytes(cfg: Dict[str, Any], live_kv_tokens: float) -> float:
    """Bytes the decode step's latent attention kernel must move in ALL its
    layers: every live token's cache row once (it is key and value), no
    more."""
    return live_kv_tokens * cache_row_bytes(cfg) * cfg["num_hidden_layers"]


def decode_step_bytes(cfg: Dict[str, Any], live_kv_tokens: float,
                      touched: float, live_seqs: float) -> float:
    """Bytes ONE decode step must move: every matrix outside the routed
    experts once (latent attention's four, the dense feed-forward, the
    routers and shared experts, and the HEAD: of the embedding table a step
    reads one row a sequence, which is left out); `touched` x one expert's
    three matrices, `touched` being the distinct experts the step's batch
    met, summed over the routed layers (the program's counter: no shape
    gives it, and it is never all experts nor a mean); the live tokens'
    cache rows in every layer, each read once.  There is no recurrent
    state: `live_seqs` moves nothing.  Bandwidth is the bound: a step does 2
    FLOP per weight byte per sequence."""
    z, n = _sizes(cfg), _counts(cfg)
    outside = n["attn"] * z["attn_mm"] + n["dense"] * z["dense_mm"] \
        + n["routed"] * z["router_mm"] + z["embed"]
    return (2.0 * outside + 2.0 * touched * z["expert"]
            + latent_decode_bytes(cfg, live_kv_tokens))


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        "deepseek_v3 is a serving family here: training reaches the dense "
        "decoder alone, and no cut with all 64 experts trains on these "
        "chips (PERF.md §4)")


reference_loss = None


# -------------------------------------------------------- reference -------

def _forward(params, tokens, cfg: Dict[str, Any], chosen=None,
             weights: str = "", rows_from: int = 0):
    """Plain float32 forward pass of one sequence: tokens (S,) -> (logits
    (S - rows_from, V) of the positions from `rows_from` on, forgiven).
    Straightforward jax.numpy, `highest` matmul precision, a loop over
    layers, the experts one after the other, attention in the EXPANDED form;
    heads, experts, the dense width and the vocabulary go a part at a time
    only so that the float32 copies fit beside the served model.
    `params` is the program's tree (one tree a half-layer, bf16); every
    matrix is cast up as it is used.

    `chosen` (routed layers, S, K) int32, or None: the experts the PROGRAM
    chose.  Where they are not the reference's own top-k, and every one of
    them scores within TOLERANCE["tie_zone"] of the reference's own cut (in
    its float32 `s + e_score_correction_bias`), the reference takes the
    program's; `forgiven` counts those, and the positions outside the zone.

    `weights`: a type to round every weight matrix to before it is cast up
    ("float8_e4m3fn": the control, the precision below the one the
    configuration states; `check` must then fail)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(cfg["rms_norm_eps"])
    H = cfg["num_attention_heads"]
    R, n, r = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
               cfg["qk_rope_head_dim"])
    top_k, width = cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * width
    theta = float(cfg["rope_theta"])
    zone = TOLERANCE["tie_zone"]
    S = tokens.shape[0]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def cast(a):
        if weights and a.ndim > 1:
            a = a.astype(getattr(jnp, weights))
        return a.astype(f32)

    def rotate(x):                                      # (S, heads, r)
        freqs = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=f32) / r)
        ang = jnp.arange(S, dtype=f32)[:, None] * freqs[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :r // 2], x[..., r // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(h, lp):
        q = jnp.einsum("se,ehd->shd", h, cast(lp["wq"]))       # (S, H, n+r)
        q_n, q_r = q[..., :n], rotate(q[..., n:])
        row = h @ cast(lp["w_kva"])                             # (S, R + r)
        c = norm(row[:, :R], lp["kv_norm"])
        k_r = rotate(row[:, None, R:])[:, 0]                    # (S, r)
        kv = jnp.einsum("tc,chd->thd", c, cast(lp["w_kvb"]))   # (S, H, n+v)
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

        def head(one):              # a head at a time: S x S scores once
            q_n, q_r, k_n, v = one
            sc = (q_n @ k_n.T + q_r @ k_r.T) / jnp.sqrt(f32(n + r))
            p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            return p @ v
        o = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (
            q_n, q_r, kv[..., :n], kv[..., n:])))               # (H, S, v)
        return jnp.einsum("hsd,hde->se", o, cast(lp["wo"]))

    def dense(h, lp):
        """A slice of the intermediate width at a time (the three matrices
        whole are 0.28 GB in float32)."""
        parts = [p for p in (8, 4, 2, 1)
                 if cfg["intermediate_size"] % p == 0][0]

        def part(acc, w):
            gate, up, down = (cast(a) for a in w)
            return acc + (jax.nn.silu(h @ gate) * (h @ up)) @ down, None
        cut = lambda a, axis: jnp.stack(jnp.split(a, parts, axis=axis))
        return jax.lax.scan(part, jnp.zeros_like(h), (
            cut(lp["w_gate"], 1), cut(lp["w_up"], 1),
            cut(lp["w_down"], 0)))[0]

    def swiglu(h, w13, w2, wide):
        w13 = cast(w13)
        return (jax.nn.silu(h @ w13[:, :wide]) * (h @ w13[:, wide:])) \
            @ cast(w2)

    def routed(h, lp, theirs, forgiven):
        s = jax.nn.sigmoid(h @ cast(lp["router"]))              # (S, X)
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

        def expert(acc, e):
            w13, w2, i = e
            mine = jnp.where(take == i, w, 0.0).sum(-1)         # (S,)
            return acc + mine[:, None] * swiglu(h, w13, w2, width), None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                              (lp["w1"], lp["w2"],
                               jnp.arange(cfg["n_routed_experts"])))
        return out + swiglu(h, lp["ws1"], lp["ws2"], shared)

    def head(x):
        """norm(x) W_head, a slice of the vocabulary at a time written into
        its place, so that neither the float32 copy of the head nor a second
        copy of the logits is ever whole (2,607 x 163,840 logits are 1.7 GB
        beside 13 GB of weights and pool)."""
        w = params["lm_head"]
        parts = [p for p in (16, 8, 4, 2, 1) if w.shape[1] % p == 0][0]
        wide = w.shape[1] // parts

        def part(i, out):
            cols = jax.lax.dynamic_slice_in_dim(w, i * wide, wide, 1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, x @ cast(cols), i * wide, 1)
        return jax.lax.fori_loop(
            0, parts, part, jnp.zeros((x.shape[0], w.shape[1]), f32))

    forgiven = {"forced": 0, "outside_zone": 0, "decisions": 0,
                "shortfall": f32(0)}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32)
        e = 0
        for i, kinds in enumerate(_kinds(cfg)):
            op, ffn = params["layers"][2 * i], params["layers"][2 * i + 1]
            x = x + attention(norm(x, op["ln"]), op["attn"])
            if kinds[1] == "F":
                x = x + dense(norm(x, ffn["ln_mlp"]), ffn["mlp"])
            else:
                x = x + routed(norm(x, ffn["ln"]), ffn,
                               None if chosen is None else chosen[e], forgiven)
                e += 1
        return head(norm(x[rows_from:], params["ln_f"])), forgiven


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, forced: bool, weights: str = "",
            rows_from: int = 0):
    import jax
    cfg = json.loads(cfg_json)
    if forced:
        return jax.jit(lambda p, t, c: _forward(p, t, cfg, c, weights,
                                                rows_from))
    return jax.jit(lambda p, t: _forward(p, t, cfg)[0])


def _shape_keys(cfg: Dict[str, Any]) -> str:
    return json.dumps({k: v for k, v in cfg.items()
                       if not isinstance(v, (dict, list))}, sort_keys=True)


def reference_logits(params, tokens, cfg: Dict[str, Any]):
    """The plain float32 reference with its OWN decisions: tokens (B, S)
    int32 -> logits (B, S, V), as a LIST of B arrays (S, V): `[b]` then gives
    a sequence's logits as they were computed, where indexing one stacked
    array would make the device copy them (1.7 GB a sequence at the cell's
    check, which does not fit twice beside the served model).  What
    `refcheck.plain` reads; a routed model cannot be held to it (a near-tie
    decided differently moves a logit by several times the dense limits),
    so `check` below decides."""
    import jax.numpy as jnp
    run = _jitted(_shape_keys(cfg), False)
    return [run(params, row) for row in jnp.asarray(tokens)]


def check(engine, prompt: List[int], served: List[List[int]],
          config: Dict[str, Any], weights: str = "") -> Dict[str, Any]:
    """The check this family owns (benchmark/README.md, "A family that owns
    its reference check").  For each served stream: the engine's own logits
    for the prompt's last position and for every token decoded through the
    latent pool (`LLMEngine.trace_logits`), against the float32 reference
    (expanded attention) forced to the experts the engine chose inside the
    tie zone.  The first stream was served cold (a whole-prompt prefill:
    the program's expanded form) and the second as a prefix-cache hit (a
    suffix over cached rows and decode steps: its absorbed form), and each
    is traced the way it was served; the positions before the hit keep the
    cold trace's experts, whose pages it reads.  An expert outside the
    zone, too many forced, a logit past the limits, or a served token the
    reference ranks too low fails the run.  `weights`: the control
    (`_forward`), which must fail."""
    import jax.numpy as jnp
    import numpy as np
    tol, n = TOLERANCE, len(prompt)
    run = _jitted(_shape_keys(config), True, weights, n - 1)
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
        mine, ref = np.asarray(got["logits"], np.float32), np.asarray(ref)
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
