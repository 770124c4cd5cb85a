"""LFM2-MoE family (`model_type: lfm2_moe`; LiquidAI LFM2-24B-A2B).  A layer
is TWO residual halves, an operator and a feed-forward, each behind its own
RMS norm with a learned scale (`norm_eps`):

    x = x + operator_i(norm_op(x));    x = x + ffn_i(norm_ffn(x))

- `layer_types[i] == "conv"`, gated short convolution (`conv_L_cache` K = 3
  taps, `conv_bias` false): `(B, C, u) = split3(h W_in)`, `z = B * u`,
  `c_t = sum_j w[j] * z_{t - K + 1 + j}` (depthwise, causal, per channel, no
  activation), `y = (C * c) W_out`.  A sequence carries `z`'s last K - 1 rows.
- `"full_attention"`: q, k, v without bias to `num_attention_heads` /
  `num_key_value_heads` heads of hidden / heads; an RMS norm with a learned
  scale over each q and each k head; rotary over the whole head
  (`rope_parameters.rope_theta`); causal softmax attention; output projection.
- `i < num_dense_layers`: `ffn(h) = (silu(h W1) * (h W3)) W2`, width
  `intermediate_size`.
- otherwise `num_experts` routed experts of width `moe_intermediate_size`,
  no shared one: `s = sigmoid(h W_r)` in float32; the `num_experts_per_tok`
  largest `s + expert_bias` are chosen (`use_expert_bias`); weights = their
  `s`, normalised over the chosen (`norm_topk_prob`), times
  `routed_scaling_factor`; `ffn(h) = sum_k w_k (silu(h W1_k) * (h W3_k)) W2_k`.
- After the last layer an RMS norm, and the head, which is the embedding
  table (tied).

Departures from the published description, each also under `assumed` in the
configuration's file: the q/k head norm and the tied head are the family's
convention (the catalog's row carries no key for either); the program holds
ONE matrix for embedding and head; the convolution's tail is kept in bf16;
W1 and W3 of an expert lie side by side in one array (`w1`: hidden x 2
width, gate first), which is storage and changes no number.

What the engine keeps for this family (`benchmark/families/nemotron_h.py`
says how both mechanisms work): the convolution tails as the slot's
recurrent state and as STATE CHECKPOINTS in the prefix cache, a row every
`4 x conv_chunk_size` tokens; the TOUCHED-EXPERT COUNTER of the routed
layers' decode steps, which `decode_step_bytes` takes.

All of this is the yardstick's: the mapping onto the program's config, the
plain float32 reference (a loop over layers, the experts one after the
other, scores built whole), the check this family owns, the required bytes.
It reads the program's parameter tree and shares no code with `ray_tpu/`.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Optional

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}

# What `selftest.shrink` applies after its own dense keys (hidden 128, 8
# heads of 16, vocabulary 512, float32): a leading dense layer, one
# attention layer, three convolutions; checkpoints every 4 x 16 tokens.
TINY = {"num_hidden_layers": 4, "num_dense_layers": 1,
        "layer_types": ["conv", "full_attention", "conv", "conv"],
        "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 64, "conv_chunk_size": 16}

# Limits of the check this family owns (`check`), set as nemotron_h's were.
# With the reference forced to the program's experts inside the tie zone,
# what is left is the rounding of continuous operations.  `tie_zone`: how
# far, in the REFERENCE's float32 `s + expert_bias`, an expert the program
# chose may lie below the reference's own cut and be forgiven;
# `forced_share`: the most of the chosen experts (positions x routed layers
# x experts per token) a run may have forgiven.  Each but `tie_zone` lies
# between its two readings on the chip at the cell's sizes (PERF.md §2;
# `python3 -m benchmark.tests.precision_control --workload
# serve_doc_reask_moe`): the largest over the sound seeds and the smallest
# over the control's (the reference's matrices rounded to float8_e4m3fn),
# about their geometric mean.  Over 36 seeds, each read sound and control:
# `logit_max` 0.120-0.166 against 0.280-0.960: 0.22; `logit_rms`
# 0.0268-0.0325 against 0.0635-0.223: 0.045 (18 residual halves in bf16
# under a tied head read twice as rough as the hybrid's 11); `forced_share`
# 0.0160-0.0198 against 0.0322-0.0403: 0.026.  `tie_zone` is the one limit
# that does NOT separate the two for this model, and is set on the sound
# side alone: read with every disagreement forced (a decision left outside
# the zone is not taken, and the two sides then differ in every later layer
# at that position: with a zone of 0.0125 a sound seed read 0.0355 for that
# reason alone) the largest shortfall below the reference's cut is
# 0.0110-0.0207 over 24 sound seeds and 0.0254-0.0417 over 8 of the
# control, two ranges that nearly touch (the hybrid's stood 3 x apart).  A
# limit between them would leave a tenth of room on either side, and the
# largest of ~39,000 decisions a run is an extreme value: one sound run in
# a few dozen would stray and fail a PR that did nothing.  So 0.03, a half
# above the largest sound reading: it still catches an expert that was not
# the model's (a wrong pick lies 0.1-0.5 under the cut), and the control
# fails by each of the three limits above on every seed.  `margin` (how far
# below the reference's best the served token may rank) is the dense
# family's: precision hardly moves it (0-0.033 sound, 0-0.402 control), it
# catches a token that was not the model's.
TOLERANCE = {"logit_max": 0.22, "logit_rms": 0.045, "margin": 0.25,
             "tie_zone": 0.03, "forced_share": 0.026}


def _kinds(cfg: Dict[str, Any]) -> List[str]:
    """Each layer as two letters of the program's pattern: operator (`C`
    convolution, `*` attention), then feed-forward (`F` dense, `E` routed)."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] \
            or set(types) - {"conv", "full_attention"}:
        raise ValueError(f"lfm2_moe: layer_types {types!r}")
    return [("C" if t == "conv" else "*")
            + ("F" if i < cfg["num_dense_layers"] else "E")
            for i, t in enumerate(types)]


def program_config(cfg: Dict[str, Any], *, attention: str = "xla",
                   max_seq_len: Optional[int] = None):
    """The program's TransformerConfig for a configuration file.  Refuses
    what the pattern's kinds cannot express."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import (RoutedDims, ShortConvDims,
                                            TransformerConfig)
    want = {"conv_bias": False, "norm_topk_prob": True,
            "use_expert_bias": True, "tie_word_embeddings": True}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"lfm2_moe: {key} = {cfg[key]!r}, not {value!r}")
    if cfg["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError(f"lfm2_moe: {cfg['rope_parameters']!r}")
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=hidden,
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or hidden // heads,
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_norm_eps=float(cfg["norm_eps"]),
        dtype=getattr(jnp, _DTYPES[cfg.get("torch_dtype", "bfloat16")]),
        attention_impl=attention, pattern=" ".join(_kinds(cfg)),
        routed=RoutedDims(
            experts=cfg["num_experts"], held=cfg["num_experts"], held_from=0,
            top_k=cfg["num_experts_per_tok"], latent=0,
            width=cfg["moe_intermediate_size"], shared_width=0,
            scale=float(cfg["routed_scaling_factor"]), gated=True),
        conv=ShortConvDims(kernel=cfg["conv_L_cache"],
                           chunk=cfg.get("conv_chunk_size", 128)),
        qk_norm=True, tie_embeddings=True)


# ------------------------------------------------------------ sizes -------

def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the configuration's keys alone (`_mm`: the
    matrices a decode step reads, without norms and biases)."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    conv_mm = h * 3 * h + h * h
    attn_mm = h * d * (2 * cfg["num_attention_heads"]
                       + 2 * cfg["num_key_value_heads"])
    return {"conv_mm": conv_mm,
            "conv": conv_mm + cfg["conv_L_cache"] * h + h,
            "attn_mm": attn_mm, "attn": attn_mm + 2 * d + h,
            "router_mm": h * cfg["num_experts"],
            "router": h * cfg["num_experts"] + cfg["num_experts"] + h,
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "dense_mm": 3 * h * cfg["intermediate_size"],
            "dense": 3 * h * cfg["intermediate_size"] + h,
            "embed": h * cfg["vocab_size"]}


def _counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    kinds = "".join(_kinds(cfg))
    return {"conv": kinds.count("C"), "attn": kinds.count("*"),
            "dense": kinds.count("F"), "routed": kinds.count("E")}


def param_count(cfg: Dict[str, Any], active: bool = False) -> int:
    """Parameters of the configuration as the file has it, the tied
    embedding counted once; `active`: with the `num_experts_per_tok` experts
    a token meets in place of all `num_experts`."""
    z, n = _sizes(cfg), _counts(cfg)
    experts = cfg["num_experts_per_tok"] if active else cfg["num_experts"]
    return (n["conv"] * z["conv"] + n["attn"] * z["attn"]
            + n["dense"] * z["dense"]
            + n["routed"] * (z["router"] + experts * z["expert"])
            + z["embed"] + cfg["hidden_size"])


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return 2 * param_count(cfg)         # bf16 as served


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One sequence's recurrent state: the last K - 1 rows of every
    convolution's input, in bf16."""
    return _counts(cfg)["conv"] * (cfg["conv_L_cache"] - 1) \
        * cfg["hidden_size"] * 2


def decode_step_bytes(cfg: Dict[str, Any], live_kv_tokens: float,
                      touched: float, live_seqs: float) -> float:
    """Bytes ONE decode step must move: every matrix outside the routed
    experts once (the convolutions' and attention's projections, the dense
    feed-forward, the routers, and the embedding table as the head);
    `touched` x one expert's three matrices, `touched` being the distinct
    experts the step's batch met, summed over the routed layers (the
    program's counter: no shape gives it, and it is never all experts nor a
    mean); the keys and values of the live tokens in the attention layers;
    each live sequence's convolution tails read and written.  Bandwidth is
    the bound: a step does 2 FLOP per weight byte per sequence."""
    z, n = _sizes(cfg), _counts(cfg)
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    outside = n["conv"] * z["conv_mm"] + n["attn"] * z["attn_mm"] \
        + n["dense"] * z["dense_mm"] + n["routed"] * z["router_mm"] \
        + z["embed"]
    kv_per_token = 2 * n["attn"] * cfg["num_key_value_heads"] * d * 2
    return (2.0 * outside + 2.0 * touched * z["expert"]
            + live_kv_tokens * kv_per_token
            + 2.0 * live_seqs * state_bytes(cfg))


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        "lfm2_moe is a serving family here: no cut with all 64 experts "
        "trains on these chips (PERF.md §4)")


reference_loss = None


# -------------------------------------------------------- reference -------

def _forward(params, tokens, cfg: Dict[str, Any], chosen=None,
             weights: str = ""):
    """Plain float32 forward pass of one sequence: tokens (S,) -> (logits
    (S, V), forgiven).  Straightforward jax.numpy, `highest` matmul
    precision, a loop over layers, the experts one after the other.
    `params` is the program's tree (one tree a half-layer, bf16); every
    matrix is cast up as it is used.

    `chosen` (routed layers, S, K) int32, or None: the experts the PROGRAM
    chose.  Where they are not the reference's own top-k, and every one of
    them scores within TOLERANCE["tie_zone"] of the reference's own cut (in
    its float32 `s + expert_bias`), the reference takes the program's;
    `forgiven` counts those, and the positions outside the zone.

    `weights`: a type to round every weight matrix to before it is cast up
    ("float8_e4m3fn": the control, the precision below the one the
    configuration states; `check` must then fail)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(cfg["norm_eps"])
    K = cfg["conv_L_cache"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    top_k, width = cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    zone = TOLERANCE["tie_zone"]
    S = tokens.shape[0]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def cast(a):
        if weights and a.ndim > 1:
            a = a.astype(getattr(jnp, weights))
        return a.astype(f32)

    def conv(h, lp):
        b, c, u = jnp.split(h @ cast(lp["w_in"]), 3, axis=-1)
        z = jnp.concatenate([jnp.zeros((K - 1, h.shape[1]), f32), b * u])
        w = cast(lp["conv_w"])                                  # (K, E)
        mixed = sum(z[j:j + S] * w[j] for j in range(K))
        return (c * mixed) @ cast(lp["w_out"])

    def rotate(x):                                              # (S, n, d)
        d = x.shape[-1]
        freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = jnp.arange(S, dtype=f32)[:, None] * freqs[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(h, lp):
        d = lp["wq"].shape[-1]
        q = rotate(norm(jnp.einsum("se,ehd->shd", h, cast(lp["wq"])),
                        lp["q_norm"]))
        k = rotate(norm(jnp.einsum("se,ekd->skd", h, cast(lp["wk"])),
                        lp["k_norm"]))
        v = jnp.einsum("se,ekd->skd", h, cast(lp["wv"]))
        k, v = jnp.repeat(k, nh // nkv, 1), jnp.repeat(v, nh // nkv, 1)
        sc = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(f32(d))
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("shd,hde->se", jnp.einsum("hst,thd->shd", p, v),
                          cast(lp["wo"]))

    def dense(h, lp):
        return (jax.nn.silu(h @ cast(lp["w_gate"])) * (h @ cast(lp["w_up"]))) \
            @ cast(lp["w_down"])

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
            w13 = cast(w13)
            hid = jax.nn.silu(h @ w13[:, :width]) * (h @ w13[:, width:])
            return acc + mine[:, None] * (hid @ cast(w2)), None
        out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                              (lp["w1"], lp["w2"],
                               jnp.arange(cfg["num_experts"])))
        return out

    forgiven = {"forced": 0, "outside_zone": 0, "decisions": 0,
                "shortfall": f32(0)}
    with jax.default_matmul_precision("highest"):
        table = cast(params["embed"])
        x = table[tokens]
        e = 0
        for i, kinds in enumerate(_kinds(cfg)):
            op, ffn = params["layers"][2 * i], params["layers"][2 * i + 1]
            if kinds[0] == "C":
                x = x + conv(norm(x, op["ln"]), op)
            else:
                x = x + attention(norm(x, op["ln_attn"]), op["attn"])
            if kinds[1] == "F":
                x = x + dense(norm(x, ffn["ln_mlp"]), ffn["mlp"])
            else:
                x = x + routed(norm(x, ffn["ln"]), ffn,
                               None if chosen is None else chosen[e], forgiven)
                e += 1
        return norm(x, params["ln_f"]) @ table.T, forgiven


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, forced: bool, weights: str = ""):
    import jax
    cfg = json.loads(cfg_json)
    if forced:
        return jax.jit(lambda p, t, c: _forward(p, t, cfg, c, weights))
    return jax.jit(lambda p, t: _forward(p, t, cfg)[0])


def _shape_keys(cfg: Dict[str, Any]) -> str:
    keep = ("layer_types", "rope_parameters")
    return json.dumps({k: v for k, v in cfg.items() if k in keep
                       or not isinstance(v, (dict, list))}, sort_keys=True)


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
    pool and the slot's convolution tails (`LLMEngine.trace_logits`),
    against the float32 reference forced to the experts the engine chose
    inside the tie zone.  The first stream was served cold and the second as
    a prefix-cache hit, and each is traced the way it was served: the second
    from the state checkpoint its hit was cut back to (the positions before
    it keep the cold trace's experts, whose pages and tails it reads).  An
    expert outside the zone, too many forced, a logit past the limits, or a
    served token the reference ranks too low fails the run.  `weights`: the
    control (`_forward`), which must fail."""
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
