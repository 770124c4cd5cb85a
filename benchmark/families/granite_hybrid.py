"""Granite 4.0-H family (`model_type: granitemoehybrid` with
`num_local_experts` 0; IBM granite-4.0-h-micro): forty layers of two
residual halves, each behind its own RMS norm with a learned scale, both
scaled by ONE `residual_multiplier`:

    x0 = embedding_multiplier * E[token]
    x  = x + residual_multiplier * mixer_i(norm(x))
    x  = x + residual_multiplier * W_out(silu(g) * u),  [g | u] = norm(x) W_in
    logits = norm(x_40) E^T / logits_scaling            (the head IS the table)

No positional encoding anywhere (`position_embedding_type` "nope").  The
mixer of a layer is what `layer_types` says:

- "attention": grouped-query, causal, no bias, NO rotation, the scores times
  `attention_multiplier` (1 / 64 at a head width of 64: NOT 1 / sqrt(64)).
- "mamba", Mamba-2: `[z | xBC | dt] = h W_in` (inner | inner + 2 x groups x
  state | heads); `xBC = silu(conv_d(xBC) + b)`, depthwise and causal; split
  x (heads x head width), B, C (groups x state); `dt = softplus(dt +
  dt_bias)`, `A = -exp(A_log)`; per head, in float32, `h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t B_t^T`, `y_t = h_t C_t + D x_t`; `y = rms_norm(y *
  silu(z))` over each group's share of the inner width (one group: all of
  it) with a learned scale; `y W_out`.

What the engine keeps for this family: keys and values of the attention
layers in pages (8 KV heads x 64: the "lanes" pool row), a state row a slot
(the SSM state in float32 and the convolution's last three inputs in bf16,
every Mamba layer's), and STATE CHECKPOINTS in the prefix cache every 512
tokens (`benchmark/families/nemotron_h.py` says how checkpoints work).  The
stack is a PERIOD repeated (`layer_types` is ten layers four times over),
which the program scans: `program_config` finds the period.

All of this is the yardstick's: the mapping onto the program's config, the
plain float32 reference (its own copy of every equation above, the
recurrence a `lax.scan` over tokens; it reads the program's parameter tree
and shares no code with `ray_tpu/models` nor with another family), the
required bytes, and the check.  No discrete decision lies inside, and the
family still owns its `check` (at the end): the plain one reads the cold
whole-prompt prefill's last row, which reads neither a cached key row nor a
slot's state, and those are what this family caches.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Optional

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}
_KIND = {"mamba": "M", "attention": "*"}


def _require_the_program() -> None:
    """A program that cannot repeat a period or carry the four multipliers
    (a parent commit the benchmark's files are laid over) must fail HERE, as
    the cell is loaded: at once, in the driver's process, before a runtime
    or a TPU client is made (the import initialises no backend)."""
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig
    have = {f.name for f in dataclasses.fields(TransformerConfig)}
    need = {"repeats", "embedding_multiplier", "residual_multiplier",
            "attention_scale", "logit_divisor"}
    if need - have:
        raise ImportError(
            "granite_hybrid: this program's TransformerConfig has no "
            f"{sorted(need - have)} (models/transformer.py)")


_require_the_program()

# What `selftest.shrink` applies after its own dense keys (hidden 128, 8
# query and 4 KV heads of 16, vocabulary 512, float32): a period of three
# layers twice over, so that the rehearsal scans too; checkpoints every 4 x
# 16 tokens.
TINY = {"num_hidden_layers": 6,
        "layer_types": ["mamba", "attention", "mamba"] * 2,
        "shared_intermediate_size": 256, "mamba_n_heads": 8,
        "mamba_d_head": 32, "mamba_d_state": 16, "mamba_n_groups": 1,
        "mamba_expand": 2, "mamba_chunk_size": 16,
        "attention_multiplier": 0.0625}

# Limits of the family's `check` (at the end: every row of both served
# streams against this reference), between two readings on the chip at the
# cell's sizes (`python3 -m benchmark.tests.granite_control --workload
# serve_chat_ssm --seeds 101,...,112`: my chip runs, PR 51, 12 seeds; a
# 600-token prompt cold, as a hit from the checkpoint at 512 with 88 tokens
# run again over their cached pages, and 2 x 159 tokens decoded through the
# pages and the state: 320 rows a seed).
# SOUND: `logit_max` 0.0228-0.0288, `logit_rms` (the worst row's)
# 0.00472-0.00594, flat from the first decoded row to the last; `margin`
# 0.000 on every seed (the logits are the tied table's over 8: small, and
# the served token is the reference's own on every row).
# STATE CONTROL (the program's SSM state, slot rows and checkpoints, held
# in bfloat16, the nearest type below the float32 the configuration
# states): 0.0495-0.0718 and 0.01061-0.01574: fails both limits on every
# seed.  A row's rms grows with the steps through the state: 0.0044-0.0053
# at the first decoded row, 0.0057-0.0082 at the 80th (ten seeds of twelve
# would PASS there), 0.0106-0.0157 at the 159th: hence
# `check_output_tokens` 160.
# WEIGHTS CONTROL (the PROGRAM's matrices rounded to float8_e4m3fn, 2
# seeds): 0.252-0.263 and 0.0521-0.0565: ten times over.
# CACHE CONTROL (the pools' key and value rows rounded to float8_e4m3fn
# where they lie, 2 seeds): 0.0244-0.0248 and 0.00470-0.00520: it reads
# like the sound engine and DOES NOT FAIL, and no logit check can make it:
# with seeded weights and scores times 1/64 the four attention layers'
# softmax is nearly flat over 600 keys, a head's output is a mean of 600
# random rows (1/25 of one row's size) and a rounding of 2^-4 in each
# averages out with it; what is left moves the logits by less than the
# bf16 rounding of the other 76 halves (PERF.md §7).
# THE LIMITS lie between the largest sound reading and the STATE control's
# least, at their geometric mean: 0.0288 x 0.0495 -> 0.038 (1.31 above the
# largest sound reading, 1.30 under the control's least), 0.00594 x 0.01061
# -> 0.0079 (1.33, 1.34).  `margin` is the dense family's: precision hardly
# moves it, it catches a token that was not the model's.
TOLERANCE = {"logit_max": 0.038, "logit_rms": 0.0079, "margin": 0.25}


def period_of(kinds: str) -> str:
    """The shortest prefix of `kinds` that, repeated, spells all of it."""
    n = len(kinds)
    return next(kinds[:p] for p in range(1, n + 1)
                if n % p == 0 and kinds[:p] * (n // p) == kinds)


def program_config(cfg: Dict[str, Any], *, attention: str = "xla",
                   max_seq_len: Optional[int] = None,
                   state_dtype: Optional[str] = None):
    """The program's TransformerConfig for a configuration file.  Refuses
    what the pattern's kinds cannot express.  `state_dtype`: the control's
    (the SSM state held in another type than the file states)."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import Mamba2Dims, TransformerConfig
    want = {"attention_bias": False, "hidden_act": "silu",
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "normalization_function": "rmsnorm", "num_local_experts": 0,
            "num_experts_per_tok": 0, "position_embedding_type": "nope",
            "rope_scaling": None, "tie_word_embeddings": True}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(
                f"granite_hybrid: {key} = {cfg[key]!r}, not {value!r}")
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or set(types) - set(_KIND):
        raise ValueError(f"granite_hybrid: layer_types {types!r}")
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["shared_intermediate_size"] != cfg.get(
            "intermediate_size", cfg["shared_intermediate_size"]):
        raise ValueError("granite_hybrid: two feed-forward widths")
    mamba = Mamba2Dims(
        num_heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
        state=cfg["mamba_d_state"], groups=cfg["mamba_n_groups"],
        conv_kernel=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
        norm_eps=float(cfg["rms_norm_eps"]),
        state_dtype=state_dtype or cfg.get("mamba_state_dtype", "float32"))
    if mamba.inner != cfg["mamba_expand"] * hidden:
        raise ValueError("granite_hybrid: mamba heads x head != expand x hidden")
    period = period_of("".join(_KIND[t] for t in types))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=hidden,
        intermediate_size=cfg["shared_intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or hidden // heads,
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        dtype=getattr(jnp, _DTYPES[cfg.get("torch_dtype", "bfloat16")]),
        attention_impl=attention,
        pattern=" ".join(k + "F" for k in period),
        repeats=len(types) // len(period), mamba=mamba, rope=False,
        tie_embeddings=True,
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_scale=float(cfg["attention_multiplier"]),
        logit_divisor=float(cfg["logits_scaling"]))


# ------------------------------------------------------------ sizes -------

def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the configuration's keys alone (`_mm`: the
    matrices a decode step reads, without norms, biases and the
    convolution)."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    heads = cfg["mamba_n_heads"]
    inner = heads * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    mamba_mm = h * (inner + conv + heads) + inner * h
    attn_mm = h * d * (2 * nh + 2 * nkv)
    ffn_mm = 3 * h * cfg["shared_intermediate_size"]
    return {"mamba_mm": mamba_mm,
            "mamba": mamba_mm + conv * (cfg["mamba_d_conv"] + 1) + 3 * heads
            + inner + h,
            "attn_mm": attn_mm, "attn": attn_mm + h,
            "ffn_mm": ffn_mm, "ffn": ffn_mm + h,
            "embed": h * cfg["vocab_size"]}


def _count(cfg: Dict[str, Any], kind: str) -> int:
    return cfg["layer_types"].count(kind)


def param_count(cfg: Dict[str, Any], active: bool = False) -> int:
    """Parameters of the configuration as the file has it (dense: `active`
    changes nothing); the table is counted once, it is the head too."""
    z = _sizes(cfg)
    return _count(cfg, "mamba") * z["mamba"] \
        + _count(cfg, "attention") * z["attn"] \
        + cfg["num_hidden_layers"] * z["ffn"] + z["embed"] \
        + cfg["hidden_size"]


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return 2 * param_count(cfg)         # bf16 as served


def state_bytes(cfg: Dict[str, Any]) -> int:
    """One sequence's recurrent state over all the Mamba layers: the SSM
    state in float32 and the convolution's tail in bf16."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return _count(cfg, "mamba") * (
        inner * cfg["mamba_d_state"] * 4 + (cfg["mamba_d_conv"] - 1) * conv * 2)


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * _count(cfg, "attention") * cfg["num_key_value_heads"] * d * 2


def decode_step_bytes(cfg: Dict[str, Any], live_kv_tokens: float,
                      live_seqs: float) -> float:
    """Bytes ONE decode step must move: every matrix once (the layers', and
    the table once as the head; the embedding's one row a sequence is
    nothing beside them), the keys and values of the live tokens in the
    attention layers, and each live sequence's recurrent state read and
    written.  Bandwidth is the bound: a step does 2 FLOP per weight byte
    per sequence."""
    z = _sizes(cfg)
    weights = _count(cfg, "mamba") * z["mamba_mm"] \
        + _count(cfg, "attention") * z["attn_mm"] \
        + cfg["num_hidden_layers"] * z["ffn_mm"] + z["embed"]
    return (2.0 * weights + live_kv_tokens * kv_bytes_per_token(cfg)
            + 2.0 * live_seqs * state_bytes(cfg))


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        "granite_hybrid is a serving family here: the program trains the "
        "dense block alone (ROADMAP R1)")


reference_loss = None


# -------------------------------------------------------- reference -------

_VOCAB_BLOCK = 12544


def _forward(params, tokens, cfg: Dict[str, Any], first: int = 0):
    """Plain float32 forward pass of one sequence: tokens (S,) -> logits
    (S - first, V), rows `first` and after.  Straightforward jax.numpy,
    `highest` matmul precision, a loop over a period's layers, the
    recurrence a `lax.scan` over tokens.  `params` is the program's tree:
    `layers` holds a tree a block of ONE period (a mixer half, then a
    feed-forward half, a layer), every leaf the repeats stacked in front
    where the period repeats, and the period is then scanned over them
    (forty layers traced as ten); each block is cast up as it is used."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(cfg["rms_norm_eps"])
    types = cfg["layer_types"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, K = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    res = f32(cfg["residual_multiplier"])
    S = tokens.shape[0]
    blocks = len(params["layers"])          # of one period
    repeats = 2 * len(types) // blocks

    def norm(x, scale, eps=eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def up(lp):
        return jax.tree.map(lambda a: a.astype(f32), lp)

    def mamba(h, lp):
        inner = H * P
        proj = h @ lp["w_in"]
        z = proj[:, :inner]
        xbc = proj[:, inner:2 * inner + 2 * G * N]
        dt = proj[:, 2 * inner + 2 * G * N:]
        padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), f32), xbc])
        xbc = jax.nn.silu(lp["conv_b"] + sum(
            padded[k:k + S] * lp["conv_w"][k] for k in range(K)))
        xs = xbc[:, :inner].reshape(S, H, P)
        B = jnp.repeat(xbc[:, inner:inner + G * N].reshape(S, G, N), H // G, 1)
        C = jnp.repeat(xbc[:, inner + G * N:].reshape(S, G, N), H // G, 1)
        dt = jax.nn.softplus(dt + lp["dt_bias"])                # (S, H)
        A = -jnp.exp(lp["A_log"])

        def step(state, t):
            x_t, b_t, c_t, dt_t = t
            state = jnp.exp(dt_t * A)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            return state, jnp.einsum("hpn,hn->hp", state, c_t)
        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32), (xs, B, C, dt))
        y = (y + lp["D"][:, None] * xs).reshape(S, inner) * jax.nn.silu(z)
        yg = y.reshape(S, G, inner // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        return (yg.reshape(S, inner) * lp["norm"]) @ lp["w_out"]

    def attention(h, w):
        q = jnp.einsum("se,ehd->shd", h, w["wq"])
        k = jnp.repeat(jnp.einsum("se,ekd->skd", h, w["wk"]), nh // nkv, 1)
        v = jnp.repeat(jnp.einsum("se,ekd->skd", h, w["wv"]), nh // nkv, 1)
        sc = jnp.einsum("shd,thd->hst", q, k) * f32(cfg["attention_multiplier"])
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("shd,hde->se", jnp.einsum("hst,thd->shd", p, v),
                          w["wo"])

    def dense(h, w):
        return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]

    with jax.default_matmul_precision("highest"):
        def period(x, layers):
            for i, kind in enumerate(types[:blocks // 2]):
                op, ffn = up(layers[2 * i]), up(layers[2 * i + 1])
                if kind == "mamba":
                    x = x + res * mamba(norm(x, op["ln"]), op)
                else:
                    x = x + res * attention(norm(x, op["ln_attn"]),
                                            op["attn"])
                x = x + res * dense(norm(x, ffn["ln_mlp"]), ffn["mlp"])
            return x

        x = f32(cfg["embedding_multiplier"]) \
            * params["embed"][tokens].astype(f32)
        if repeats > 1:
            x, _ = jax.lax.scan(lambda x, lp: (period(x, lp), None), x,
                                params["layers"])
        else:
            x = period(x, params["layers"])
        # The head a block of the vocabulary at a time: the table cast up
        # whole is 0.82 GB of float32 beside the engine the check holds.
        x, table = norm(x, params["ln_f"])[first:], params["embed"]
        V = table.shape[0]
        width = next(w for w in range(min(V, _VOCAB_BLOCK), 0, -1)
                     if V % w == 0)

        def rows(i, logits):                                # written in place
            cut = jax.lax.dynamic_slice_in_dim(table, i * width, width, 0)
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ cut.astype(f32).T, i * width, 1)
        logits = jax.lax.fori_loop(0, V // width, rows,
                                   jnp.zeros((x.shape[0], V), f32))
        return logits / f32(cfg["logits_scaling"])


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, first: int = 0):
    import jax
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, t: _forward(p, t, cfg, first))


def _shape_keys(cfg: Dict[str, Any]) -> str:
    return json.dumps({k: v for k, v in cfg.items()
                       if not isinstance(v, dict)}, sort_keys=True)


def reference_logits(params, tokens, cfg: Dict[str, Any]):
    """The plain float32 reference: tokens (B, S) int32 -> logits [b][s][v],
    one (S, V) array a sequence (`[0]` of a tuple copies nothing)."""
    import jax.numpy as jnp
    run = _jitted(_shape_keys(cfg))
    tokens = jnp.asarray(tokens)
    return tuple(run(params, tokens[i]) for i in range(tokens.shape[0]))


# ------------------------------------------------------------ the check ---

def reference_rows(params, prompt, served, cfg: Dict[str, Any]) -> list:
    """The reference's rows for each served stream, from `params`: (tokens,
    V) float32 each, the prompt's last position and then every served
    token's but the last (the rows that predict `served[i]`)."""
    import jax.numpy as jnp
    import numpy as np
    run = _jitted(_shape_keys(cfg), len(prompt) - 1)
    return [np.asarray(run(params, jnp.asarray(
        list(prompt) + list(out[:-1]), jnp.int32))) for out in served]


def read(engine, prompt, served, refs) -> Dict[str, Any]:
    """Every row the path computes for each stream (`LLMEngine.trace_logits`:
    the first cold, the others as they were served, from whatever checkpoint
    the prefix cache holds) against `refs`: the worst value and the worst
    row's rms over all of them, the reference's margin for the served
    tokens, and where each stream was traced from.  `by_row`: the rows'
    rms at a few places of each stream, for the reader."""
    import numpy as np
    out = {"logit_max": 0.0, "logit_rms": 0.0, "margin": 0.0,
           "traced_from": [], "rows": 0, "by_row": []}
    for i, (toks, ref) in enumerate(zip(served, refs)):
        got = engine.trace_logits(prompt, toks[:-1], cached=i > 0)
        out["traced_from"].append(int(got["from"]))
        diff = np.asarray(got["logits"], np.float32) - ref
        rms = np.sqrt((diff ** 2).mean(-1))
        out["rows"] += len(rms)
        out["by_row"].append({int(r): float(rms[r]) for r in sorted(
            {0, 1, len(rms) // 4, len(rms) // 2, len(rms) - 1})})
        out["logit_max"] = max(out["logit_max"], float(np.abs(diff).max()))
        out["logit_rms"] = max(out["logit_rms"], float(rms.max()))
        out["margin"] = max(out["margin"], float(max(
            row.max() - row[t] for row, t in zip(ref, toks))))
    return out


def check(engine, prompt: List[int], served: List[List[int]],
          config: Dict[str, Any]) -> Dict[str, Any]:
    """The check this family owns (benchmark/README.md, "A family that owns
    its reference check"), and why it owns one though no discrete decision
    lies inside: `refcheck.plain` reads the cold whole-prompt prefill's last
    row, which reads no cached key row and no slot's state.  What this
    family caches is both: so each served stream is traced the way it was
    served, the first cold and the second from the state checkpoint its hit
    found (its suffix attends the hit's cached pages), every served token
    decoded through the pages and the state, and EVERY row is held to the
    reference by the limits the plain check holds one row to.  The second
    stream must have started from a checkpoint, or nothing of one was
    checked."""
    got = read(engine, prompt, served,
               reference_rows(engine.params, prompt, served, config))
    tol = TOLERANCE
    got.update(
        tolerance=tol,
        forgiven={"why": "nothing: no discrete decision lies inside"},
        ok=bool(all(got[k] <= tol[k] for k in tol)
                and all(at > 0 for at in got["traced_from"][1:])))
    return got
