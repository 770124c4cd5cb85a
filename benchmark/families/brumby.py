"""Brumby family (`model_type: brumby`; Manifest AI Brumby-14B-Base): a
decoder whose every layer is a POWER RETENTION layer and a SwiGLU
feed-forward, each a residual half behind its own RMS norm with a learned
scale (`rms_norm_eps`).  The widths are Qwen3-14B's, key for key: hidden
5,120; 40 layers, all alike; 40 query heads and 8 KV heads of 128;
intermediate 17,408; vocabulary 151,936, untied head; rope theta 1e6, no
scaling; 32,768 positions; bf16.

    x = x + retention_i(norm_attn(x));    x = x + ffn_i(norm_mlp(x))

Power retention, degree 2, gated (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239, and the Brumby release note), with
h a query head, g(h) = h // 5 its KV head, d = 128, x~ the normed rows:

    q_h = rope(rms_norm_d(x~ W_q)_h)    k_g = rope(rms_norm_d(x~ W_k)_g)    v_g = (x~ W_v)_g
    a_g(t) = log sigmoid((x~ W_g + b_g)_g(t))       <= 0, ONE a KV head a token
    A_g(t) = sum_{r <= t} a_g(r)
    w_h(t,s) = exp(A_g(t) - A_g(s)) (q_h(t).k_g(s) / sqrt d)^2     s <= t, else 0
    o_h(t)   = sum_s w_h(t,s) v_g(s) / (sum_s w_h(t,s) + eps)
    out = x + concat_h(o_h) W_o

That is the ATTENTION form, and the only one this file computes.  The
program also runs the same numbers as a recurrent state (phi(x).phi(y) =
(x.y)^2: `ray_tpu/models/retention.py`), which is all it caches: the state
is 8 KV heads x D x (128 + 1) float32 a layer a sequence, D = 8,256 as
published (the exact symmetric square) and 9,216 as the program holds it
(blocks of 16: whole 128-lane rows).  `ffn(h) = (silu(h W1) * (h W3)) W2`.
After the last layer an RMS norm and the untied head.

Departures from the published description, each also under `assumed` in the
configuration's file: the degree, the gate's width (a KV head's) and bias,
the q/k head norm and the rotation kept from the Qwen3 shape, `eps` and the
1 / sqrt d scale, the scan chunk, the state's type, and the seeded gate: the
catalog's row carries no key for any of them.

What the engine keeps for this family: NO page (no layer attends); a state
row a slot, and STATE CHECKPOINTS in the prefix cache every `4 x
retention_chunk_size` tokens, of which a prefill keeps the last few it
passes (`benchmark/families/nemotron_h.py` says how checkpoints work).

All of this is the yardstick's: the mapping onto the program's config, the
plain float32 reference (a loop over layers, a block of query rows at a
time against all the keys, so that a 2,600-token prompt fits beside the
weights), the required bytes.  It reads the program's parameter tree and
shares no code with `ray_tpu/`.  No discrete decision lies inside, and yet
the family owns its `check` (at the end): the plain one reads the cold
prefill's row, which never reads the state, and the state is all this
family caches.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Optional

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def _require_the_kind() -> None:
    """A program without power retention (a parent commit the benchmark's
    files are laid over) must fail HERE, as the cell is loaded: at once, in
    the driver's process, before a runtime or a TPU client is made (the
    import initialises no backend)."""
    from ray_tpu.models import transformer
    if "P" not in transformer.KINDS:
        raise ImportError(
            "brumby: this program has no power retention kind (`P` is not "
            f"in models/transformer.py KINDS = {transformer.KINDS!r})")


_require_the_kind()

# What `selftest.shrink` applies after its own dense keys (hidden 128, 8
# query and 4 KV heads of 16, vocabulary 512, float32): three layers,
# phi in blocks of 4 (D = 160), checkpoints every 4 x 16 tokens.
TINY = {"num_hidden_layers": 3, "retention_block": 4,
        "retention_chunk_size": 16}

# Limits of the family's `check` (at the end: every row of both served
# streams against this reference; the plain check's one row is held to them
# too), between two readings on the chip at the cell's sizes (`python3 -m
# benchmark.tests.retention_control --workload serve_doc_reask_retention
# --seeds 61,...,72`: my chip runs, PR 46, 12 seeds; a 2,600-token prompt
# cold, as a hit from the checkpoint at 2,560 with 40 tokens run again, and
# 2 x 183 tokens decoded through the state: 368 rows a seed; and 12 seeds
# more, 41-52, at 2 x 255).  (q.k)^2 doubles a product's relative error: a
# bf16 rounding that moves q.k by 2^-9 moves a weight by 2^-8, which the
# normaliser mostly takes back; six layers read no rougher than the older
# families' (the hybrid's 11 halves: 0.064).
# SOUND: `logit_max` 0.0871-0.1005, `logit_rms` (the worst row's)
# 0.0177-0.0185, flat from the first decoded row to the last (0.0147-0.0182
# at rows 0, 1, 46, 92, 183); at 2 x 255: 0.0906-0.1048, 0.0176-0.0190;
# `margin` 0.026-0.071.
# STATE CONTROL (the program's state, slot rows and checkpoints, held in
# bfloat16, the nearest type below the float32 the configuration states):
# 0.1964-0.2269 and 0.0397-0.0439: fails both limits on every seed.  A
# row's rms grows as the steps through the state add up, a rounding of the
# whole state a step: 0.015-0.018 at the first decoded row, 0.024-0.028 at
# the 46th, 0.028-0.035 at the 92nd, 0.036-0.042 at the 183rd (at 2 x 255:
# 0.2265-0.2541 and 0.0457-0.0498).  Over the sibling cells' 8 check tokens
# it read 0.087-0.104 / 0.0180-0.0195 beside 0.079-0.098 / 0.0167-0.0179
# sound and PASSED (12 other seeds, before review): hence
# `check_output_tokens` 184 here, the most the rehearsal's engine serves.
# WEIGHTS CONTROL (the PROGRAM's matrices rounded to float8_e4m3fn):
# 1.906-2.214 and 0.369-0.396: 13 times over.
# THE LIMITS lie between the sound readings (the largest of the 24 seeds)
# and the STATE control's least, the nearer one, at their geometric mean:
# 0.1048 x 0.1964 -> 0.145 (1.38 above the largest sound reading, 1.35
# under the control's least; the readings of either side lie within 8% of
# their mean), 0.0190 x 0.0397 -> 0.0275 (1.45, 1.44).  `margin` is the
# dense family's: precision hardly moves it, it catches a token that was
# not the model's.
TOLERANCE = {"logit_max": 0.145, "logit_rms": 0.0275, "margin": 0.25}


def program_config(cfg: Dict[str, Any], *, attention: str = "xla",
                   max_seq_len: Optional[int] = None,
                   state_dtype: Optional[str] = None):
    """The program's TransformerConfig for a configuration file.  Refuses
    what the pattern's kinds cannot express.  `state_dtype`: the control's
    (the state held in another type than the file states)."""
    import jax.numpy as jnp
    from ray_tpu.models.transformer import RetentionDims, TransformerConfig
    want = {"attention_bias": False, "tie_word_embeddings": False,
            "hidden_act": "silu", "rope_scaling": None,
            "use_sliding_window": False, "retention_degree": 2}
    for key, value in want.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"brumby: {key} = {cfg[key]!r}, not {value!r}")
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d = cfg.get("head_dim") or hidden // heads
    n = cfg["num_hidden_layers"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=hidden,
        intermediate_size=cfg["intermediate_size"], num_layers=n,
        num_heads=heads, num_kv_heads=cfg["num_key_value_heads"], head_dim=d,
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        dtype=getattr(jnp, _DTYPES[cfg.get("torch_dtype", "bfloat16")]),
        attention_impl=attention, pattern=" ".join(["PF"] * n),
        retention=RetentionDims(
            degree=cfg.get("retention_degree", 2), num_heads=heads,
            num_kv_heads=cfg["num_key_value_heads"], head_dim=d,
            chunk=cfg.get("retention_chunk_size", 128),
            block=cfg.get("retention_block", 16),
            eps=float(cfg.get("retention_eps", 1e-6)),
            state_dtype=state_dtype
            or cfg.get("retention_state_dtype", "float32")),
        qk_norm=True)


# ------------------------------------------------------------ sizes -------

def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the configuration's keys alone (`_mm`: the
    matrices a decode step reads, without norms and biases)."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    mix_mm = h * d * (2 * nh + 2 * nkv) + h * nkv       # q, o, k, v, gate
    ffn_mm = 3 * h * cfg["intermediate_size"]
    return {"mix_mm": mix_mm, "mix": mix_mm + nkv + 2 * d + h,
            "ffn_mm": ffn_mm, "ffn": ffn_mm + h,
            "embed": h * cfg["vocab_size"]}


def param_count(cfg: Dict[str, Any], active: bool = False) -> int:
    """Parameters of the configuration as the file has it (dense: `active`
    changes nothing), embedding and untied head both counted."""
    z = _sizes(cfg)
    return cfg["num_hidden_layers"] * (z["mix"] + z["ffn"]) \
        + 2 * z["embed"] + cfg["hidden_size"]


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return 2 * param_count(cfg)         # bf16 as served


def state_bytes(cfg: Dict[str, Any], published: bool = True) -> int:
    """One sequence's state over all layers, float32: KV heads x D x (d +
    1) a layer.  `published`: D = d (d + 1) / 2, the exact symmetric
    square, the least any form must hold; else the tiled D the program's
    `retention_block` gives."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    b = 1 if published else cfg.get("retention_block", 16)
    D = (d // b) * (d // b + 1) // 2 * b * b
    return cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * D \
        * (d + 1) * 4


def decode_step_bytes(cfg: Dict[str, Any], live_kv_tokens: float,
                      live_seqs: float) -> float:
    """Bytes ONE decode step must move: every matrix once (the layers' and
    the head; the embedding's one row a sequence is nothing beside them)
    and each live sequence's state read and written, in the PUBLISHED
    width (8 x 8,256 x 129 x 4 B a layer), whatever block the program
    holds it in: a change of form then moves the share and not the
    yardstick.  No byte follows the tokens a sequence has read
    (`live_kv_tokens` is taken for the readers' sake and counts nothing).
    Bandwidth is the bound: a step does 2 FLOP per weight byte per
    sequence and 4 per state byte."""
    z = _sizes(cfg)
    weights = cfg["num_hidden_layers"] * (z["mix_mm"] + z["ffn_mm"]) \
        + z["embed"]
    return 2.0 * weights + 2.0 * live_seqs * state_bytes(cfg)


def retention_step_bytes(cfg: Dict[str, Any], live_seqs: float) -> float:
    """Bytes the state's update and query of ONE decode step must move: the
    live sequences' published state, read and written, in every layer."""
    return 2.0 * live_seqs * state_bytes(cfg)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    raise NotImplementedError(
        "brumby is a serving family here: the program trains the dense "
        "block alone, and at 8 bytes a parameter four layers and an eighth "
        "of the vocabulary are 12.1 GB before any activation (PERF.md §4)")


reference_loss = None


# -------------------------------------------------------- reference -------

_QUERY_BLOCK = 256
_VOCAB_BLOCK = 20000
_WIDTH_BLOCK = 4352


def _forward(params, tokens, cfg: Dict[str, Any], first: int = 0):
    """Plain float32 forward pass of one sequence: tokens (S,) -> logits
    (S - first, V), rows `first` and after.  Straightforward jax.numpy,
    `highest` matmul precision, a loop over layers, the ATTENTION form of
    power retention a block of query rows at a time against all the keys.
    `params` is the program's tree (one tree a half-layer, bf16); every
    matrix is cast up as it is used."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    eps = float(cfg["rms_norm_eps"])
    ret_eps = float(cfg.get("retention_eps", 1e-6))
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    theta = float(cfg["rope_theta"])
    S = tokens.shape[0]

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def cast(a):
        return a.astype(f32)

    def rotate(x):                                              # (S, n, d)
        d = x.shape[-1]
        freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = jnp.arange(S, dtype=f32)[:, None] * freqs[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def retention(h, w):
        d = w["wq"].shape[-1]
        q = rotate(norm(jnp.einsum("se,ehd->shd", h, cast(w["wq"])),
                        w["q_norm"]))
        k = rotate(norm(jnp.einsum("se,ekd->skd", h, cast(w["wk"])),
                        w["k_norm"]))
        v = jnp.einsum("se,ekd->skd", h, cast(w["wv"]))
        a = jax.nn.log_sigmoid(h @ cast(w["wg"]) + cast(w["bg"]))  # (S, G)
        A = jnp.repeat(jnp.cumsum(a, axis=0), nh // nkv, axis=1)   # (S, H)
        k, v = jnp.repeat(k, nh // nkv, 1), jnp.repeat(v, nh // nkv, 1)
        out = []
        for at in range(0, S, _QUERY_BLOCK):
            qb, Ab = q[at:at + _QUERY_BLOCK], A[at:at + _QUERY_BLOCK]
            rows = at + jnp.arange(qb.shape[0])
            sc = jnp.einsum("shd,thd->hst", qb, k) / jnp.sqrt(f32(d))
            seen = rows[:, None] >= jnp.arange(S)[None, :]
            decay = jnp.exp(jnp.where(seen, Ab.T[:, :, None] - A.T[:, None, :],
                                      -jnp.inf))
            wgt = sc * sc * decay
            num = jnp.einsum("hst,thd->shd", wgt, v)
            out.append(num / (wgt.sum(-1).T[..., None] + ret_eps))
        return jnp.einsum("shd,hde->se", jnp.concatenate(out), cast(w["wo"]))

    def dense(h, lp):
        # A block of the intermediate width at a time: the three matrices
        # cast up whole are 1.07 GB of float32 beside the engine.
        m = lp["w_gate"].shape[1]
        out = 0.0
        for at in range(0, m, _WIDTH_BLOCK):
            cut = slice(at, at + _WIDTH_BLOCK)
            out = out + (jax.nn.silu(h @ cast(lp["w_gate"][:, cut]))
                         * (h @ cast(lp["w_up"][:, cut]))) \
                @ cast(lp["w_down"][cut])
        return out

    with jax.default_matmul_precision("highest"):
        x = cast(params["embed"][tokens])
        for i in range(cfg["num_hidden_layers"]):
            op, ffn = params["layers"][2 * i], params["layers"][2 * i + 1]
            x = x + retention(norm(x, op["ln_attn"]), op["attn"])
            x = x + dense(norm(x, ffn["ln_mlp"]), ffn["mlp"])
        # The head a block of the vocabulary at a time: cast up whole it is
        # 3.1 GB of float32 beside the engine the check holds.
        x, head = norm(x, params["ln_f"])[first:], params["lm_head"]
        V = head.shape[1]
        width = next(w for w in range(min(V, _VOCAB_BLOCK), 0, -1)
                     if V % w == 0)

        def block(i, logits):                               # written in place
            cut = jax.lax.dynamic_slice_in_dim(head, i * width, width, 1)
            return jax.lax.dynamic_update_slice_in_dim(
                logits, x @ cast(cut), i * width, 1)
        return jax.lax.fori_loop(0, V // width, block,
                                 jnp.zeros((x.shape[0], V), f32))


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, first: int = 0):
    import jax
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, t: _forward(p, t, cfg, first))


def _shape_keys(cfg: Dict[str, Any]) -> str:
    return json.dumps({k: v for k, v in cfg.items()
                       if not isinstance(v, (dict, list))}, sort_keys=True)


def reference_logits(params, tokens, cfg: Dict[str, Any]):
    """The plain float32 reference: tokens (B, S) int32 -> logits [b][s][v],
    one (S, V) array a sequence and not one (B, S, V) array: a 2,600-token
    prompt's are 1.58 GB, and a caller's `[0]` on one array of them (as
    `refcheck.plain` takes it, eagerly: a slice and then a reshape) holds
    three copies on the chip for a moment, 4.75 GB beside an engine that
    leaves 4.7.  `[0]` of a tuple copies nothing."""
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
    rms at a few places of the first and the last stream, for the reader."""
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


def check(engine, prompt, served, config: Dict[str, Any]) -> Dict[str, Any]:
    """The check this family owns (benchmark/README.md, "A family that owns
    its reference check"), and why it owns one though no discrete decision
    lies inside: `refcheck.plain` reads the cold whole-prompt prefill's last
    row, which the ATTENTION form computes; it never reads the state.  What
    this family caches IS the state: the checkpoint a second ask starts
    from, the product of the tokens run again with it, and the decode
    step's update and query (the kernel).  So each served stream is traced
    the way it was served, the first cold and the second from the
    checkpoint its hit found, every served token decoded through the state,
    and EVERY row is held to the reference by the limits the plain check
    holds one row to.  The second stream must have started from a
    checkpoint, or nothing of one was checked.  `check_output_tokens` is
    184 in this family's cell so that the state's own precision shows: a
    state held in bfloat16 drifts from the float32 one by a rounding a step,
    and is past both limits after some hundred steps (TOLERANCE)."""
    got = read(engine, prompt, served,
               reference_rows(engine.params, prompt, served, config))
    tol = TOLERANCE
    got.update(
        tolerance=tol,
        forgiven={"why": "nothing: no discrete decision lies inside"},
        ok=bool(all(got[k] <= tol[k] for k in tol)
                and all(at > 0 for at in got["traced_from"][1:])))
    return got
