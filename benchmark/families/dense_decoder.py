"""Dense decoder family (Mistral-7B, and any config.json of the same
shape): pre-norm RMSNorm blocks, rotary GQA attention with full causal
masking, SwiGLU MLP, untied output head.

Three things live here, all of them the yardstick's and none the
program's: how a configuration file maps onto the program's model config,
the plain float32 reference the program's outputs are held to, and the
functions that count the operations and bytes a step *requires*.
"""

from __future__ import annotations

from typing import Any, Dict

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}

# Logits of a seeded-weight model have a standard deviation near 1.  The
# engine computes in bf16 with float32 accumulation; against the float32
# reference PRs 22-23 measured max |diff| 0.06-0.09 and rms 0.014-0.016 at
# these widths on the chip.  The limits leave that a factor of about three
# and sit well under what a lower precision gives (fp8 weights or
# activations: rms above 0.1).  `margin`: a served token need not be the
# reference's argmax, because the largest logit changes on rounding, but the
# reference's logit for it may trail the reference's best by no more than
# the two computations can differ.
TOLERANCE = {"logit_max": 0.25, "logit_rms": 0.045, "margin": 0.25}
TOLERANCE["loss"] = 0.01         # mean of >= 1,000 token losses: rms / sqrt(n)


def program_config(cfg: Dict[str, Any], *, attention: str = "xla",
                   max_seq_len: int | None = None):
    """The program's TransformerConfig for a configuration file (HF
    `config.json` keys).  Refuses what the dense decoder cannot express."""
    import jax.numpy as jnp
    from ray_tpu.models import TransformerConfig

    if cfg.get("sliding_window") is not None:
        raise ValueError("dense_decoder has no sliding window")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("dense_decoder has an untied head")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r} is not SwiGLU")
    heads = cfg["num_attention_heads"]
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        dtype=getattr(jnp, _DTYPES[cfg.get("torch_dtype", "bfloat16")]),
        attention_impl=attention)


# ------------------------------------------------------------ sizes -------

def _dims(cfg: Dict[str, Any]):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return h, v, nh, nkv, d, cfg["intermediate_size"], \
        cfg["num_hidden_layers"]


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that are operands of a matrix product for every token:
    the layers' projections and the output head.  The embedding table is a
    gather, and the norm scales are elementwise: neither counts."""
    h, v, nh, nkv, d, m, L = _dims(cfg)
    per_layer = h * nh * d + 2 * h * nkv * d + nh * d * h + 3 * h * m
    return L * per_layer + h * v


def param_count(cfg: Dict[str, Any]) -> int:
    h, v, nh, nkv, d, m, L = _dims(cfg)
    return matmul_params(cfg) + v * h + L * 2 * h + h


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per trained
    token: 6 per matmul parameter (2 forward, 4 backward), plus causal
    attention — QK^T and PV are 2*2*S*H*D forward per token unmasked,
    half of that under the causal mask, three times that with the
    backward pass.  No embedding gather, no recomputation (remat's second
    forward is the program's choice, not the model's requirement)."""
    h, v, nh, nkv, d, m, L = _dims(cfg)
    attn_fwd = 0.5 * 4 * seq * nh * d * L
    return 6.0 * matmul_params(cfg) + 3.0 * attn_fwd


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return 2 * param_count(cfg)         # bf16 as served


def decode_step_bytes(cfg: Dict[str, Any], live_kv_tokens: float) -> float:
    """Bytes ONE decode step must read from HBM: every matmul weight once
    (whatever the batch), one embedding row per sequence (neglected), and
    the keys and values of every token the batch's sequences hold.  What
    the step writes (one token's KV per sequence) is neglected.  Decode is
    bandwidth-bound on a v5e at these batch sizes (2 FLOP per weight byte
    per sequence against a ridge of 240 FLOP/byte), so this over the peak
    bandwidth is the step's least time."""
    h, v, nh, nkv, d, m, L = _dims(cfg)
    kv_per_token = 2 * L * nkv * d * 2
    return 2.0 * matmul_params(cfg) + live_kv_tokens * kv_per_token


# -------------------------------------------------------- reference -------

def reference_logits(params, tokens, cfg: Dict[str, Any]):
    """Plain float32 forward pass: tokens (B, S) int32 -> logits (B, S, V).

    Straightforward jax.numpy with no kernel, cache or batching trick, at
    `highest` matmul precision (on a TPU a float32 product otherwise runs
    in bf16 passes).  `params` is the program's parameter tree (stacked
    layers, bf16): each layer is cast to float32 as it is used, so the
    reference never holds a second full copy of the weights.  Departures
    from the published model: none in the mathematics; weights are
    seeded, not trained."""
    import jax
    import jax.numpy as jnp

    h, v, nh, nkv, d, m, L = _dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    f32 = jnp.float32

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale.astype(f32)

    def rope(x, pos):                   # x (B, S, H, D), rotate-half
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=f32) / d)
        ang = pos.astype(f32)[:, None] * inv[None]
        c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        pos = jnp.arange(S)
        causal = pos[:, None] >= pos[None, :]
        x = params["embed"].astype(f32)[tokens]

        def layer(x, lp):
            lp = jax.tree.map(lambda a: a.astype(f32), lp)
            a = norm(x, lp["ln_attn"])
            q = rope(jnp.einsum("bse,ehd->bshd", a, lp["attn"]["wq"]), pos)
            k = rope(jnp.einsum("bse,ekd->bskd", a, lp["attn"]["wk"]), pos)
            val = jnp.einsum("bse,ekd->bskd", a, lp["attn"]["wv"])
            k = jnp.repeat(k, nh // nkv, axis=2)
            val = jnp.repeat(val, nh // nkv, axis=2)
            sc = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(f32(d))
            p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf),
                               axis=-1)
            o = jnp.einsum("bhst,bthd->bshd", p, val)
            x = x + jnp.einsum("bshd,hde->bse", o, lp["attn"]["wo"])
            a = norm(x, lp["ln_mlp"])
            g = jnp.einsum("bse,em->bsm", a, lp["mlp"]["w_gate"])
            u = jnp.einsum("bse,em->bsm", a, lp["mlp"]["w_up"])
            x = x + jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                               lp["mlp"]["w_down"])
            return x, None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = norm(x, params["ln_f"])
        return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(f32))


def reference_loss(logits, tokens):
    """Next-token cross-entropy of reference (or program) logits over
    tokens (B, S+1): inputs tokens[:, :-1], targets tokens[:, 1:]."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))
