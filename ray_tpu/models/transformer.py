"""Llama-style decoder-only transformer, TPU-first functional JAX.

The flagship model family for the framework's Train stack and the driver's
compile gates (BASELINE.md north star: Llama-2-7B fine-tune on v5e-64 at
≥40% MFU).  The reference delegates model code to user frameworks (MaxText in
the JaxTrainer docstring, reference: python/ray/train/v2/jax/jax_trainer.py:40-46);
here the model ships in-tree so the whole stack is self-contained.

Design for the MXU/HBM (see SURVEY.md §7):
  - params are pure pytrees; every tensor carries a *logical axis* tuple so
    GSPMD shards it via LogicalAxisRules (parallel/sharding.py) — dp/fsdp/
    tp/sp all come from annotations, zero hand-written collectives.
  - bfloat16 activations/weights, f32 RMSNorm accumulation and logits.
  - per-layer jax.checkpoint (remat) with dots-saveable policy to trade
    FLOPs for HBM.
  - layers stacked with lax.scan over a (L, ...) leading dim: one compiled
    layer body, fast compile times, clean pipeline-parallel slicing.
  - GQA (num_kv_heads < num_heads), RoPE, SwiGLU — the Llama-2/3 recipe.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..parallel.sharding import LogicalAxisRules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "xla" = reference dot-product attention (works everywhere);
    # "flash" = Pallas TPU kernel (ops/flash_attention.py);
    # "ring" = ring attention over the sp axis (ops/ring_attention.py).
    attention_impl: str = "xla"
    # Sequence-parallel degree for the LLM engine's prefill attention
    # (llm/sequence_parallel.py): >1 shards prefill over an `sp` mesh
    # axis (ring attention / Ulysses).  Must be a power of two; the
    # engine builds a local sp mesh when none is passed.  1 = off.
    sp_degree: int = 1

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def param_count(self) -> int:
        h, v, l = self.hidden_size, self.vocab_size, self.num_layers
        d = self.head_dim_
        qkv = h * (self.num_heads * d) + 2 * h * (self.num_kv_heads * d)
        o = self.num_heads * d * h
        mlp = 3 * h * self.intermediate_size
        return v * h + l * (qkv + o + mlp + 2 * h) + h + v * h


PRESETS: Dict[str, TransformerConfig] = {
    # test-size: runs on the 8-device virtual CPU mesh in seconds
    "tiny": TransformerConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256, num_layers=2,
        num_heads=8, num_kv_heads=4, max_seq_len=256, dtype=jnp.float32),
    "nano": TransformerConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=512, num_layers=4,
        num_heads=8, num_kv_heads=8, max_seq_len=512),
    "1b": TransformerConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_layers=22, num_heads=16, num_kv_heads=16, max_seq_len=2048),
    # Llama-2-7B dims (the BASELINE.md north-star config)
    "7b": TransformerConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_layers=32, num_heads=32, num_kv_heads=32, max_seq_len=4096),
    # Llama-3-8B-style GQA config
    "8b-gqa": TransformerConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
        rope_theta=500000.0),
}


# ---------------------------------------------------------------------------
# Logical axis annotations (consumed by parallel.tree_shardings)
# ---------------------------------------------------------------------------

def param_logical_axes(cfg: TransformerConfig):
    """Pytree (same structure as init params) of logical-axis tuples."""
    layer = {
        "attn": {
            "wq": ("layer", "embed", "heads", "head_dim"),
            "wk": ("layer", "embed", "kv_heads", "head_dim"),
            "wv": ("layer", "embed", "kv_heads", "head_dim"),
            "wo": ("layer", "heads", "head_dim", "embed"),
        },
        "mlp": {
            "w_gate": ("layer", "embed", "mlp"),
            "w_up": ("layer", "embed", "mlp"),
            "w_down": ("layer", "mlp", "embed"),
        },
        "ln_attn": ("layer", "norm"),
        "ln_mlp": ("layer", "norm"),
    }
    return {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "ln_f": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    h, d = cfg.hidden_size, cfg.head_dim_
    nh, nkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    k = iter(jax.random.split(key, 16))
    dt = cfg.dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dt)

    params = {
        "embed": dense(next(k), (cfg.vocab_size, h), h),
        "layers": {
            "attn": {
                "wq": dense(next(k), (L, h, nh, d), h),
                "wk": dense(next(k), (L, h, nkv, d), h),
                "wv": dense(next(k), (L, h, nkv, d), h),
                "wo": dense(next(k), (L, nh, d, h), nh * d),
            },
            "mlp": {
                "w_gate": dense(next(k), (L, h, cfg.intermediate_size), h),
                "w_up": dense(next(k), (L, h, cfg.intermediate_size), h),
                "w_down": dense(next(k), (L, cfg.intermediate_size, h),
                                cfg.intermediate_size),
            },
            "ln_attn": jnp.ones((L, h), jnp.float32),
            "ln_mlp": jnp.ones((L, h), jnp.float32),
        },
        "ln_f": jnp.ones((h,), jnp.float32),
        "lm_head": dense(next(k), (h, cfg.vocab_size), h),
    }
    return params


# ---------------------------------------------------------------------------
# Ops
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rope_angles(positions, cfg: TransformerConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """cos, sin (N, D/2) float32 of the rotary angles at `positions` (N,),
    which may be traced (a suffix after cached tokens, each slot's own
    length in a decode step)."""
    d = cfg.head_dim_
    freqs = 1.0 / (cfg.rope_theta
                   ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); rotate-half formulation.  cos, sin: (S, D/2) where
    the batch shares its positions, (B, S, D/2) where each row has its own."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = (t[None, :, None, :] if t.ndim == 2 else t[:, :, None, :]
            for t in (cos, sin))
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def embed_tokens(params, tokens, cfg: TransformerConfig):
    """tokens (...) int32 -> their rows of the table (..., E)."""
    return params["embed"].astype(cfg.dtype)[tokens]


def lm_logits(params, x, cfg: TransformerConfig):
    """The head: final norm and output projection of rows x (..., E) ->
    logits (..., V), accumulated and returned in float32."""
    x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    return jnp.einsum("...e,ev->...v", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The decoder block: two halves around an attention that is passed in
# ---------------------------------------------------------------------------

def _unconstrained(x, axes):
    return x


def block_qkv(lp, x, cos, sin, cfg: TransformerConfig,
              constrain=_unconstrained):
    """First half of the block: norm, the three projections, RoPE.
    x (B, S, E) -> q (B, S, H, D), k, v (B, S, KV, D)."""
    dt = cfg.dtype
    h = rms_norm(x, lp["ln_attn"], cfg.rms_norm_eps)
    q = jnp.einsum("bse,ehd->bshd", h, lp["attn"]["wq"].astype(dt))
    k = jnp.einsum("bse,ekd->bskd", h, lp["attn"]["wk"].astype(dt))
    v = jnp.einsum("bse,ekd->bskd", h, lp["attn"]["wv"].astype(dt))
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def block_out(lp, x, o, cfg: TransformerConfig, constrain=_unconstrained):
    """Second half: output projection of the attention's o (B, S, H, D),
    residual, norm, SwiGLU, residual -> x (B, S, E)."""
    dt = cfg.dtype
    o = constrain(o, ("batch", "seq", "heads", "head_dim"))
    o = jnp.einsum("bshd,hde->bse", o, lp["attn"]["wo"].astype(dt))
    x = x + constrain(o, ("batch", "seq", "embed"))
    h = rms_norm(x, lp["ln_mlp"], cfg.rms_norm_eps)
    g = jnp.einsum("bse,em->bsm", h, lp["mlp"]["w_gate"].astype(dt))
    u = jnp.einsum("bse,em->bsm", h, lp["mlp"]["w_up"].astype(dt))
    g = constrain(g, ("batch", "seq", "mlp"))
    d = jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                   lp["mlp"]["w_down"].astype(dt))
    return x + constrain(d, ("batch", "seq", "embed"))


def decoder_block(lp, x, cos, sin, attend, cfg: TransformerConfig,
                  constrain=_unconstrained):
    """One layer.  `attend(q, k, v) -> (o, kept)` is the caller's: training's
    causal attention, a prefill's (kernel or scores), a decode step's read of
    the paged pool; `kept` is what the caller's scan collects or carries (a
    prefill's new cache rows, the decode step's written pool, nothing).
    `constrain(x, logical_axes)` places activations on a training mesh.
    Returns (x, kept)."""
    q, k, v = block_qkv(lp, x, cos, sin, cfg, constrain)
    o, kept = attend(q, k, v)
    return block_out(lp, x, o, cfg, constrain), kept


def scan_blocks(layers, x, cos, sin, attend, cfg: TransformerConfig,
                per_layer=()):
    """`lax.scan` of the block over the stacked `layers`, each layer's
    `attend(q, k, v, *at)` given its slice `at` of the arrays in `per_layer`
    (a pool's layer, a layer index).  Returns (x, every layer's `kept`)."""
    def body(x, layer):
        lp, *at = layer
        return decoder_block(lp, x, cos, sin,
                             lambda q, k, v: attend(q, k, v, *at), cfg)
    return jax.lax.scan(body, x, (layers, *per_layer))


def _xla_attention(q, k, v, causal: bool = True):
    """Reference dot-product attention (single implementation lives in
    ops/flash_attention.py; XLA fuses it well on its own)."""
    from ..ops.flash_attention import reference_attention
    return reference_attention(q, k, v, causal=causal)


def _flash_attention(q, k, v, mesh: Optional[Mesh],
                     rules: LogicalAxisRules):
    """The Pallas kernel is a custom call the GSPMD partitioner cannot
    split — left inside a sharded jit it gathers q/k/v onto every chip.
    So on a multi-device mesh run it per shard over the batch and head
    axes (attention is independent across both); every shard sees whole
    sequences."""
    from ..ops.flash_attention import flash_attention
    attend = functools.partial(flash_attention, causal=True)
    if mesh is None or mesh.size == 1:
        return attend(q, k, v)
    q_spec = rules.spec(("batch", None, "heads", None), mesh)
    kv_spec = rules.spec(("batch", None, "kv_heads", None), mesh)
    return jax.shard_map(attend, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def _attention(cfg: TransformerConfig, q, k, v, mesh: Optional[Mesh],
               rules: LogicalAxisRules):
    if cfg.attention_impl == "flash":
        return _flash_attention(q, k, v, mesh, rules)
    if cfg.attention_impl == "ring" and mesh is not None:
        from ..ops.ring_attention import ring_attention
        return ring_attention(q, k, v, mesh=mesh, axis_name="sp", causal=True)
    if cfg.attention_impl not in ("xla", "ring"):
        raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")
    return _xla_attention(q, k, v)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params: Dict[str, Any], tokens: jax.Array,
            cfg: TransformerConfig, mesh: Optional[Mesh] = None,
            rules: Optional[LogicalAxisRules] = None,
            num_microbatches: Optional[int] = None) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S, V) float32.

    `rules` must match the table used to shard the params
    (train_step.make_train_step threads its rules through here). With a
    pp>1 mesh axis the layer stack runs as a collective pipeline
    (parallel/pipeline.py) over `num_microbatches` (default: pp)."""
    rules = rules or LogicalAxisRules.default()

    def constrain(x, axes):
        if mesh is None:
            return x
        return with_logical_constraint(x, axes, mesh, rules)

    vocab_sharded = False
    if mesh is not None:
        spec = rules.spec(("vocab", "embed"), mesh)
        vax = spec[0] if len(spec) > 0 else None
        for ax in ([vax] if isinstance(vax, str) else (vax or [])):
            if dict(mesh.shape).get(ax, 1) > 1:
                vocab_sharded = True
    if vocab_sharded:
        # One-hot matmul instead of gather: with the table sharded over
        # vocab a row-gather forces SPMD into involuntary full
        # rematerialization (replicate-then-reshard); contracting over the
        # vocab axis instead becomes a clean psum over its mesh axis and
        # runs on the MXU (the MaxText iota-embed trick). Single-chip (or
        # unsharded-vocab) keeps the cheaper gather.
        table = params["embed"].astype(cfg.dtype)
        one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.dtype)
        x = jnp.einsum("bsv,ve->bse", one_hot, table)
    else:
        x = embed_tokens(params, tokens, cfg)
    x = constrain(x, ("batch", "seq", "embed"))
    S = tokens.shape[1]
    cos, sin = rope_angles(jnp.arange(0, S, dtype=jnp.float32), cfg)

    def _make_layer_body(constrain):
        # The mesh-bound attention variants are training's alone, and off
        # inside the pp region with the constraints.
        amesh = mesh if constrain is not _unconstrained else None

        def attend(q, k, v):
            return _attention(cfg, q, k, v, amesh, rules), None

        def layer_body(x, lp):
            return decoder_block(lp, x, cos, sin, attend, cfg, constrain)
        return layer_body

    pp = dict(mesh.shape).get("pp", 1) if mesh is not None else 1
    if pp > 1:
        # Collective pipelining over the pp axis: each rank applies its
        # stage's layer slice; activations rotate via ppermute
        # (parallel/pipeline.py). Sharding constraints (and the mesh-bound
        # attention variants) are elided inside the manual region — XLA
        # propagates shardings through the auto axes.
        from ..parallel.pipeline import pipeline_spmd, split_stages

        sbody = _make_layer_body(_unconstrained)
        if cfg.remat:
            sbody = jax.checkpoint(
                sbody,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)

        def apply_stage(stage_layers, xmb):
            out, _ = jax.lax.scan(sbody, xmb, stage_layers)
            return out

        x = pipeline_spmd(
            apply_stage, split_stages(params["layers"], pp), x,
            mesh=mesh, num_microbatches=num_microbatches or pp)
    else:
        body = _make_layer_body(constrain)
        if cfg.remat:
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)

        x, _ = jax.lax.scan(body, x, params["layers"])
    logits = lm_logits(params, x, cfg)
    return constrain(logits, ("batch", "seq", "vocab"))


def loss_fn(params, batch, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None,
            rules: Optional[LogicalAxisRules] = None,
            num_microbatches: Optional[int] = None) -> jax.Array:
    """Next-token cross-entropy; batch = {"tokens": (B,S)} or
    {"inputs","targets"}; ignores padding id 0 when targets provided."""
    if "targets" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
        weights = (targets != 0).astype(jnp.float32)
    else:
        toks = batch["tokens"]
        inputs, targets = toks[:, :-1], toks[:, 1:]
        weights = jnp.ones(targets.shape, jnp.float32)
    logits = forward(params, inputs, cfg, mesh, rules,
                     num_microbatches=num_microbatches)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
