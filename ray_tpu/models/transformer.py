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
from . import mamba2, retention, routed, shortconv
from .mamba2 import Mamba2Dims
from .retention import RetentionDims
from .routed import RoutedDims
from .shortconv import ShortConvDims


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """A latent attention layer's widths (DeepSeek-V2/V3's multi-head latent
    attention without a compressed query, as Moonlight-16B-A3B publishes
    it): a token's keys and values of ALL heads are one compressed row of
    `rank` values, beside `rope` rotated values that every head shares."""
    rank: int = 512             # kv_lora_rank: the compressed row c
    nope: int = 128             # qk_nope_head_dim: a head's unrotated part
    rope: int = 64              # qk_rope_head_dim: the shared rotated part
    value: int = 128            # v_head_dim

    @property
    def row(self) -> int:
        """THE CACHE ROW's values: [c | k_r], key and value at once."""
        return self.rank + self.rope

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.nope + self.rope)

    def param_count(self, hidden: int, heads: int) -> int:
        return (hidden * heads * (self.nope + self.rope)        # W_q
                + hidden * self.row + self.rank                 # W_kva, norm
                + self.rank * heads * (self.nope + self.value)  # W_kvb
                + heads * self.value * hidden)                  # W_o


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
    # The stack as a pattern of block kinds, one letter a block (KINDS):
    # "" = every layer the dense block, which is what the fields above
    # describe and the only pattern training, meshes and the streamed paths
    # know.  Without spaces every letter is a layer ("MEM*E"); a model whose
    # layer is an operator AND a feed-forward, each a residual half behind
    # its own norm, spells a layer as two letters and parts the layers with
    # spaces ("CF *E CE": `num_layers` 3, six blocks, one attention layer
    # in the page pool).  A pattern with `M`, `E`, `C`, `L` or `P` blocks
    # brings their sizes in `mamba`, `routed`, `conv`, `latent` and
    # `retention` (`L`: latent attention, whose page pool holds ONE row a
    # token, `cache_row`; a pattern's attention layers are all `*` or all
    # `L`; `P`: power retention, whose projections, head norm and rotation
    # are the attention block's and whose cache is a recurrent state alone:
    # a pattern of `P` and `F` has no page pool); `rope` off = the
    # attention layers rotate nothing (position is carried by the recurrent
    # layers); `qk_norm` = an RMS norm with a learned scale over each q and
    # k head before the rotation; `tie_embeddings` = the head is the
    # embedding table, held once.  `repeats` (a pattern's alone): the stack
    # is `pattern`, ONE PERIOD, that many times over (`num_layers` counts
    # them all).  More than one and the period's weights, and whatever the
    # engine keeps for its stateful layers, are stacked along a leading
    # repeat axis, and `run_pattern` scans the period over it: one traced
    # period however deep the stack.
    pattern: str = ""
    repeats: int = 1
    mamba: Optional[Mamba2Dims] = None
    routed: Optional[RoutedDims] = None
    conv: Optional[ShortConvDims] = None
    latent: Optional[LatentDims] = None
    retention: Optional[RetentionDims] = None
    rope: bool = True
    qk_norm: bool = False
    tie_embeddings: bool = False
    # Four multipliers a family may publish (Granite 4.0: 12, 0.22, 1 / 64,
    # 8), each None = absent, and then no operation is added anywhere: the
    # token's row of the table times `embedding_multiplier`; every residual
    # half's output times `residual_multiplier` before it is added; the
    # attention scores times `attention_scale` in the place of 1 / sqrt(head
    # width); the logits divided by `logit_divisor`.
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    attention_scale: Optional[float] = None
    logit_divisor: Optional[float] = None

    @property
    def head_dim_(self) -> int:
        if self.latent:
            return self.latent.nope + self.latent.rope
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def cache_row(self) -> Tuple[int, int]:
        """(KV heads, width) of what one token leaves in one attention
        layer's page pool: its keys' (and values') heads, or a latent
        layer's one row (ops/paged_attention.py: `pool_row`)."""
        if self.latent:
            return 1, self.latent.row
        return self.num_kv_heads, self.head_dim_

    @property
    def score_scale(self) -> float:
        """What the attention scores are multiplied by."""
        if self.attention_scale is not None:
            return self.attention_scale
        return 1.0 / math.sqrt(self.head_dim_)

    @property
    def period(self) -> str:
        """One letter a block of ONE period, in order: what `params["layers"]`
        holds a tree for."""
        return self.pattern.replace(" ", "")

    @property
    def kinds(self) -> str:
        """One letter a block of the whole stack, in order."""
        return self.period * self.repeats or "D" * self.num_layers

    @property
    def pattern_layers(self) -> int:
        """The layers the stack spells: `pattern`'s words, or its letters
        where it has no spaces, times `repeats`."""
        return self.repeats * len(
            self.pattern.split() if " " in self.pattern else self.pattern)

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    def param_count(self) -> int:
        h, v = self.hidden_size, self.vocab_size
        d = self.head_dim_
        qkv = h * (self.num_heads * d) + 2 * h * (self.num_kv_heads * d)
        o = self.num_heads * d * h
        mlp = 3 * h * self.intermediate_size
        qk = 2 * d if self.qk_norm else 0
        per = {"D": qkv + o + mlp + 2 * h, "*": qkv + o + h + qk,
               "F": mlp + h}
        if self.mamba:
            per["M"] = self.mamba.param_count(h) + h
        if self.routed:
            per["E"] = (self.routed.shared_params(h) + h
                        + self.routed.held * self.routed.expert_params(h))
        if self.conv:
            per["C"] = self.conv.param_count(h) + h
        if self.latent:
            per["L"] = self.latent.param_count(h, self.num_heads) + h
        if self.retention:
            per["P"] = per["*"] + self.retention.param_count(h)
        head = 0 if self.tie_embeddings else v * h
        return v * h + sum(per[k] for k in self.kinds) + h + head


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
    if cfg.pattern:
        raise ValueError("a pattern of layer kinds has no sharding rules yet")
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

def _dense(key, shape, fan_in, dt):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dt)


def _init_pattern_layer(kind: str, key, cfg: TransformerConfig):
    """One layer of a pattern, unstacked (`run_pattern` walks them)."""
    h, dt = cfg.hidden_size, cfg.dtype
    ln = jnp.ones((h,), jnp.float32)
    if kind == "M":
        return {"ln": ln, **mamba2.init_layer(key, h, cfg.mamba, dt)}
    if kind == "E":
        return {"ln": ln, **routed.init_layer(key, h, cfg.routed, dt)}
    if kind == "C":
        return {"ln": ln, **shortconv.init_layer(key, h, cfg.conv, dt)}
    ks = jax.random.split(key, 4)
    if kind == "L":
        z, nh = cfg.latent, cfg.num_heads
        return {"ln": ln, "attn": {
            "wq": _dense(ks[0], (h, nh, z.nope + z.rope), h, dt),
            "w_kva": _dense(ks[1], (h, z.row), h, dt),
            "kv_norm": jnp.ones((z.rank,), jnp.float32),
            "w_kvb": _dense(ks[2], (z.rank, nh, z.nope + z.value), z.rank,
                            dt),
            "wo": _dense(ks[3], (nh, z.value, h), nh * z.value, dt)}}
    if kind == "F":
        m = cfg.intermediate_size
        return {"ln_mlp": ln, "mlp": {
            "w_gate": _dense(ks[0], (h, m), h, dt),
            "w_up": _dense(ks[1], (h, m), h, dt),
            "w_down": _dense(ks[2], (m, h), m, dt)}}
    if kind not in "*P":
        raise ValueError(f"layer kind {kind!r} is none of {KINDS}")
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    attn = {"wq": _dense(ks[0], (h, nh, d), h, dt),
            "wk": _dense(ks[1], (h, nkv, d), h, dt),
            "wv": _dense(ks[2], (h, nkv, d), h, dt),
            "wo": _dense(ks[3], (nh, d, h), nh * d, dt)}
    if cfg.qk_norm:
        attn.update(q_norm=jnp.ones((d,), jnp.float32),
                    k_norm=jnp.ones((d,), jnp.float32))
    if kind == "P":
        attn.update(retention.init_layer(
            jax.random.fold_in(key, 4), h, cfg.retention, dt))
    return {"ln_attn": ln, "attn": attn}


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    h, d = cfg.hidden_size, cfg.head_dim_
    nh, nkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    k = iter(jax.random.split(key, 16))
    dt = cfg.dtype

    dense = functools.partial(_dense, dt=dt)

    if cfg.pattern:
        if cfg.pattern_layers != L:
            raise ValueError(f"pattern {cfg.pattern!r} has not {L} layers")
        keys = jax.random.split(next(k), len(cfg.kinds))
        if cfg.repeats > 1:
            if cfg.routed:
                raise ValueError("`balance_routers` walks a stack spelled "
                                 "out: no scanned period of routed layers")
            # Block j of every repeat in ONE traced initialiser, its leaves
            # stacked as they come out (repeat r's are block r x period + j
            # of the stack spelled out, from that block's own key): a
            # quarter of the program at four repeats.
            n = len(cfg.period)
            layers = tuple(
                jax.vmap(functools.partial(_init_pattern_layer, kind,
                                           cfg=cfg))(keys[j::n])
                for j, kind in enumerate(cfg.period))
        else:
            layers = tuple(_init_pattern_layer(kind, keys[i], cfg)
                           for i, kind in enumerate(cfg.kinds))
        params = {"embed": dense(next(k), (cfg.vocab_size, h), h),
                  "layers": layers, "ln_f": jnp.ones((h,), jnp.float32)}
        head = next(k)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(head, (h, cfg.vocab_size), h)
        return balance_routers(params, cfg, next(k)) if cfg.routed else params

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
    d = cfg.latent.rope if cfg.latent else cfg.head_dim_
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
    """tokens (...) int32 -> their rows of the table (..., E), times the
    configuration's `embedding_multiplier` where it has one."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    return _times(x, cfg.embedding_multiplier)


def _times(y, by: Optional[float]):
    """y x `by`, the product taken in float32 and rounded once to y's type
    (a multiplier like 0.22 is no bfloat16); y itself where `by` is None."""
    if by is None:
        return y
    return (y.astype(jnp.float32) * by).astype(y.dtype)


def residual(x, y, cfg: TransformerConfig):
    """x + y, the residual half's output y times the configuration's
    `residual_multiplier` where it has one."""
    return x + _times(y, cfg.residual_multiplier)


def lm_logits(params, x, cfg: TransformerConfig):
    """The head: final norm and output projection of rows x (..., E) ->
    logits (..., V), accumulated and returned in float32."""
    x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "...e,ve->...v", x, params["embed"].astype(cfg.dtype),
            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum(
            "...e,ev->...v", x, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32)
    if cfg.logit_divisor is not None:
        logits = logits / cfg.logit_divisor
    return logits


# ---------------------------------------------------------------------------
# The decoder block: two halves around an attention that is passed in
# ---------------------------------------------------------------------------

def _unconstrained(x, axes):
    return x


def block_qkv(lp, x, cos, sin, cfg: TransformerConfig,
              constrain=_unconstrained, beside=None):
    """First half of the block: norm, the three projections, with `qk_norm`
    each q and k head's own norm, RoPE.
    x (B, S, E) -> q (B, S, H, D), k, v (B, S, KV, D), and after them
    `beside(h)` of the normed rows h where a kind reads more of them (a
    retention layer's gate)."""
    dt = cfg.dtype
    h = rms_norm(x, lp["ln_attn"], cfg.rms_norm_eps)
    q = jnp.einsum("bse,ehd->bshd", h, lp["attn"]["wq"].astype(dt))
    k = jnp.einsum("bse,ekd->bskd", h, lp["attn"]["wk"].astype(dt))
    v = jnp.einsum("bse,ekd->bskd", h, lp["attn"]["wv"].astype(dt))
    q = constrain(q, ("batch", "seq", "heads", "head_dim"))
    k = constrain(k, ("batch", "seq", "kv_heads", "head_dim"))
    if cfg.qk_norm:
        q = rms_norm(q, lp["attn"]["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["attn"]["k_norm"], cfg.rms_norm_eps)
    if cfg.rope:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return (q, k, v) if beside is None else (q, k, v, beside(h))


def attn_out(lp, x, o, cfg: TransformerConfig, constrain=_unconstrained):
    """Output projection of the attention's o (B, S, H, D), and residual."""
    o = constrain(o, ("batch", "seq", "heads", "head_dim"))
    o = jnp.einsum("bshd,hde->bse", o, lp["attn"]["wo"].astype(cfg.dtype))
    return residual(x, constrain(o, ("batch", "seq", "embed")), cfg)


def ffn_block(lp, x, cfg: TransformerConfig, constrain=_unconstrained):
    """`F`, and the end of `D`: norm, SwiGLU, residual -> x (B, S, E)."""
    dt = cfg.dtype
    h = rms_norm(x, lp["ln_mlp"], cfg.rms_norm_eps)
    g = jnp.einsum("bse,em->bsm", h, lp["mlp"]["w_gate"].astype(dt))
    u = jnp.einsum("bse,em->bsm", h, lp["mlp"]["w_up"].astype(dt))
    g = constrain(g, ("batch", "seq", "mlp"))
    d = jnp.einsum("bsm,me->bse", jax.nn.silu(g) * u,
                   lp["mlp"]["w_down"].astype(dt))
    return residual(x, constrain(d, ("batch", "seq", "embed")), cfg)


def block_out(lp, x, o, cfg: TransformerConfig, constrain=_unconstrained):
    """Second half: output projection of the attention's o (B, S, H, D),
    residual, then the feed-forward half -> x (B, S, E)."""
    return ffn_block(lp, attn_out(lp, x, o, cfg, constrain), cfg, constrain)


# A prefill's bucket is padded to a power of two, and every half of every
# kind of block but the attention itself is row-wise: what it computes for a
# row past the prompt's real length is thrown away.  Told that length,
# `decoder_block` and `run_pattern` run each half over blocks of ROW_BLOCK
# rows (the prefill kernel's own block) and over those alone that hold a
# real row (`over_rows`, the one loop).  Decided from the shapes, on any
# platform: a bucket of at least MIN_ROW_BLOCKS whole blocks (2,048 rows).
# A bucket is more than half full, so under four blocks none can be left out
# and the loop only costs (a 1,024-row bucket always ran both of its two:
# +2.0 ms of 45.5 on a v5e, PERF.md PR 39).  Below that, and for every
# caller that gives no length, the halves take all rows at once.
ROW_BLOCK = 512
MIN_ROW_BLOCKS = 4


def by_row_blocks(length, rows: int, row_block: int = ROW_BLOCK,
                  every: int = 0) -> bool:
    """Whether the halves of a `rows`-row bucket with `length` real rows go
    by row blocks: a length, and MIN_ROW_BLOCKS whole blocks or more; where
    layers carry state that is kept every `every` rows, a block holds a
    whole number of those steps."""
    return length is not None and rows % row_block == 0 \
        and rows // row_block >= MIN_ROW_BLOCKS \
        and not (every and row_block % every)


def blocks_to_run(length, rows: int, row_block: int = ROW_BLOCK,
                  every: int = 0):
    """`over_rows`'s `blocks` for such a bucket: the blocks that hold a real
    row (traced where `length` is), None = all rows at once."""
    if not by_row_blocks(length, rows, row_block, every):
        return None
    return -(-length // row_block)


def row_blocks(length, rows: int, row_block: int = ROW_BLOCK, every: int = 0):
    """(row blocks the halves of one layer run, row blocks of the bucket) for
    `length` real rows (an int, or traced) of a `rows`-row bucket; the same
    where the halves take all rows at once."""
    dense = max(1, rows // row_block)
    run = blocks_to_run(length, rows, row_block, every)
    return (dense if run is None else run), dense


def over_rows(half, ins, outs, blocks, row_block: int, carry=None):
    """`half(*arrays of ins)` -> a tree of arrays shaped as `outs` (a tree of
    anything with a shape and a dtype), rows on axis 1.  `ins` pairs each
    array with its row axis.  `blocks` None: all rows at once.  Else
    (traced) a loop runs the half on the first `blocks` blocks of
    `row_block` rows, a block a trip, and leaves ZEROS in the rows of the
    others, so that whatever reads them (a softmax over masked scores, a
    decode step's read of a page's tail) meets nothing that is not finite.
    A result may have fewer rows than went in (a state kept every so many):
    each trip's lands behind the trip's before.  With `carry` (a layer's
    recurrent state) the half is `half(carry, *arrays) -> (carry', tree)`,
    each trip starts from what the one before left, and (carry', tree)
    comes back.  A loop traces its body once."""
    if blocks is None:
        rows = (a for a, _ in ins)
        return half(*rows) if carry is None else half(carry, *rows)
    if carry is None:
        stateless = half
        half = lambda _, *rows: (None, stateless(*rows))    # noqa: E731

    def body(i, at_trip):
        bufs, carry = at_trip
        at = i * row_block
        carry, got = half(carry, *(jax.lax.dynamic_slice_in_dim(
            a, at, row_block, ax) for a, ax in ins))
        # (`at` again where a result has as many rows as went in, so that
        # the dense decoder's programs lower as they did.)
        return jax.tree.map(
            lambda b, g: jax.lax.dynamic_update_slice_in_dim(
                b, g, at if g.shape[1] == row_block else i * g.shape[1], 1),
            bufs, got), carry
    bufs, end = jax.lax.fori_loop(0, blocks, body, (jax.tree.map(
        lambda o: jnp.zeros(o.shape, o.dtype), outs), carry))
    return bufs if carry is None else (end, bufs)


def _layer_of(stack, layer, pin=None):
    """Layer `layer` (traced) of the stacked weights `stack` (None: `stack`
    is the layer's own), indexed where it is used: inside a loop over row
    blocks.  XLA would hoist the slice out of that loop as invariant, and a
    slice that is a loop's operand is a COPY (the MLP's and wo's 385 MB a
    layer: +15 ms a prefill, measured) where inside it is an offset fused
    into the product, as in the plain scan.  `pin`, the block's rows, ties
    the index to the loop's own data so that it stays; the first half goes
    without (its projections are copied to another layout a layer either
    way, and pinned XLA would turn the whole stack at the program's start:
    0.8 GB of scratch)."""
    if layer is None:
        return stack
    if pin is not None:
        layer, _ = jax.lax.optimization_barrier((layer, pin))
    return jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
        w, layer, keepdims=False), stack)


def _around_attention(qkv, out, x, cos, sin, attend, cfg: TransformerConfig,
                      blocks, row_block: int, heads=None):
    """The two row-wise halves around an attention, each over the rows as
    `over_rows` has it: `qkv(x, cos, sin)` -> q, k, v as `block_qkv` gives
    them (or what `heads`, the (heads, width) of each result, says: a latent
    layer's q and cache row), the caller's `attend(q, k, v)` -> (o, kept)
    between the loops, `out(x, o)` -> the new x.  Returns (x, kept)."""
    heads = heads or [(n, cfg.head_dim_) for n in (
        cfg.num_heads, cfg.num_kv_heads, cfg.num_kv_heads)]
    heads = tuple(jax.ShapeDtypeStruct((*x.shape[:2], *h), x.dtype)
                  for h in heads)
    first = over_rows(
        qkv, [(x, 1), (cos, cos.ndim - 2), (sin, sin.ndim - 2)], heads,
        blocks, row_block)
    o, kept = attend(*first)
    x, = over_rows(lambda x, o: (out(x, o),), [(x, 1), (o, 1)], (x,), blocks,
                   row_block)
    return x, kept


def decoder_block(lp, x, cos, sin, attend, cfg: TransformerConfig,
                  constrain=_unconstrained, length=None,
                  row_block: int = ROW_BLOCK, layer=None):
    """One layer.  `attend(q, k, v) -> (o, kept)` is the caller's: training's
    causal attention, a prefill's (kernel or scores), a decode step's read of
    the paged pool; `kept` is what the caller's scan collects or carries (a
    prefill's new cache rows, the decode step's written pool, nothing).
    `constrain(x, logical_axes)` places activations on a training mesh.
    `length`: a prefill's real rows, the first of x's padded S; the halves
    then run over the row blocks that hold them (`row_blocks`), and q, k, v
    and the new x are zeros past the last block that ran.  With `layer`,
    `lp` is the whole stack and `layer` this layer's index (`_layer_of`).
    `row_block` is an argument for the tests' small buckets alone.
    Returns (x, kept)."""
    weights = functools.partial(_layer_of, lp, layer)
    return _around_attention(
        lambda x, cos, sin: block_qkv(weights(), x, cos, sin, cfg, constrain),
        lambda x, o: block_out(weights(x), x, o, cfg, constrain),
        x, cos, sin, attend, cfg,
        blocks_to_run(length, x.shape[1], row_block), row_block)


# The kinds of block a pattern is made of.  Each is defined once, here, and
# every path calls it: `D` the dense block above (attention and SwiGLU),
# `*` attention alone, `L` latent attention alone (`latent_block`, below),
# `F` the SwiGLU feed-forward alone (`ffn_block`, above), `M` a Mamba-2
# mixer, `C` a gated short convolution, `E` a routed-expert layer, `P` power
# retention (`retention_block`, below); each but `D` is x +
# mixer(rms_norm(x)).  The STATEFUL kinds carry recurrent state from row to
# row: a tree a layer (`zero_state`), which the engine keeps a row a slot
# and checkpoints in its prefix cache without knowing what is in it.  The
# kinds that ATTEND leave keys and values (or a latent row) in the engine's
# page pool; a pattern with none of them has no pool.
KINDS = "D*FMCELP"
STATEFUL = "MCP"
ATTEND = "D*L"


def zero_state(cfg: TransformerConfig, kind: str, batch: int):
    """The state of `batch` sequences that have read nothing, in one layer
    of a STATEFUL `kind`."""
    if kind == "M":
        return mamba2.zero_state(cfg.mamba, batch, cfg.dtype)
    if kind == "P":
        return retention.zero_state(cfg.retention, batch)
    return shortconv.zero_state(cfg.conv, cfg.hidden_size, batch, cfg.dtype)


def state_axis(cfg: TransformerConfig) -> int:
    """The axis of a state tree's leaves the sequences lie on: 0, or 1
    behind the repeats where the period is scanned (`run_pattern`)."""
    return int(cfg.repeats > 1)


def zero_states(cfg: TransformerConfig, batch: int):
    """`run_pattern`'s `rec` for `batch` sequences that have read nothing:
    one tree for each STATEFUL block of the period, in order; each leaf
    (batch, ...), or (repeats, batch, ...) where the period is scanned."""
    def period():
        return [zero_state(cfg, k, batch) for k in cfg.period
                if k in STATEFUL]
    if not state_axis(cfg):
        return period()
    return jax.tree.map(
        lambda s: jnp.zeros((cfg.repeats, *s.shape), s.dtype),
        jax.eval_shape(period))


def state_bytes(cfg: TransformerConfig) -> int:
    """One sequence's recurrent state over all the stateful layers."""
    act = jnp.dtype(cfg.dtype).itemsize
    per = {"M": lambda: cfg.mamba.state_bytes(act),
           "C": lambda: cfg.conv.state_bytes(cfg.hidden_size, act),
           "P": lambda: cfg.retention.state_bytes()}
    return sum(per[k]() for k in cfg.kinds if k in STATEFUL)


def state_chunk(cfg: TransformerConfig) -> int:
    """The rows the stateful layers count their state's boundaries in (a
    mixer's `every` is a multiple of its kind's): 0 where no layer carries
    state."""
    dims = {"M": cfg.mamba, "C": cfg.conv, "P": cfg.retention}
    return max([0] + [dims[k].chunk for k in set(cfg.kinds) & set(STATEFUL)])


def attention_block(lp, x, cos, sin, attend, cfg: TransformerConfig,
                    blocks=None, row_block: int = ROW_BLOCK):
    """`*`: the dense block's attention half and nothing after it; `blocks`
    and `row_block` here and below as `over_rows` takes them."""
    return _around_attention(
        lambda x, cos, sin: block_qkv(lp, x, cos, sin, cfg),
        lambda x, o: attn_out(lp, x, o, cfg), x, cos, sin, attend, cfg,
        blocks, row_block)


# Latent attention (`L`; Moonlight-16B-A3B, `model_type` deepseek_v3, no
# compressed query).  With h = rms_norm(x):
#
#     q = h W_q                 -> H x [q_n (nope) | q_r (rope)],  q_r rotated
#     [c | k_r] = h W_kva       -> ONE row a token, shared by all heads
#     c = rms_norm(c);  k_r rotated          THE CACHE ROW: [c | k_r]
#     expanded:  [k_n,h | v_h] = c W_kvb,h
#                s_h(t,u) = (q_n,h(t).k_n,h(u) + q_r,h(t).k_r(u)) * scale
#                o_h = sum_u softmax_u(s_h)(t,u) v_h(u)
#     absorbed:  q~_h = q_n,h W_kvb,h[k]^T;  s_h = (q~_h.c(u) + q_r,h.k_r(u))
#                * scale;  o~_h = sum_u p_h c(u);  o_h = o~_h W_kvb,h[v]
#     x + concat_h(o_h) W_o
#
# The two forms give the same numbers from the same weights.  The expanded
# one pays W_kvb once for every key row and then 320 values a head and pair;
# the absorbed one pays nothing a key row and 1,088 a head and pair: ONE
# first half (`latent_qrow`), two builders of the attention over given key
# rows (`LATENT_FORMS`), and one rule that picks from the shapes
# (`latent_form`).  Departures from the published modelling code: a rotated
# pair is split in halves, not interleaved (with seeded weights a
# permutation of W_q's and W_kva's columns); no rope scaling.

def latent_form(cached: int) -> str:
    """Which form a call's attention takes, from how many of its key rows
    lie in the pool: over `cached` rows (a decode step, a suffix after a
    cached prefix) it attends them as they lie, "absorbed"; a whole prompt
    (nothing cached) up-projects every key row once, "expanded".  A key row
    costs the expanded form W_kvb once (rank x H x (nope + value) products)
    and the absorbed form 768 products more a head and query row, equal at
    171 query rows at the published widths: a threshold for long suffixes
    over cached rows waits for traffic that has them and a chip run that
    sets it.  The expanded rows of a whole prompt go through the blocked
    prefill kernel where the engine's chooser takes it
    (`llm/programs.py:_prefill_path`: a TPU, 1,024 padded rows or more) and
    no scores are built; `LATENT_FORMS`' built scores are what a suffix, a
    bucket under that and the CPU still run."""
    return "absorbed" if cached else "expanded"


def latent_qrow(lp, x, cos, sin, cfg: TransformerConfig):
    """The first half of `L`: x (B, S, E) -> the per-head queries (B, S, H,
    nope + rope) and the token's cache row (B, S, 1, rank + rope)."""
    z, dt, w = cfg.latent, cfg.dtype, lp["attn"]
    h = rms_norm(x, lp["ln"], cfg.rms_norm_eps)
    q = jnp.einsum("bse,ehd->bshd", h, w["wq"].astype(dt))
    row = jnp.einsum("bse,ec->bsc", h, w["w_kva"].astype(dt))[:, :, None]
    q = jnp.concatenate(
        [q[..., :z.nope], apply_rope(q[..., z.nope:], cos, sin)], axis=-1)
    row = jnp.concatenate(
        [rms_norm(row[..., :z.rank], w["kv_norm"], cfg.rms_norm_eps),
         apply_rope(row[..., z.rank:], cos, sin)], axis=-1)
    return q, row


def _latent_softmax(q, k, v, mask, scale: float):
    """q (B, S, H, C) over keys k and values v, (B, T, H, .) a head's own or
    (B, T, .) shared by all heads; key t open to query s where mask (S, T).
    THE BUILT SCORES of both forms: reached by a suffix over cached rows
    (absorbed, a bucket of 64-1,024 rows against the slot's whole table
    row), by a whole prompt under the prefill kernel's least bucket, and by
    every prefill off a TPU; a whole prompt of 1,024 padded rows or more on
    a TPU never comes here (`latent_form`)."""
    keys = "bthc" if k.ndim == 4 else "btc"
    s = jnp.einsum(f"bshc,{keys}->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None, None], s, -1e30)
    # The row maximum behind a barrier: fused with the subtraction that
    # broadcasts it back, the chip's compiler turns the two into ONE
    # reduce-window as wide as the keys, 23.5 ms a block of 512 query rows
    # against 8,192 keys where the scores' product takes 0.4 (PERF.md §6,
    # PR 44).
    top = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - top)
    p = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(q.dtype)
    return jnp.einsum(f"bhst,{keys}->bshc", p, v)


def latent_absorb(w, q, cfg: TransformerConfig):
    """Queries as wide as the cache row: [q_n W_kvb[k]^T | q_r] (..., H,
    rank + rope)."""
    z = cfg.latent
    wide = jnp.einsum("...hd,chd->...hc", q[..., :z.nope],
                      w["w_kvb"][..., :z.nope].astype(q.dtype))
    return jnp.concatenate([wide, q[..., z.nope:]], axis=-1)


def latent_unabsorb(w, o, cfg: TransformerConfig):
    """Sums of compressed rows (..., H, rank) -> a head's values."""
    return jnp.einsum("...hc,chd->...hd", o,
                      w["w_kvb"][..., cfg.latent.nope:].astype(o.dtype))


def latent_expand(w, rows, cfg: TransformerConfig):
    """Key rows (B, T, rank + rope) up-projected ONCE -> every head's own
    keys (B, T, H, nope + rope), the rotated part shared by all heads, and
    values (B, T, H, value)."""
    z = cfg.latent
    kv = jnp.einsum("btc,chd->bthd", rows[..., :z.rank],
                    w["w_kvb"].astype(rows.dtype))
    shared = jnp.broadcast_to(rows[:, :, None, z.rank:],
                              (*kv.shape[:3], z.rope))
    return (jnp.concatenate([kv[..., :z.nope], shared], axis=-1),
            kv[..., z.nope:])


def expanded_attend(w, rows, cfg: TransformerConfig):
    """Key rows (B, T, rank + rope) up-projected (`latent_expand`) ->
    attend(q (B, S, H, nope + rope), mask (S, T), upto=None) -> (B, S, H,
    value); `upto` (static): the first so many keys alone, for a caller
    that knows its queries see no later one."""
    k, v = latent_expand(w, rows, cfg)
    return lambda q, mask, upto=None: _latent_softmax(
        q, k[:, :upto], v[:, :upto], mask[:, :upto], cfg.latent.scale)


def absorbed_attend(w, rows, cfg: TransformerConfig):
    """The same attention over the key rows as they lie: the queries take
    W_kvb's key half, the sums of compressed rows its value half."""
    z = cfg.latent
    return lambda q, mask, upto=None: latent_unabsorb(w, _latent_softmax(
        latent_absorb(w, q, cfg), rows[:, :upto], rows[:, :upto, :z.rank],
        mask[:, :upto], z.scale), cfg)


LATENT_FORMS = {"expanded": expanded_attend, "absorbed": absorbed_attend}


def latent_block(lp, x, cos, sin, attend, cfg: TransformerConfig,
                 blocks=None, row_block: int = ROW_BLOCK):
    """`L`: `attend(q, row, w)` -> (o (B, S, H, value), kept), given the
    layer's attention weights `w` for whichever form it takes."""
    z = cfg.latent
    return _around_attention(
        lambda x, cos, sin: latent_qrow(lp, x, cos, sin, cfg),
        lambda x, o: attn_out(lp, x, o, cfg), x, cos, sin,
        lambda q, row: attend(q, row, lp["attn"]), cfg, blocks, row_block,
        [(cfg.num_heads, z.nope + z.rope), (1, z.row)])


def _stateful_block(mixer, dims):
    def block(lp, x, state, cfg: TransformerConfig, blocks=None,
              row_block: int = ROW_BLOCK, length=None, live=None,
              every: int = 0, **beside):
        """x (B, S, E) from the layer's recurrent `state` -> (x, state',
        checkpoints); `length`, `live`, `every` and what a kind takes
        `beside` them (`_beside`) are the mixer's.  By row
        blocks the loop carries the state: a trip is the mixer on its
        block's rows from the state the trip before left, told how many of
        its rows are real, and the checkpoints it passes are its own share
        of the prefill's."""
        def rows(state, x, length):
            h = rms_norm(x, lp["ln"], cfg.rms_norm_eps)
            y, state, kept = mixer(lp, h, state, dims(cfg), length=length,
                                   live=live, every=every, **beside)
            return state, (residual(x, y, cfg), kept)
        if blocks is None:
            state, (x, kept) = rows(state, x, length)
            return x, state, kept

        def trip(carry, x):
            state, left = carry
            state, out = rows(state, x, jnp.minimum(left, row_block))
            return (state, left - row_block), out
        kept = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (s.shape[0], x.shape[1] // every, *s.shape[1:]), s.dtype),
            state) if every else None
        (state, _), (x, kept) = over_rows(
            trip, [(x, 1)], (x, kept), blocks, row_block,
            (state, jnp.asarray(length, jnp.int32)))
        return x, state, kept
    return block


def retention_block(lp, x, state, cfg: TransformerConfig, blocks=None,
                    row_block: int = ROW_BLOCK, length=None, live=None,
                    every: int = 0, cos=None, sin=None, keep: int = 0):
    """`P` (`models/retention.py`): the attention block's first half
    (`block_qkv`: the projections, each q and k head's norm, the rotation)
    with the layer's log gate beside it, power retention in the attention's
    place, the attention block's output projection.  The two row-wise
    halves go as every other (`over_rows`); the mixer between them takes the
    whole call, as an attention does: its outputs come from the rows' own
    keys and the state it was given, and its state is built once, at the
    boundaries that are kept (`keep`: the last so many) and at the end.
    -> (x, state', checkpoints)."""
    z = cfg.retention

    def first(x, cos, sin):
        return block_qkv(lp, x, cos, sin, cfg,
                         beside=lambda h: retention.gate(lp["attn"], h))
    rows = x.shape[:2]
    shapes = tuple(jax.ShapeDtypeStruct((*rows, n, cfg.head_dim_), x.dtype)
                   for n in (cfg.num_heads, cfg.num_kv_heads,
                             cfg.num_kv_heads)) \
        + (jax.ShapeDtypeStruct((*rows, z.num_kv_heads), jnp.float32),)
    q, k, v, a = over_rows(
        first, [(x, 1), (cos, cos.ndim - 2), (sin, sin.ndim - 2)], shapes,
        blocks, row_block)
    o, state, kept = retention.mixer(q, k, v, a, state, z, length=length,
                                     live=live, every=every, keep=keep)
    x, = over_rows(lambda x, o: (attn_out(lp, x, o, cfg),),
                   [(x, 1), (o, 1)], (x,), blocks, row_block)
    return x, state, kept


# `M` (`models/mamba2.py`) and `C` (`models/shortconv.py`, the state a
# convolution's tail): one block for both.
mamba_block = _stateful_block(mamba2.mixer, lambda cfg: cfg.mamba)
conv_block = _stateful_block(shortconv.mixer, lambda cfg: cfg.conv)
_STATEFUL_BLOCK = {"M": mamba_block, "C": conv_block, "P": retention_block}


def _beside(kind: str, cos, sin, keep: int = 0, order=None, repeat=None):
    """What a kind's block takes besides what every stateful block does:
    `retention_block` rotates, and keeps only so many of the boundaries it
    passes (the others' state is small enough to keep at every one); a
    Mamba-2 step that moves the live slots alone (`carried_whole`) is told
    their `order` and which `repeat` of the stacked state is its own."""
    if kind == "P":
        return {"cos": cos, "sin": sin, "keep": keep}
    return {"order": order, "repeat": repeat} \
        if kind == "M" and order is not None else {}


def carried_whole(cfg: TransformerConfig, rows: int, length, live,
                  every: int):
    """For each STATEFUL block of the period, the leaves of its state that a
    call of these shapes updates where they lie, whatever is stacked in
    front of them: a Mamba-2 decode step's "ssm" where `mamba2.step_path`
    says "pallas", nothing anywhere else."""
    return [("ssm",) if kind == "M" and mamba2.step_path(
        cfg.mamba, rows, length, live, every) == "pallas" else ()
        for kind in cfg.period if kind in STATEFUL]


def routed_block(lp, x, cfg: TransformerConfig, real=None, blocks=None,
                 row_block: int = ROW_BLOCK):
    """`E`: -> (x, counts (2,) int32: held experts touched and rows
    computed, the experts each row chose (B, S, K)); rows that are not
    `real` (B, S) go to no routed expert.  The layer's row-wise parts
    (`models/routed.py`: `choose` before the experts, `combine` after them)
    go as every other half; the grouped products between them are ONE call
    over the bucket, which runs no row past the real ones as it is: a call a
    block would read every touched expert's weights once a block."""
    r = cfg.routed

    def before(x):
        h = rms_norm(x, lp["ln"], cfg.rms_norm_eps)
        return (h, *routed.choose(lp, h, r))
    picks = [jax.ShapeDtypeStruct((*x.shape[:2], r.top_k), t)
             for t in (jnp.int32, jnp.float32)]
    acted = jax.ShapeDtypeStruct(
        (*x.shape[:2], r.latent or x.shape[2]), x.dtype)
    h, chosen, w, u = over_rows(before, [(x, 1)], (x, *picks, acted), blocks,
                                row_block)
    y, counts = routed.held_experts(lp, u, chosen, w, r, real)
    x, = over_rows(lambda x, h, y: (residual(
        x, routed.combine(lp, h, y, r), cfg),),
                   [(x, 1), (h, 1), (y, 1)], (x,), blocks, row_block)
    return x, counts, chosen


def run_pattern(layers, x, cos, sin, attend, cfg: TransformerConfig, rec,
                per_layer=(), length=None, live=None, every: int = 0,
                row_block: int = ROW_BLOCK, keep: int = 0, pools=None):
    """A pattern of kinds, block by block (`layers`: one tree a block of the
    period).  `attend(q, k, v, *at)` as `scan_blocks` takes it, `at` the
    i-th slice of `per_layer` for the i-th attention layer (an `L` layer:
    `attend(q, row, w, *at)`, `latent_block`); `rec` the recurrent state,
    one tree (`zero_state`) for each STATEFUL block of the period in order.
    Which rows are real: the first `length` (a prefill's padded bucket), the
    slots that are `live` (B,) (a decode step); the others move no state
    and meet no routed expert.  `every`: the stateful mixers' checkpoints; `keep`: how
    many of those a prompt passes the caller keeps, the last so many (0:
    all), which a kind whose state is large builds no others for.  Given
    `length` in a bucket that `by_row_blocks` (blocks of `row_block` rows,
    an argument for the tests' small buckets alone; each a whole number of
    checkpoints), every kind's row-wise halves run over the blocks that
    hold a real row, a stateful kind's state carried from block to block,
    and x, the kept keys and values, the checkpoints and the chosen experts
    are ZEROS past the last block that ran.  Returns (x, the attention
    layers' `kept` stacked, rec', the stateful blocks' checkpoints, the `E`
    blocks' counts (n, 2) and chosen experts (n, B, S, K)).

    `pools` (a decode step's): something CARRIED from one attention layer
    to the next, the page pools it writes in place: `attend(pools, q, k, v,
    *at)` -> (o, the pools after the layer), and what comes back in
    `kept`'s place is the pools after the last.

    `cfg.repeats` > 1: the period is traced ONCE and `lax.scan`ned over the
    repeats.  Every leaf of `layers`, of `rec` and of the checkpoints that
    come back then has the repeats on a leading axis, in front of its batch;
    `rec` rides the scan's carry and is read and written where it lies, a
    repeat's slice a trip (as `pools` is), but for the leaves a block
    updates in place itself (`carried_whole`: a slice handed to a kernel
    would be a copy of it), which go to the block whole with the repeat's
    number; `per_layer`, `kept`, the counts
    and the chosen experts are flat over ALL the stack's layers of their
    kind, repeat-major: layer j of repeat r is r x (the period's) + j."""
    whole = carried_whole(cfg, x.shape[1], length, live, every)
    period = functools.partial(
        _run_period, cos=cos, sin=sin, attend=attend, cfg=cfg, length=length,
        live=live, every=every, row_block=row_block, keep=keep,
        order=mamba2.live_order(live) if any(whole) else None)
    R = cfg.repeats
    if R == 1:
        return period(layers, x, rec, per_layer, pools)

    def fold(a):                        # (all layers, ...) -> (R, period's,)
        return a.reshape(R, a.shape[0] // R, *a.shape[1:])

    def trip(carry, at_repeat):
        x, pools, rec = carry
        lp, at, r = at_repeat
        here = [{k: s[k] if k in w else jax.lax.dynamic_index_in_dim(
            s[k], r, keepdims=False) for k in sorted(s)}
            for s, w in zip(rec, whole)]
        x, kept, new, *rest = period(lp, x, here, at, pools, repeat=r)
        rec = [{k: n[k] if k in w else jax.lax.dynamic_update_index_in_dim(
            s[k], n[k], r, 0) for k in sorted(s)}
            for s, n, w in zip(rec, new, whole)]
        if pools is not None:
            pools, kept = kept, None
        return (x, pools, rec), (kept, *rest)
    (x, pools, rec), (kept, ckpts, counts, chosen) = jax.lax.scan(
        trip, (x, pools, rec),
        (layers, tuple(fold(a) for a in per_layer), jnp.arange(R)))
    kept, counts, chosen = jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), (kept, counts, chosen))
    return x, (kept if pools is None else pools), rec, ckpts, counts, chosen


def _run_period(layers, x, rec, per_layer, pools, *, cos, sin, attend,
                cfg: TransformerConfig, length, live, every: int,
                row_block: int, keep: int, order=None, repeat=None):
    """`run_pattern` for the blocks of one period, walked in Python;
    `order`, `repeat`: `_beside`'s."""
    kept, new, ckpts, counts, chosen = [], [], [], [], []
    real = None
    if length is not None:
        real = jnp.broadcast_to(jnp.arange(x.shape[1]) < length, x.shape[:2])
    if live is not None:
        real = jnp.broadcast_to(live[:, None], x.shape[:2])
    by = (blocks_to_run(length, x.shape[1], row_block, every), row_block)
    n_attend = 0
    for kind, lp in zip(cfg.period, layers):
        if kind in "*L":
            at = tuple(a[n_attend] for a in per_layer)
            n_attend += 1
            carried = () if pools is None else (pools,)
            x, k = (attention_block if kind == "*" else latent_block)(
                lp, x, cos, sin,
                lambda *a, at=at, carried=carried: attend(*carried, *a, *at),
                cfg, *by)
            if pools is None:
                kept.append(k)
            else:                       # this layer's are the next one's
                pools = k
        elif kind in STATEFUL:
            x, state, ck = _STATEFUL_BLOCK[kind](
                lp, x, rec[len(new)], cfg, *by, length=length, live=live,
                every=every, **_beside(kind, cos, sin, keep, order, repeat))
            new.append(state)
            ckpts.append(ck)
        elif kind == "F":
            x, = over_rows(lambda x: (ffn_block(lp, x, cfg),), [(x, 1)],
                           (x,), *by)
        else:
            x, c, ch = routed_block(lp, x, cfg, real, *by)
            counts.append(c)
            chosen.append(ch)
    if pools is not None:
        kept = pools
    else:
        kept = jax.tree.map(lambda *a: jnp.stack(a), *kept) \
            if kept and kept[0] is not None else None
    if not counts:
        return x, kept, new, ckpts, None, None
    return x, kept, new, ckpts, jnp.stack(counts), jnp.stack(chosen)


def balance_routers(params, cfg: TransformerConfig, key, batch: int = 2,
                    seq: int = 2048):
    """Seeded weights have no training behind them, and a router that nobody
    balanced loads its experts unevenly (some several times the mean, on
    these stacks' own activations): a chip's share of the experts would see
    a load that the SEED decides.  The published training keeps the experts
    equally loaded through `e_score_correction_bias`; this sets that bias to
    the same end, layer by layer, on the stack's activations for seeded
    tokens: an expert's bias is minus the score it exceeds for a `top_k /
    experts` share of the tokens, so that every expert clears a common bar
    equally often.  Returns the parameters with each routed layer's
    `router_bias` set so."""
    r = cfg.routed
    tokens = jax.random.randint(key, (batch, min(seq, cfg.max_seq_len)), 0,
                                cfg.vocab_size)
    x = embed_tokens(params, tokens, cfg)
    cos, sin = rope_angles(jnp.arange(tokens.shape[1]), cfg)
    layers = []
    for kind, lp in zip(cfg.kinds, params["layers"]):
        if kind == "E":
            h = rms_norm(x, lp["ln"], cfg.rms_norm_eps)
            s = routed.scores(lp, h)
            bar = jnp.quantile(s.reshape(-1, r.experts),
                               1.0 - r.top_k / r.experts, axis=0)
            lp = dict(lp, router_bias=jnp.mean(bar) - bar)
            x = routed_block(lp, x, cfg)[0]
        elif kind in STATEFUL:
            x = _STATEFUL_BLOCK[kind](
                lp, x, zero_state(cfg, kind, batch), cfg,
                **_beside(kind, cos, sin))[0]
        elif kind == "F":
            x = ffn_block(lp, x, cfg)
        elif kind == "L":
            causal = jnp.tril(jnp.ones((tokens.shape[1],) * 2, bool))
            x = latent_block(
                lp, x, cos, sin, lambda q, row, w: (expanded_attend(
                    w, row[:, :, 0], cfg)(q, causal), None), cfg)[0]
        else:
            x = attention_block(
                lp, x, cos, sin, lambda q, k, v: (_xla_attention(
                    q, k, v, scale=cfg.attention_scale), None), cfg)[0]
        layers.append(lp)
    return dict(params, layers=tuple(layers))


def scan_blocks(layers, x, cos, sin, attend, cfg: TransformerConfig,
                per_layer=(), length=None, row_block: int = ROW_BLOCK):
    """`lax.scan` of the block over the stacked `layers`, each layer's
    `attend(q, k, v, *at)` given its slice `at` of the arrays in `per_layer`
    (a pool's layer, a layer index); `length` and `row_block` as
    `decoder_block` takes them: where the halves go by row blocks the scan
    is over the layers' indices and the block indexes the stack itself.
    Returns (x, every layer's `kept`)."""
    by_rows = by_row_blocks(length, x.shape[1], row_block)

    def body(x, layer):
        lp, *at = layer                     # by rows: the layer's index
        return decoder_block(layers if by_rows else lp, x, cos, sin,
                             lambda q, k, v: attend(q, k, v, *at), cfg,
                             length=length, row_block=row_block,
                             layer=lp if by_rows else None)
    n = jax.tree.leaves(layers)[0].shape[0]
    return jax.lax.scan(
        body, x, (jnp.arange(n) if by_rows else layers, *per_layer))


def _xla_attention(q, k, v, causal: bool = True,
                   scale: Optional[float] = None):
    """Reference dot-product attention (single implementation lives in
    ops/flash_attention.py; XLA fuses it well on its own)."""
    from ..ops.flash_attention import reference_attention
    return reference_attention(q, k, v, causal=causal, scale=scale)


def _flash_attention(q, k, v, mesh: Optional[Mesh],
                     rules: LogicalAxisRules, scale: Optional[float] = None):
    """The Pallas kernel is a custom call the GSPMD partitioner cannot
    split — left inside a sharded jit it gathers q/k/v onto every chip.
    So on a multi-device mesh run it per shard over the batch and head
    axes (attention is independent across both); every shard sees whole
    sequences."""
    from ..ops.flash_attention import flash_attention
    attend = functools.partial(flash_attention, causal=True, scale=scale)
    if mesh is None or mesh.size == 1:
        return attend(q, k, v)
    q_spec = rules.spec(("batch", None, "heads", None), mesh)
    kv_spec = rules.spec(("batch", None, "kv_heads", None), mesh)
    return jax.shard_map(attend, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def _attention(cfg: TransformerConfig, q, k, v, mesh: Optional[Mesh],
               rules: LogicalAxisRules):
    scale = cfg.attention_scale         # None: each form's 1 / sqrt(D)
    if cfg.attention_impl == "flash":
        return _flash_attention(q, k, v, mesh, rules, scale)
    if cfg.attention_impl == "ring" and mesh is not None:
        from ..ops.ring_attention import ring_attention
        return ring_attention(q, k, v, mesh=mesh, axis_name="sp", causal=True,
                              scale=scale)
    if cfg.attention_impl not in ("xla", "ring"):
        raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")
    return _xla_attention(q, k, v, scale=scale)


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
    if cfg.pattern:
        raise ValueError(
            "forward() trains the dense decoder; a pattern of layer kinds is "
            "run by the serving engine (llm/engine.py, `run_pattern`)")
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
