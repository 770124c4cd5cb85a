"""Flash attention: Pallas TPU kernel with online softmax, plus the XLA
reference it is checked against.

The hot attention op for the model zoo (models/transformer.py selects it via
TransformerConfig.attention_impl="flash").  Tiled over (batch*head, q-block,
kv-block) with the kv dimension innermost so the running max/denominator/
accumulator live in VMEM scratch across kv steps — the standard flash recipe,
written for the MXU/VMEM model of /opt/skills/guides/pallas_guide.md.

On a TPU `flash_attention` runs the kernel or raises naming the shape
condition that failed; it never swaps in another implementation there.  Off
the TPU (the CPU test mesh) the same call computes `reference_attention`,
which is also the parity oracle for the kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


def reference_attention(q, k, v, causal: bool = True,
                        scale: Optional[float] = None):
    """(B,S,Hq,D),(B,S,Hkv,D) GQA dot-product attention; f32 softmax."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, Hkv, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return o.reshape(B, S, Hq, D)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, block_q: int, block_k: int, causal: bool):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Blocks fully above the causal diagonal contribute nothing.
    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (block_q, D)
        k = k_ref[0].astype(jnp.float32)            # (block_k, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, -1e30)
        m_prev = m_scr[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_q: int, block_k: int,
                   causal: bool):
    """Forward that also emits logsumexp for the backward pass."""
    from jax.experimental import pallas as pl

    _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
               scale=scale, block_q=block_q, block_k=block_k,
               causal=causal)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == nk - 1)
    def _emit_lse():
        lse_ref[0] = m_scr[...] + jnp.log(
            jnp.maximum(l_scr[...], 1e-30))


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_scr, *, scale: float, block_q: int, block_k: int,
                  causal: bool):
    """dq = (p * (do·vᵀ − delta)) · k · scale, accumulated over kv blocks."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                       # (bq, 1)
        delta = delta_ref[0]                   # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, -1e30)
        p = jnp.exp(s - lse)                   # normalized probs
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                   block_q: int, block_k: int, causal: bool):
    """dk/dv for ONE query head, accumulated over q blocks (GQA heads are
    reduced outside the kernel)."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, -1e30)
        p = jnp.exp(s - lse)                                  # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, D)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bq, bk)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bk, D)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _heads_layout(q, k, v):
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    return qh, kh, vh


def _flash_forward_pallas(q, k, v, causal, scale, bq, bk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qh, kh, vh = _heads_layout(q, k, v)
    nq, nk = S // bq, S // bk
    kernel = functools.partial(_fa_fwd_kernel, scale=scale, block_q=bq,
                               block_k=bk, causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qi, ki: (h, qi, 0)),
            # kv head = (batch of h) * Hkv + (head of h) // group
            pl.BlockSpec((1, bk, D),
                         lambda h, qi, ki:
                         ((h // Hq) * Hkv + (h % Hq) // group, ki, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda h, qi, ki:
                         ((h // Hq) * Hkv + (h % Hq) // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, qi, ki: (h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qh.shape, q.dtype),
            jax.ShapeDtypeStruct((B * Hq, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max
            pltpu.VMEM((bq, 1), jnp.float32),      # running denom
            pltpu.VMEM((bq, D), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_fwd",
    )(qh, kh, vh)
    o = out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
    return o, (out, lse)        # heads-layout residuals


def _flash_backward_pallas(q, k, v, oh, lse, do, causal, scale, bq, bk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qh, kh, vh = _heads_layout(q, k, v)
    doh = do.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    # delta_i = sum_d do_i * o_i  (rowwise; standard flash backward).
    delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (B*Hq, S, 1)
    nq, nk = S // bq, S // bk
    qspec = pl.BlockSpec((1, bq, D), lambda h, qi, ki: (h, qi, 0))
    kv_map = lambda h, qi, ki: ((h // Hq) * Hkv + (h % Hq) // group, ki, 0)
    vec_q = pl.BlockSpec((1, bq, 1), lambda h, qi, ki: (h, qi, 0))

    dq_kernel = functools.partial(_fa_dq_kernel, scale=scale, block_q=bq,
                                  block_k=bk, causal=causal)
    dqh = pl.pallas_call(
        dq_kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[qspec,
                  pl.BlockSpec((1, bk, D), kv_map),
                  pl.BlockSpec((1, bk, D), kv_map),
                  qspec, vec_q, vec_q],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dq",
    )(qh, kh, vh, doh, lse, delta)

    # dk/dv per QUERY head (grid ki outer, qi inner), then the GQA group
    # reduces outside — keeps every grid cell's accumulator private.
    dkv_kernel = functools.partial(_fa_dkv_kernel, scale=scale, block_q=bq,
                                   block_k=bk, causal=causal)
    qspec2 = pl.BlockSpec((1, bq, D), lambda h, ki, qi: (h, qi, 0))
    kv_map2 = lambda h, ki, qi: ((h // Hq) * Hkv + (h % Hq) // group, ki, 0)
    vec_q2 = pl.BlockSpec((1, bq, 1), lambda h, ki, qi: (h, qi, 0))
    dkh, dvh = pl.pallas_call(
        dkv_kernel,
        grid=(B * Hq, nk, nq),
        in_specs=[qspec2,
                  pl.BlockSpec((1, bk, D), kv_map2),
                  pl.BlockSpec((1, bk, D), kv_map2),
                  qspec2, vec_q2, vec_q2],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda h, ki, qi: (h, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda h, ki, qi: (h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Hq, S, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dkv",
    )(qh, kh, vh, doh, lse, delta)

    dq = dqh.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
    dk = dkh.reshape(B, Hkv, group, S, D).sum(2).astype(k.dtype)
    dv = dvh.reshape(B, Hkv, group, S, D).sum(2).astype(v.dtype)
    return dq, dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal, scale, bq, bk):
    o, _ = _flash_forward_pallas(q, k, v, causal, scale, bq, bk)
    return o


def _flash_diff_fwd(q, k, v, causal, scale, bq, bk):
    o, (oh, lse) = _flash_forward_pallas(q, k, v, causal, scale, bq, bk)
    return o, (q, k, v, oh, lse)


def _flash_diff_bwd(causal, scale, bq, bk, res, do):
    q, k, v, oh, lse = res
    return _flash_backward_pallas(q, k, v, oh, lse, do, causal, scale,
                                  bq, bk)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024):
    """Public entry: q (B,S,Hq,D), k/v (B,S,Hkv,D) → (B,S,Hq,D).

    On a TPU this is the Pallas kernel, fully differentiable: a custom VJP
    with Pallas dq and dk/dv kernels (the standard flash backward —
    recompute p from saved logsumexp, one rowwise delta = Σ do·o correction
    term).  A shape the kernel cannot tile raises ValueError there.  On any
    other platform it is `reference_attention`.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    if jax.devices()[0].platform != "tpu":
        return reference_attention(q, k, v, causal=causal, scale=scale)
    bq, bk = min(block_q, S), min(block_k, S)
    unmet = [why for ok, why in (
        (S % bq == 0 and S % bk == 0,
         f"seq {S} is not a multiple of blocks ({bq}, {bk})"),
        (D % 128 == 0, f"head_dim {D} is not a multiple of 128 lanes"),
        (Hq % Hkv == 0, f"{Hq} query heads do not group over {Hkv} kv heads"),
    ) if not ok]
    if unmet:
        raise ValueError(
            "flash_attention cannot tile this shape on the TPU: "
            + "; ".join(unmet) + ' (use attention_impl="xla")')
    return _flash_diff(q, k, v, causal, scale, bq, bk)
