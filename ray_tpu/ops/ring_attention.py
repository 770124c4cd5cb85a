"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence parallelism anywhere (SURVEY.md §5.7 — grep
confirms no ring-attention/Ulysses/context-parallel in python/ray); it
delegates long-context scaling to user frameworks.  Here it is first-class:
attention over a sequence axis sharded across the `sp` mesh axis, with the
KV shards rotated around the ICI ring (lax.ppermute compiles to
collective-permute on the interconnect) and an online-softmax accumulator so
no device ever materializes the full sequence.

Two strategies, matching the literature:
  ring_attention     — KV rotation, O(S/P) memory per device, overlap-friendly
  ulysses_attention  — all-to-all seq→head resharding, local full attention
                       (head-count must be divisible by the sp size)

Both are pure shard_map programs: they run identically on the 8-device CPU
test mesh and a TPU pod, and XLA overlaps the ppermute with compute.  Batch
stays sharded over (dp, fsdp) and heads over tp across the shard_map
boundary — attention is embarrassingly parallel in both, so only the
sequence axis communicates.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _grouped_scores(q, k, scale):
    """q (B,Sq,Hkv,G,D), k (B,Sk,Hkv,D) → scores (B,Hkv,G,Sq,Sk) f32."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k,
                      preferred_element_type=jnp.float32) * scale


def _ring_attention_shard(q, k, v, *, axis_name: str, causal: bool,
                          scale: float, n_shards: int):
    """shard_map body: q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D) local shards."""
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    idx = jax.lax.axis_index(axis_name)
    q_pos = idx * Sq + jnp.arange(Sq)

    def accumulate(k_blk, v_blk, m, l, acc, s):
        """One online-softmax update against the KV shard of src=idx-s."""
        src = (idx - s) % n_shards
        scores = _grouped_scores(qg, k_blk, scale)         # (B,Hkv,G,Sq,Sk)
        if causal:
            k_pos = src * Sk + jnp.arange(Sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None, None], scores, -1e30)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)                         # (B,Hkv,G,Sq,1)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, alpha * acc + pv

    m = jnp.full((B, Hkv, G, Sq, 1), -1e30, jnp.float32)
    l = jnp.zeros((B, Hkv, G, Sq, 1), jnp.float32)
    acc = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def step(carry, s):
        k_blk, v_blk, m, l, acc = carry
        m, l, acc = accumulate(k_blk, v_blk, m, l, acc, s)
        # Rotate KV to the next device for the following iteration.
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, m, l, acc), None

    if n_shards > 1:
        (k, v, m, l, acc), _ = jax.lax.scan(
            step, (k, v, m, l, acc), jnp.arange(n_shards - 1))
    # Final shard: accumulate only — no rotation after the last use.
    m, l, acc = accumulate(k, v, m, l, acc, n_shards - 1)

    out = acc / jnp.maximum(l, 1e-30)                      # (B,Hkv,G,Sq,D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def _qkv_specs(axis_name: str,
               batch_axes: Tuple[str, ...],
               heads_axis: Optional[str]):
    """(B, S, H, D) specs: batch over dp/fsdp, seq over sp, heads over tp —
    attention is independent across batch and heads, so only `axis_name`
    communicates inside the body."""
    return P(batch_axes if batch_axes else None, axis_name, heads_axis, None)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = True, scale: Optional[float] = None,
                   batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
                   heads_axis: Optional[str] = "tp"):
    """Causal GQA attention with the sequence dim sharded over `axis_name`.

    q,k,v: (B, S, H*, D) global arrays.  Batch/head dims keep their dp-fsdp/
    tp shardings; only the sequence axis is communicated (KV ring rotation).
    Degenerate sp=1 reduces to one local attention pass.
    """
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    n = mesh.shape[axis_name]
    batch_axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    if heads_axis is not None and mesh.shape.get(heads_axis, 1) == 1:
        heads_axis = None

    body = functools.partial(_ring_attention_shard, axis_name=axis_name,
                             causal=causal, scale=scale, n_shards=n)
    spec = _qkv_specs(axis_name, batch_axes, heads_axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                      causal: bool = True, scale: Optional[float] = None,
                      batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
                      heads_axis: Optional[str] = "tp"):
    """All-to-all sequence parallelism: reshard seq→heads, attend locally,
    reshard back.  Requires local head count divisible by the sp size."""
    from .flash_attention import reference_attention

    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    batch_axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    if heads_axis is not None and mesh.shape.get(heads_axis, 1) == 1:
        heads_axis = None

    def body(q_loc, k_loc, v_loc):
        # local (B, S/n, H, D) → gather seq, scatter heads → (B, S, H/n, D)
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                      concat_axis=1, tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                      concat_axis=2, tiled=True)

        qh, kh, vh = (seq_to_heads(q_loc), seq_to_heads(k_loc),
                      seq_to_heads(v_loc))
        o = reference_attention(qh, kh, vh, causal=causal, scale=scale)
        return heads_to_seq(o)

    spec = _qkv_specs(axis_name, batch_axes, heads_axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
