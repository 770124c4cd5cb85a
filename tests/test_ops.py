"""Attention op tests on the 8-device CPU mesh (Pallas path needs real TPU;
the fallback + ring/ulysses shard_map paths are fully exercised here)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import (flash_attention, reference_attention,
                         ring_attention, ulysses_attention)
from ray_tpu.parallel import MeshSpec, build_mesh


def _rand_qkv(B=2, S=32, Hq=4, Hkv=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    return q, k, v


def test_flash_falls_back_and_matches():
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_gradients_match_reference():
    """Differentiability of the flash path (on TPU this exercises the
    custom-VJP Pallas dq/dkv kernels; on the CPU mesh it runs the
    reference path end-to-end through jax.grad)."""
    q, k, v = _rand_qkv(B=1, S=256, Hq=4, Hkv=2, D=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-2


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_reference(causal):
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    q, k, v = _rand_qkv(B=2, S=32, Hq=4, Hkv=2, D=16)
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ring_sp1_degenerates():
    mesh = build_mesh(MeshSpec(dp=8))
    q, k, v = _rand_qkv(B=8)
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ulysses_matches_reference():
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    # heads divisible by sp: Hq=Hkv=4
    q, k, v = _rand_qkv(B=2, S=32, Hq=4, Hkv=4, D=16)
    out = ulysses_attention(q, k, v, mesh=mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ring_attention_in_model():
    """attention_impl='ring' end-to-end under jit on a dp x sp mesh."""
    import dataclasses
    from ray_tpu.models import PRESETS, forward, init_params
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    cfg = dataclasses.replace(PRESETS["tiny"], attention_impl="ring")
    params = init_params(cfg, jax.random.key(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (4, 32)), jnp.int32)
    with jax.set_mesh(mesh):
        logits = jax.jit(lambda p, t: forward(p, t, cfg, mesh))(params, toks)
    ref_cfg = dataclasses.replace(cfg, attention_impl="xla")
    ref = jax.jit(lambda p, t: forward(p, t, ref_cfg, mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
