"""A stack of power retention layers (models/transformer.py: `P`,
models/retention.py: one mixer, the attention form for a prompt's rows, the
state form for a decode step, the carried state's part between them), its
cache in the engine (llm/engine.py: NO page pool, a state row a slot, state
checkpoints sized by the operator and kept by their bytes), and the
benchmark family that holds it to a plain float32 reference
(benchmark/families/brumby.py, whose own cases run here too).  CPU, tiny
sizes, seeded weights, float32.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.tests.test_brumby import *                   # noqa: F401,F403
from benchmark.tests.test_brumby import engine, prompt_of, tiny
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.kv_cache import _PrefixCache
from ray_tpu.models import retention
from ray_tpu.models import transformer as T

DIMS = retention.RetentionDims(num_heads=4, num_kv_heads=2, head_dim=16,
                               chunk=8, block=4)
TOL = dict(rtol=2e-4, atol=2e-5)


# ---- the mixer -------------------------------------------------------------

@pytest.mark.parametrize("block", [1, 2, 4, 8, 16])
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(block):
    x = jax.random.normal(jax.random.key(0), (5, 16))
    y = jax.random.normal(jax.random.key(1), (5, 16))
    n = 16 // block
    assert retention.phi(x, block).shape == (5, n * (n + 1) // 2 * block ** 2)
    np.testing.assert_allclose(
        (retention.phi(x, block) * retention.phi(y, block)).sum(-1),
        (x * y).sum(-1) ** 2, rtol=1e-4, atol=1e-5)
    dims = retention.RetentionDims(num_heads=8, num_kv_heads=8, head_dim=128,
                                   block=block)
    assert dims.expanded == {1: 8256, 16: 9216}.get(block, dims.expanded)


def _attention_form(q, k, v, a, eps=1e-6):
    """The reference's form, whole, in numpy float64."""
    q, k, v, a = (np.asarray(x, np.float64) for x in (q, k, v, a))
    S, H, d = q.shape[1:]
    r = H // k.shape[2]
    A = np.repeat(np.cumsum(a, 1), r, 2)[0].T               # (H, S)
    k, v = np.repeat(k, r, 2)[0], np.repeat(v, r, 2)[0]
    s = np.einsum("shd,thd->hst", q[0], k) / math.sqrt(d)
    w = np.tril(np.ones((S, S)))[None] * s * s \
        * np.exp(np.minimum(A[:, :, None] - A[:, None, :], 0))
    return np.einsum("hst,thd->shd", w, v) / (w.sum(-1).T[..., None] + eps)


def _rows(S, seed=3):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (1, S, 4, 16)),
            jax.random.normal(ks[1], (1, S, 2, 16)),
            jax.random.normal(ks[2], (1, S, 2, 16)),
            -0.1 * jnp.abs(jax.random.normal(ks[3], (1, S, 2))))


@pytest.mark.parametrize("length", [16, 37])
def test_state_form_is_attention_form_is_chunked_form(length):
    q, k, v, a = _rows(length)
    want = _attention_form(q, k, v, a)
    zero = retention.zero_state(DIMS, 1)
    o, end, kept = retention.mixer(q, k, v, a, zero, DIMS, every=16)
    np.testing.assert_allclose(o[0], want, **TOL)          # attention form
    assert kept["s"].shape == (1, length // 16, 2, 16, DIMS.expanded)
    state, rows = zero, []
    for t in range(length):                                 # state form
        row, state, none = retention.mixer(
            q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], a[:, t:t + 1],
            state, DIMS)
        rows.append(row)
        if (t + 1) % 16 == 0:
            for key in ("s", "z"):
                np.testing.assert_allclose(
                    kept[key][:, (t + 1) // 16 - 1], state[key], **TOL)
    np.testing.assert_allclose(jnp.concatenate(rows, 1)[0], want, **TOL)
    for key in ("s", "z"):
        np.testing.assert_allclose(end[key], state[key], **TOL)
    # chunked: two pieces through the carried state, the second in a padded
    # bucket that is told its length and keeps ONE boundary, the last
    cut = length // 3
    first, mid, _ = retention.mixer(q[:, :cut], k[:, :cut], v[:, :cut],
                                    a[:, :cut], zero, DIMS)
    pad = 48 - (length - cut)
    rest = [jnp.pad(x[:, cut:], ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                    constant_values=c)
            for x, c in ((q, 1.0), (k, 1.0), (v, 1.0), (a, -0.3))]
    second, last, ring = retention.mixer(*rest, mid, DIMS,
                                         length=length - cut, every=16, keep=1)
    np.testing.assert_allclose(
        jnp.concatenate([first, second[:, :length - cut]], 1)[0], want, **TOL)
    for key in ("s", "z"):
        np.testing.assert_allclose(last[key], state[key], **TOL)
    assert ring["s"].shape[1] == 1
    if length - cut >= 16:
        upto = cut + (length - cut) // 16 * 16
        state = zero
        for t in range(upto):
            _, state, _ = retention.mixer(
                q[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1], a[:, t:t + 1],
                state, DIMS)
        np.testing.assert_allclose(ring["s"][:, 0], state["s"], **TOL)


def test_a_slot_that_is_not_live_keeps_its_state_bit_for_bit():
    q, k, v, a = (jnp.concatenate([x, x]) for x in _rows(9))
    zero = retention.zero_state(DIMS, 2)
    _, state, _ = retention.mixer(q[:, :8], k[:, :8], v[:, :8], a[:, :8],
                                  zero, DIMS)
    _, new, _ = retention.mixer(q[:, 8:], k[:, 8:], v[:, 8:], a[:, 8:], state,
                                DIMS, live=jnp.asarray([True, False]))
    assert (new["s"][1] == state["s"][1]).all() \
        and (new["z"][1] == state["z"][1]).all()
    assert not (new["s"][0] == state["s"][0]).all()


def test_the_step_kernel_is_the_reference_step():
    """Interpreted: the same update and products, block of lanes by block,
    with the state's buffers the results'."""
    B, G, R, D = 2, 2, 3, 2560
    ks = jax.random.split(jax.random.key(5), 6)
    args = (jax.random.normal(ks[0], (B, G, R, D)),
            jax.random.normal(ks[1], (B, G, D)),
            jax.random.normal(ks[2], (B, G, 128)),
            jax.random.uniform(ks[3], (B, G)),
            jax.random.normal(ks[4], (B, G, 128, D)),
            jax.random.normal(ks[5], (B, G, D)))
    assert retention._lane_block(D) == 1280 and retention._lane_block(9216) \
        == 1536
    want = retention.reference_step(*args)
    got = retention.retention_step(*args, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-3)
    full = retention.RetentionDims()
    assert full.expanded == 9216 and full.state_bytes() == 38_043_648
    assert retention.step_path(full) == "reference"         # on the CPU


def test_the_seeded_gate_forgets_over_hundreds_of_tokens_not_two():
    dims = retention.RetentionDims()
    w = retention.init_layer(jax.random.key(0), 256, dims, jnp.float32)
    h = jax.random.normal(jax.random.key(1), (1, 4096, 256))
    h = h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True))
    over_512 = 512 * retention.gate(w, h).mean((0, 1))
    assert (over_512 < -0.3).all() and (over_512 > -6).all(), over_512
    assert over_512[0] > 2 * over_512[-1] * 0.25           # spread over heads


# ---- the pattern -----------------------------------------------------------

def test_the_kind_is_stateful_and_its_sizes_are_the_dims():
    _, pc = tiny()
    assert "P" in T.KINDS and "P" in T.STATEFUL and "P" not in T.ATTEND
    assert pc.kinds == "PFPFPF" and pc.pattern_layers == 3
    z = pc.retention
    assert T.state_chunk(pc) == z.chunk == 16
    assert T.state_bytes(pc) == 3 * 4 * z.expanded * 17 * 4
    state = T.zero_state(pc, "P", 2)
    assert state["s"].shape == (2, 4, 16, z.expanded) \
        and state["z"].shape == (2, 4, z.expanded)
    params = T.init_params(pc, jax.random.key(0))
    assert sorted(params["layers"][0]["attn"]) == [
        "bg", "k_norm", "q_norm", "wg", "wk", "wo", "wq", "wv"]
    assert sum(a.size for a in jax.tree.leaves(params)) == pc.param_count()


def test_a_prefill_by_row_blocks_is_the_same_prefill():
    """The row-wise halves by row blocks of a bucket, the mixer over the
    whole of it: the logits, the state and the kept checkpoints of a call
    that is told its length are those of the call over the real rows."""
    from ray_tpu.llm import programs as E
    _, pc = tiny()
    params = T.init_params(pc, jax.random.key(2))
    toks = jax.random.randint(jax.random.key(3), (1, 256), 1, 512)
    ckpt = [T.zero_state(pc, "P", 2) for _ in range(3)]
    run = lambda t, n, rb: E._state_prefill_fn(    # noqa: E731
        params, None, None, None, t, 0, n, ckpt, 0, pc, 16, 64, rb, keep=2)
    lg, ks, vs, end, kept, chosen = run(toks, 150, 32)      # 8 blocks, 5 run
    assert ks is None and vs is None and chosen is None
    lg2, _, _, end2, kept2, _ = run(toks[:, :150], 150, 512)
    np.testing.assert_allclose(lg, lg2, rtol=2e-4, atol=2e-4)
    for a, b in zip(end, end2):
        np.testing.assert_allclose(a["s"], b["s"], rtol=2e-4, atol=2e-4)
    # two slots: boundaries 64 and 128, of which 128 is in slot 1
    assert kept[0]["s"].shape[:2] == (1, 2)
    for a, b in zip(kept, kept2):
        np.testing.assert_allclose(a["s"], b["s"], rtol=2e-4, atol=2e-4)


# ---- the engine ------------------------------------------------------------

def test_an_engine_of_retention_layers_holds_no_pool_and_awaits_no_page():
    cfg, pc = tiny()
    eng = engine(pc, 3)
    assert eng._pk is None and eng._pv is None
    assert eng.kv_pages_total == 0 and eng.kv_pages_free() == 0 \
        and eng.kv_page_occupancy() == 0.0
    dec = eng.decode_stats()
    assert dec["pool_row"] == "none" and dec["path"] == "none"
    assert len(eng._dev["rec"]) == 3 and eng._every == 64
    st = eng.state_stats()
    assert st["rows_total"] == 6 and st["row_bytes"] == T.state_bytes(pc)
    assert jax.tree.leaves(eng._ckpt[0])[0].shape[0] == 8   # 6 + 2 reserved
    # no free page, and both slots admit and answer as a cold engine does
    prompts = [prompt_of(cfg, 7, 150), prompt_of(cfg, 8, 90)]
    outs = eng.generate(prompts, SamplingParams(max_tokens=5))
    cold = engine(pc, 3, prefix_cache=False)
    assert cold._ckpt[0]["s"].shape[0] == 2                 # the reserved two
    assert outs == [cold.generate([p], SamplingParams(max_tokens=5))[0]
                    for p in prompts]
    dec, ret = eng.decode_stats(), eng.retention_stats()
    assert dec["pages_read"] == dec["pages_addressable"] == 0
    assert ret["enabled"] and ret["path"] == "reference" \
        and ret["block"] == 4 and ret["D"] == pc.retention.expanded
    assert ret["steps"] == dec["steps"] == 4 \
        and ret["rows_stepped"] == 8 and ret["step_rows_stepped"] == 2
    assert ret["boundaries_passed"] == 2 + 1 == ret["boundaries_kept"]
    assert eng.prefill_stats()["path"] == "none"
    assert not engine(T.PRESETS["tiny"], 0).retention_stats()["enabled"]


@pytest.mark.parametrize("rows, slots, max_len, keep", [
    (96, 8, 4096, 6),           # the hybrid's and LFM2's cells: all they pass
    (12, 8, 4096, 2),           # this family's cell
    (16, 2, 512, 4), (6, 2, 512, 2),        # the tiny engines of the tests
    (2048, 8, 4096, 64)])       # never more than a prompt has: 4,096 / 64
def test_the_keep_rule_follows_the_rows_there_are(rows, slots, max_len, keep):
    _, pc = tiny()
    pc = T.dataclasses.replace(pc, max_seq_len=max_len)
    eng = LLMEngine(pc, {"none": None}, max_batch=slots, max_len=max_len,
                    page_size=16, ckpt_rows=rows, prefix_cache=True)
    assert eng._keep == keep and eng.state_stats()["rows_total"] == rows


def test_a_prefill_keeps_the_last_boundaries_it_passes_and_no_others():
    cache = _PrefixCache(16, every=64, rows=range(2, 8))
    prompt = list(range(1, 300))                    # boundaries 64 .. 256
    assert cache.boundaries(prompt, 0) == [64, 128, 192, 256]
    assert cache.boundaries(prompt, 0, keep=2) == [192, 256]
    assert cache.boundaries(prompt, 128, keep=6) == [192, 256]
    cache.insert(prompt, None, None, {192: cache.free_rows.pop(),
                                      256: cache.free_rows.pop()})
    assert cache.rows_kept == 2 and len(cache.free_rows) == 4
    # the last two are kept already: a longer prompt adds its own last two
    assert cache.boundaries(prompt, 0, keep=2) == []
    assert cache.boundaries(prompt + list(range(90)), 0, keep=2) == [320, 384]
    # an entry holds rows only; a re-ask starts from the last kept boundary
    got = cache.lookup(prompt[:280] + [7] * 30)
    assert got[0] == 256 and got[1] == [] and got[2] >= 2
    assert cache.recomputed == 272 - 256
    while cache.evict_lru(None):
        pass
    assert sorted(cache.free_rows) == list(range(2, 8)) \
        and cache.rows_evicted == 2


def test_checkpoints_are_evicted_by_their_rows_alone():
    cfg, pc = tiny()
    eng = engine(pc, 6, ckpt_rows=4)            # two documents' rows
    for seed in range(3):                       # a third evicts the first's
        eng.generate([prompt_of(cfg, 20 + seed, 150)],
                     SamplingParams(max_tokens=2))
    st, pc_st = eng.state_stats(), eng.prefix_cache_stats()
    assert st["checkpoints_kept"] == 6 and st["checkpoints_evicted"] == 2 \
        and st["rows_in_use"] == 4
    assert pc_st["evictions"] > 0 and pc_st["allocated_pages"] == 0
    again = eng.generate([prompt_of(cfg, 22, 150) + [5, 6, 7]],
                         SamplingParams(max_tokens=2))
    assert eng.prefix_cache_stats()["hits"] == 1 and len(again[0]) == 2


@pytest.mark.parametrize("family_tests", ["test_lfm2_moe", "test_hybrid"])
def test_the_older_stateful_engines_keep_and_evict_what_they_did(family_tests):
    """The rule names no model and is the sized pool's alone: with the
    default rows (one for every 512 tokens of pages, however many pages)
    the hybrid's and LFM2's tiny engines keep EVERY boundary a prompt
    passes, in the rows they did, evict them with their pages, and serve a
    prompt that shares only an early prefix from its early boundary."""
    if family_tests == "test_hybrid":
        from tests.test_hybrid_model import _engine, _prompt, _tiny
        cfg, pc = _tiny()
        make, prompt = _engine, lambda s, n: _prompt(cfg, s, n)
    else:
        from benchmark.tests import test_lfm2_moe as m
        cfg, pc = m.tiny()
        make, prompt = m.engine, lambda s, n: m.prompt_of(cfg, s, n)
    eng = make(pc, 3)
    assert eng.state_stats()["rows_total"] == 16 and eng._keep == 8
    eng.generate([prompt(3, 150) + prompt(4, 9)], SamplingParams(max_tokens=2))
    st = eng.state_stats()
    assert st["rows_in_use"] == st["checkpoints_kept"] == 2
    assert sorted(eng._cache._row_key) == [16, 17]          # the rows it took
    long = prompt(5, 400)                   # passes 6 of the 8 there are
    eng.generate([long], SamplingParams(max_tokens=2))
    assert eng.state_stats()["checkpoints_kept"] == 2 + 6
    before = eng.prefix_cache_stats()["hits"]
    eng.generate([long[:100] + prompt(6, 60)], SamplingParams(max_tokens=2))
    assert eng.prefix_cache_stats()["hits"] == before + 1   # from row 64
    small = make(pc, 6, kv_pages=24)
    assert small.state_stats()["rows_total"] == 6 and small._keep == 8
    for seed in range(4):
        small.generate([prompt(10 + seed, 150)], SamplingParams(max_tokens=2))
    st = small.state_stats()
    assert st["checkpoints_kept"] == 8 and st["checkpoints_evicted"] == 4 \
        and st["rows_in_use"] == 4


def test_the_replica_passes_the_rows_through_and_reports_the_counters():
    import asyncio

    from ray_tpu.llm.serving import EngineReplica
    _, pc = tiny()
    rep = EngineReplica(pc, max_batch=2, max_len=512, page_size=16,
                        kv_pages=64, ckpt_rows=6, prefix_cache=True)
    stats = asyncio.run(rep.debug_stats())
    assert stats["retention"]["enabled"] and stats["retention"]["keep"] == 2
    assert stats["state"]["rows_total"] == 6 \
        and stats["decode"]["pool_row"] == "none" \
        and stats["kv_pages_total"] == 0 and stats["load"] == 0
