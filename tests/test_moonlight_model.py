"""A stack of latent attention layers (models/transformer.py: `L`, its one
first half and its two attention forms, expanded and absorbed) over a dense
and a routed feed-forward in its third form (models/routed.py: gated, with
a gated shared expert), its ONE-array page pool in the engine
(llm/engine.py, ops/paged_attention.py: the "latent" row), and the
benchmark family that holds it to a plain float32 reference
(benchmark/families/deepseek_v3.py, whose own cases run here too).  CPU,
tiny sizes, seeded weights, float32.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.tests.test_deepseek_v3 import *              # noqa: F401,F403
from benchmark.tests.test_prefill_kernel import *           # noqa: F401,F403
from benchmark.tests.test_deepseek_v3 import (TOL, engine, family, prompt_of,
                                              tiny)
from ray_tpu.llm.engine import SamplingParams
from ray_tpu.models import routed
from ray_tpu.models import transformer as T
from tests.test_hybrid_model import _plain_gated
from tests.test_llm import prefill_kernel                   # noqa: F401

SHARED = routed.RoutedDims(experts=16, held=16, held_from=0, top_k=3,
                           latent=0, width=24, shared_width=40, scale=2.446,
                           gated=True)


# ---- the routed layer's third form -----------------------------------------

def test_gated_layer_with_a_gated_shared_expert_is_the_plain_loop():
    lp = routed.init_layer(jax.random.key(0), 32, SHARED, jnp.float32)
    assert lp["ws1"].shape == (32, 80) and lp["ws2"].shape == (40, 32)
    assert lp["w1"].shape == (16, 32, 48)
    x = jax.random.normal(jax.random.key(1), (1, 12, 32))
    y, counts, chosen = routed.mixer(lp, x, SHARED)
    mix, shared = _plain_gated(lp, x[0], SHARED)
    np.testing.assert_allclose(y[0], mix + shared, **TOL)
    assert np.abs(shared).max() > 0.05 and chosen.shape == (1, 12, 3)
    assert counts.tolist()[1] == 36
    # its parameters, counted and held: 3 matrices an expert, 3 the shared
    n = sum(a.size for a in jax.tree.leaves(lp))
    assert n == SHARED.shared_params(32) + 16 * SHARED.expert_params(32)
    assert SHARED.shared_params(32) == 32 * 16 + 16 + 3 * 32 * 40


@pytest.mark.parametrize("width,tile", [(1408, 1408), (2816, 1408),
                                        (1536, 768), (3072, 1024),
                                        (2688, 896), (2048, 1024)])
def test_tile_of_a_width_of_eleven_lane_rows(width, tile):
    """1,408 = 11 x 128 has no divisor between 128 and itself: its grouped
    products take it whole and 2,816 in halves; every other family's width
    keeps the tile it had."""
    assert routed._tile(width) == tile and width % tile == 0


# ---- the grouped products take a matrix's k in one tile --------------------

PUBLISHED = {"nemotron_h": "nemotron-3-super-120b-l11-ep4.json",
             "lfm2_moe": "lfm2-24b-a2b-l9.json",
             "deepseek_v3": "moonlight-16b-a3b-l9.json"}
# What every call's products take, w1's and w2's (PERF.md §6, PR 45): k in
# ONE tile; `_tile(k)` gave Moonlight's w2 and the hybrid's w1 that already.
K_WHOLE = {"nemotron_h": ((128, 1024, 896), (128, 2688, 1024)),
           "lfm2_moe": ((128, 2048, 1024), (128, 1536, 1024)),
           "deepseek_v3": ((128, 2048, 1408), (128, 1408, 1024))}


def _published(name):
    """(RoutedDims, the (k, n) of w1 and of w2) of a family's cell."""
    import importlib
    import json
    import os

    from benchmark.run import ROOT
    family = importlib.import_module("benchmark.families." + name)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           PUBLISHED[name])) as f:
        cfg = family.program_config(json.load(f))
    lp = jax.eval_shape(lambda: routed.init_layer(
        jax.random.key(0), cfg.hidden_size, cfg.routed, jnp.bfloat16))
    return cfg.routed, (lp["w1"].shape[1:], lp["w2"].shape[1:])


@pytest.mark.parametrize("product", [0, 1], ids=["w1", "w2"])
@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_a_grouped_product_takes_k_in_one_tile(name, product):
    """The one rule, at the three families' published widths: k whole, so
    that the next row tile of a group asks for the block that is there; n
    by `_tile`; 128 rows.  A decode step, a suffix and a whole prompt take
    the same tiles: the rows are not asked."""
    k, n = _published(name)[1][product]
    tm, tk, tn = got = routed._tiling(k, n)
    assert got == K_WHOLE[name][product] == (routed.ROW_TILE, k,
                                             routed._tile(n))
    # the rows are padded to the row tile; the widths must divide
    assert n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    # two of each block and the accumulator, in the kernel's 16 MiB
    assert 4 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn < 16 << 20


@pytest.mark.parametrize("k,n,itemsize,tile", [
    (2048, 2816, 4, (128, 1024, 1408)),     # Moonlight's w1 in float32
    (8192, 2048, 2, (128, 1024, 1024)),     # a matrix four times as tall
    (2688, 1024, 4, (128, 896, 1024)),      # the hybrid's w2 in float32
    (1024, 2688, 4, (128, 1024, 896)),      # its w1 fits in float32 too
])
def test_k_is_cut_only_where_its_blocks_would_not_fit(k, n, itemsize, tile):
    assert routed._tiling(k, n, itemsize) == tile and k % tile[1] == 0


def test_k_whole_is_the_same_product_as_k_in_tiles():
    """The kernel itself, interpreted: k whole and k cut in two give what
    `ragged_dot` gives."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    k, n, sizes = 1536, 128, jnp.asarray([200, 0, 312], jnp.int32)
    rows = jax.random.normal(jax.random.key(0), (512, k))
    w = jax.random.normal(jax.random.key(1), (3, k, n)) / np.sqrt(k)
    assert routed._tiling(k, n) == (128, 1536, 128)
    plain = jax.lax.ragged_dot(rows, w, sizes)
    for tile in ((128, 1536, 128), (128, 768, 128)):
        got = gmm(rows, w, sizes, tiling=tile, interpret=True)
        np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", ["latent_relu2", "gated", "gated_shared"])
def test_many_rows_a_group_give_the_plain_per_expert_loop(form):
    """512 tokens over a handful of experts, each given a row tile or more
    (a whole prompt's shape), and the sums are the plain loop's."""
    from tests.test_hybrid_model import _plain_routed
    if form == "latent_relu2":
        dims = routed.RoutedDims(experts=8, held=2, held_from=2, top_k=2,
                                 latent=16, width=24, shared_width=40)
    else:
        dims = dataclasses.replace(
            SHARED, experts=4, held=4, top_k=2,
            shared_width=40 if form == "gated_shared" else 0)
    lp = routed.init_layer(jax.random.key(0), 32, dims, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 512, 32))
    y, counts, chosen = jax.jit(lambda p, x: routed.mixer(p, x, dims))(lp, x)
    mix, shared = _plain_routed(lp, x[0], dims, 2, 2) if dims.latent \
        else _plain_gated(lp, x[0], dims)
    np.testing.assert_allclose(y[0], mix + shared, **TOL)
    chosen = np.asarray(chosen)
    held = (chosen >= dims.held_from) & (chosen < dims.held_from + dims.held)
    assert counts.tolist() == [dims.held, held.sum()]


# ---- the two forms of one attention ----------------------------------------

def _layer(seed=0):
    _, pc = tiny()
    lp = T._init_pattern_layer("L", jax.random.key(seed), pc)
    return pc, lp


@pytest.mark.parametrize("cached", [0, 40])
def test_absorbed_is_expanded_on_the_same_weights(cached):
    """The same rows, queries and mask through both builders: float32
    rounding apart.  `cached`: key rows before the queries' own."""
    pc, lp = _layer()
    S = 24
    x = jax.random.normal(jax.random.key(1), (1, cached + S, pc.hidden_size))
    cos, sin = T.rope_angles(jnp.arange(cached + S), pc)
    q, row = T.latent_qrow(lp, x, cos, sin, pc)
    assert q.shape == (1, cached + S, 8, 48) and row.shape == (1, cached + S,
                                                               1, 160)
    keys, q = row[:, :, 0], q[:, cached:]
    mask = jnp.arange(cached + S)[None] <= cached + jnp.arange(S)[:, None]
    got = {form: build(lp["attn"], keys, pc)(q, mask)
           for form, build in T.LATENT_FORMS.items()}
    assert got["expanded"].shape == (1, S, 8, 32)
    np.testing.assert_allclose(got["absorbed"], got["expanded"], rtol=1e-5,
                               atol=1e-5)
    # and the pieces the decode step uses: wide queries over rows as they lie
    wide = T.latent_absorb(lp["attn"], q, pc)
    assert wide.shape == (1, S, 8, 160)
    s = jnp.einsum("bshc,btc->bhst", wide, keys) * pc.latent.scale
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), -1)
    o = T.latent_unabsorb(lp["attn"], jnp.einsum(
        "bhst,btc->bshc", p, keys[..., :144]), pc)
    np.testing.assert_allclose(o, got["expanded"], rtol=1e-5, atol=1e-5)


def test_the_form_follows_from_the_shapes_in_one_place():
    assert T.latent_form(8192) == T.latent_form(16) == "absorbed"
    assert T.latent_form(0) == "expanded"               # a whole prompt
    # the crossover the docstring names: W_kvb over every key row against
    # 768 values more a head and pair
    z = T.LatentDims()
    once = z.rank * 16 * (z.nope + z.value)
    more = 16 * ((z.rank + z.rope + z.rank) - (z.nope + z.rope + z.value))
    assert more == 16 * 768 and round(once / more) == 171
    assert (z.row, round(z.scale ** -2)) == (576, 192)
    assert z.param_count(2048, 16) == 13_762_560 + 512


def test_config_counts_what_init_holds():
    _, pc = tiny()
    params = T.init_params(pc, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == pc.param_count()
    assert pc.kinds == "LFLELE" and pc.cache_row == (1, 160)
    assert pc.head_dim_ == 48 and T.state_chunk(pc) == 0
    with pytest.raises(ValueError, match="pattern"):
        T.forward(params, jnp.zeros((1, 8), jnp.int32), pc)


# ---- through the engine ----------------------------------------------------

def test_a_suffix_over_a_cached_prefix_is_the_whole_prompt():
    cfg, pc = tiny()
    eng = engine(pc, 4)
    prompt = prompt_of(cfg, 4, 90)
    out = eng.generate([prompt], SamplingParams(max_tokens=6))[0]
    cold = eng.trace_logits(prompt, out[:-1])
    assert eng.latent_stats()["form"] == "expanded"
    hit = eng.trace_logits(prompt, out[:-1], cached=True)
    assert (cold["from"], hit["from"]) == (0, 80)       # 5 pages of 16
    assert eng.latent_stats()["form"] == "absorbed"
    np.testing.assert_allclose(hit["logits"], cold["logits"], **TOL)
    # a long suffix over cached rows takes the absorbed form too
    longer = prompt[:32] + prompt_of(cfg, 5, 200)
    eng.generate([prompt[:40]], SamplingParams(max_tokens=2))
    a = eng.trace_logits(longer, cached=True)
    assert a["from"] == 32 and eng.latent_stats()["form"] == "absorbed"
    b = eng.trace_logits(longer)
    np.testing.assert_allclose(a["logits"], b["logits"], **TOL)


def test_a_prompt_across_pages_and_row_blocks_against_the_reference():
    """1,100 tokens in a 2,048-row bucket: 3 of its 4 row blocks run, 69
    pages are installed, and decode reads across a page's end."""
    cfg, pc = tiny()
    eng = engine(pc, 6, max_len=2304, kv_pages=160, max_batch=1)
    prompt = prompt_of(cfg, 6, 1100)
    out = eng.generate([prompt], SamplingParams(max_tokens=6))[0]
    st = eng.prefill_stats()
    assert (st["row_blocks_run"], st["row_blocks_dense"]) == (3, 4)
    got = eng.trace_logits(prompt, out[:-1])
    toks = jnp.asarray([prompt + out[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0][len(prompt) - 1:]
    np.testing.assert_allclose(got["logits"], ref, rtol=5e-4, atol=5e-4)
    assert np.asarray(ref).argmax(-1).tolist() == out


def test_a_whole_prompt_through_the_kernel_against_the_reference(
        prefill_kernel):                                    # noqa: F811
    """Heads as the published ones lie (keys 128 + 64 over values of 128),
    the prefill bodies steered onto the interpreted kernel: 1,100 tokens in
    a 2,048-row bucket attend through it, 9 of 16 query blocks of 128, and
    build no scores; the re-ask's suffix still absorbs over its pages, in
    XLA."""
    cfg, _ = tiny()
    cfg = dict(cfg, kv_lora_rank=96, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128)
    pc = family.program_config(cfg, max_seq_len=512)
    prefill_kernel()
    eng = engine(pc, 8, max_len=2304, kv_pages=160, max_batch=1)
    prompt = prompt_of(cfg, 8, 1100)
    out = eng.generate([prompt], SamplingParams(max_tokens=6))[0]
    st = eng.prefill_stats()
    assert (st["path"], st["kernel_calls"], st["xla_calls"]) == ("kernel", 1,
                                                                 0)
    assert (st["kv_blocks_run"], st["kv_blocks_dense"]) == (45, 256)
    assert (st["row_blocks_run"], st["row_blocks_dense"]) == (3, 4)
    assert eng.latent_stats()["prefills"] == {"expanded": 1, "absorbed": 0}
    toks = jnp.asarray([prompt + out[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0][len(prompt) - 1:]
    assert np.asarray(ref).argmax(-1).tolist() == out
    cold = eng.trace_logits(prompt, out[:-1])
    assert eng.prefill_stats()["kernel_calls"] == 2
    np.testing.assert_allclose(cold["logits"], ref, rtol=5e-4, atol=5e-4)
    hit = eng.trace_logits(prompt, out[:-1], cached=True)
    st = eng.prefill_stats()
    assert hit["from"] == 1088 and eng.latent_stats()["form"] == "absorbed"
    assert (st["path"], st["kernel_calls"], st["xla_calls"]) == ("xla", 2, 1)
    np.testing.assert_allclose(hit["logits"], ref, rtol=5e-4, atol=5e-4)


def test_one_pool_and_what_the_counters_say():
    cfg, pc = tiny()
    eng = engine(pc, 7)
    assert eng._pv is None and eng._pk.shape == (3, 65, 16, 256)
    assert eng._demote is None and eng._every == 0
    prompt = prompt_of(cfg, 7, 70)
    for _ in range(2):
        eng.generate([prompt], SamplingParams(max_tokens=4))
    st = eng.latent_stats()
    assert st["enabled"] and st["pool_row"] == "latent"
    assert (st["row_bytes"], st["pool_row_bytes"]) == (160 * 4, 256 * 4)
    # cold: 70 rows attended, all expanded; hit: 64 cached + 6 new, none
    assert (st["rows_attended"], st["rows_expanded"]) == (140, 70)
    assert st["prefills"] == {"expanded": 1, "absorbed": 1}
    # three decode steps a request, at 70, 71, 72 tokens in cache + its own
    assert st["steps"] == 6 and st["rows_read"] == 2 * (71 + 72 + 73)
    assert st["step_rows_read"] == 73
    assert eng._prefill_ran["form"] == "absorbed" \
        and eng._prefill_ran["expanded"] == 0
    assert eng.prefix_cache_stats()["hits"] == 1
    dense = engine(T.PRESETS["tiny"], 0, prefix_cache=False)
    assert dense.latent_stats() == {"enabled": False}


def test_what_a_latent_pattern_is_refused():
    cfg, pc = tiny()
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    for kw in ({"mesh": mesh}, {"prefill_chunk": 64}, {"sp_degree": 2}):
        with pytest.raises(ValueError, match="pattern of layer kinds"):
            engine(pc, 0, **kw)
    # (the gather window, `_KVWindow`, is reached through add_paged_request
    # and prefill_paged_chunk alone; a replica always hands its fetchers in)
    eng = engine(pc, 0, kv_fetch=lambda handle: handle)
    blob = {"k": np.zeros((3, 8, 1, 160)), "v": np.zeros((3, 8, 1, 160)),
            "len": 8}
    calls = {
        "add_external_request": lambda: eng.add_external_request(blob, 1),
        "add_paged_request": lambda: eng.add_paged_request([blob], 8, 1),
        "prefill_paged_chunk": lambda: eng.prefill_paged_chunk(
            [1] * 8, 0, [], span=8, is_last=True),
        "prefill_only": lambda: eng.prefill_only([1] * 8),
        "decode_from": lambda: eng.decode_from(blob, 1),
    }
    for name, call in calls.items():
        with pytest.raises(ValueError, match="one row a token in one pool"):
            call()
    # a pool whose row is a whole number of lane rows is not a latent row
    with pytest.raises(ValueError, match="latent row"):
        engine(dataclasses.replace(pc, latent=T.LatentDims(
            rank=112, nope=32, rope=16, value=32)), 0)
