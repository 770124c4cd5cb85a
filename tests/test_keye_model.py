"""Attention that reads the cached tokens a learned indexer picks
(models/transformer.py: kind `S`, `IndexerDims`; ops/sparse_attention.py:
the masked form of a prefill's row block and the gathered form of a decode
step), its third pool of index keys behind the engine's page allocator
(llm/programs.py: `CACHES["sparse"]`), a softmax router (models/routed.py),
and the benchmark family that holds the stack to a plain float32 reference
(benchmark/families/keye_vl2.py, whose own cases run here too, imported by
name).  CPU, tiny sizes, seeded weights, float32.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.tests.test_keye_vl2 import (                 # noqa: F401
    engine, prompt_of, tiny,
    test_a_hit_after_eviction_reads_the_index_keys_of_its_cached_pages,
    test_a_scanned_period_of_routed_layers_is_refused,
    test_a_seeded_softmax_router_is_balanced_in_its_weights,
    test_check_fails_a_program_that_selects_the_most_recent_tokens,
    test_check_fails_one_selected_token_outside_the_zone,
    test_check_fails_the_float8_control, test_check_passes_sound_seeds,
    test_decode_step_bytes_against_a_hand_count,
    test_prefill_then_decode_through_the_cache_is_the_full_forward,
    test_program_config_refuses_what_the_kinds_cannot_express,
    test_sizes_from_the_keys_are_the_published_ones,
    test_the_file_holds_every_published_key,
    test_the_softmax_router_is_the_references,
    test_two_slots_one_under_and_one_over_top_k_in_one_step)
from ray_tpu.llm import programs
from ray_tpu.models.transformer import ATTEND, KINDS, IndexerDims
from ray_tpu.ops import sparse_attention as sa


def _numpy_topk_mask(scores, seen, k):
    """The k seen keys of each row with the highest scores, the least index
    winning an exact tie: a stable sort in numpy."""
    out = np.zeros(scores.shape, bool)
    for r, (row, open_) in enumerate(zip(np.asarray(scores), np.asarray(seen))):
        at = np.flatnonzero(open_)
        order = at[np.argsort(-row[at], kind="stable")][:k]
        out[r, order] = True
    return out


@pytest.mark.parametrize("rows", [8, 24])     # a re-ask's question, a block
@pytest.mark.parametrize("ties", [False, True])
def test_select_mask_is_the_top_k_of_the_seen_keys(rows, ties):
    keys, k = 200, 32
    scores = jax.random.normal(jax.random.key(0), (rows, keys))
    if ties:                # few distinct values: the cut falls among ties
        scores = jnp.round(scores * 2) / 2
    scores = scores.at[0].set(-jnp.abs(scores[0]))      # all negative
    seen = jnp.arange(keys)[None] <= (keys - rows + jnp.arange(rows))[:, None]
    seen = seen.at[1].set(jnp.arange(keys) < 20)        # fewer than k
    got = np.asarray(sa.select_mask(scores, seen, k))
    assert (got == _numpy_topk_mask(scores, seen, k)).all()
    assert got[1].sum() == 20 and (got.sum(-1)[2:] == k).all()


def test_the_counted_cut_is_the_sorted_one_and_the_rule_reads_the_rows():
    scores = jax.random.normal(jax.random.key(1), (16, 300))
    scores = scores.at[3].set(-jnp.abs(scores[3]))      # all negative
    scores = scores.at[4, :7].set(-0.0).at[4, 7:40].set(0.0)
    for k in (1, 40, 300):
        want = np.sort(np.asarray(scores), axis=1)[:, -k]
        assert (sa.kth_largest(scores, k) == sa._ordered(want)).all(), k
    assert sa.sparse_path(1) == "gathered" and sa.sparse_path(64) == "masked"


def test_index_scores_are_the_equation():
    qi = jax.random.normal(jax.random.key(2), (5, 4, 8))
    wi = jax.random.normal(jax.random.key(3), (5, 4))
    keys = jax.random.normal(jax.random.key(4), (33, 8))
    want = np.einsum("rj,rjt->rt", np.asarray(wi, np.float64), np.maximum(
        np.einsum("rjd,td->rjt", np.asarray(qi, np.float64),
                  np.asarray(keys, np.float64)), 0))
    np.testing.assert_allclose(sa.index_scores(qi, wi, keys), want,
                               rtol=1e-4, atol=1e-4)


def test_the_table_has_a_row_of_three_pools_and_the_engine_holds_them():
    cfg, pc = tiny()
    assert "S" in KINDS and "S" in ATTEND
    entry = programs.cache_of(pc)
    assert entry is programs.CACHES["sparse"] and entry.pools == 3
    assert entry.whole_program and not entry.kernel_over_pages
    assert IndexerDims(16, 64, 2048).row == (2, 64)     # one lane row
    assert IndexerDims(16, 64, 2048).param_count(2048) == 2_261_120
    eng = engine(pc, 0)
    pk, (pv, pi) = eng._pk, eng._pv
    assert pk.shape == pv.shape == (2, 161, 8, 4, 16)
    assert pi.shape == (2, 161, 8, 128)                 # lanes, zeros past 8
    assert eng._demote is None                          # not a pair
    assert eng.decode_stats()["pool_row"] == "heads"
    eng.generate([prompt_of(cfg, 0, 60)])
    assert float(jnp.abs(eng._pv[1][..., 8:]).max()) == 0.0
    assert float(jnp.abs(eng._pv[1][..., :8]).max()) > 0.0
    counted = programs.counters(pc, pool=eng._pk, keep=0, slots=2,
                                table_rows=64)
    assert set(counted) == {"sparse", "routed"}
    assert {"layers", "topk", "row_bytes", "index_row_bytes", "rows_visible",
            "rows_selected", "index_rows_read", "index_rows_scanned",
            "index_pool_row_bytes", "path"} <= set(
                eng.sparse_stats()) and eng.sparse_stats()["steps"] > 0
    assert counted["sparse"]["scanned_a_step"] == 2 * 64


def test_the_prefill_event_counts_the_pairs_seen_and_selected():
    c = programs._sparse_zero(tiny()[1], 2, 64)
    got = programs._sparse_prefill(c, 50, None, 0, 64)
    seen = sum(range(1, 51))
    assert got == {"pairs_visible": seen,
                   "pairs_selected": sum(min(t, 32) for t in range(1, 51))}
    got = programs._sparse_prefill(c, 10, 40, 512, 16)
    assert got["pairs_visible"] == sum(range(41, 51))
    assert got["pairs_selected"] == 10 * 32
    assert c["prefill_pairs_visible"] == seen + sum(range(41, 51))


@pytest.mark.parametrize("length", [70, 200, 256])
def test_a_whole_prompt_by_row_blocks_is_the_whole_prompt_at_once(length):
    """A bucket run by row blocks (each block over the keys up to its own
    last row: one branch for every four blocks of keys) against the same
    bucket with all rows at once: the logits, the new cache rows of all
    three pools up to the last block that ran, and the sets every real
    row picked."""
    cfg, pc = tiny()
    eng = engine(pc, 7)
    toks = np.zeros((1, 256), np.int32)
    toks[0, :length] = prompt_of(cfg, 7, length)

    def run(row_block):
        return jax.jit(lambda p, t, n: programs._state_prefill_fn(
            p, eng._pk, eng._pv, None, t, 0, n, [], 0, pc, eng.page, 0,
            row_block=row_block, expose=True))(eng.params, toks, length)
    at_once, by_blocks = run(512), run(16)      # 16 blocks, 4 key branches
    ran = -(-length // 16) * 16
    np.testing.assert_allclose(by_blocks[0], at_once[0], rtol=2e-4, atol=2e-4)
    for a, b in zip(jax.tree.leaves(by_blocks[1:3]),
                    jax.tree.leaves(at_once[1:3])):
        np.testing.assert_allclose(a[:, :ran], b[:, :ran], rtol=2e-4,
                                   atol=2e-4)
        assert not np.asarray(a[:, ran:]).any()
    picked = np.asarray(by_blocks[-1])
    assert picked.shape == (2, 256, 256)
    assert (picked[:, :length] == np.asarray(at_once[-1])[:, :length]).all()
    assert (picked[:, :length].sum(-1) == np.minimum(
        np.arange(length) + 1, 32)).all()


# ---- The "paged" decode form: two kernels over a slot's live pages ---------
# Interpreted (CPU), at widths the kernels tile: heads of 128, pages of 16, an
# index key of 64 held as one lane row; two pages a chunk, so a few pages
# already cross chunk boundaries.

PAGE, KVH, HD, TOPK = 16, 4, 128, 40


@pytest.fixture
def two_page_chunks(monkeypatch):
    monkeypatch.setattr(sa, "_SELECT_CHUNK_ROWS", 2 * PAGE)
    monkeypatch.setattr(sa.paged_attention, "_CHUNK_ROWS", 2 * PAGE * KVH)


def _paged_case(lengths, *, pages=8, seed=0, ties=False, dtype=jnp.bfloat16):
    """q, the index queries and weights, three stacked pools of two layers
    (the other layer NaN), tables, lengths; `ties`: small whole numbers
    drawn with repeats, so that every score is exact and many are equal."""
    B, N = len(lengths), 1 + len(lengths) * pages
    ks = jax.random.split(jax.random.key(seed), 6)
    draw = (lambda k, s: jax.random.randint(k, s, -2, 3).astype(jnp.float32)
            ) if ties else (lambda k, s: jax.random.normal(k, s, jnp.float32))
    q = jax.random.normal(ks[0], (B, 8, HD), jnp.float32).astype(dtype)
    qi, wi = draw(ks[1], (B, 4, 64)).astype(dtype), \
        draw(ks[2], (B, 4)).astype(dtype)
    pk, pv = (jax.random.normal(k, (N, PAGE, KVH, HD), jnp.float32
                                ).astype(dtype) for k in ks[3:5])
    pi = jnp.pad(draw(ks[5], (N, PAGE, 64)), ((0, 0), (0, 0), (0, 64))
                 ).astype(dtype)
    stack = lambda pool: jnp.stack([jnp.full_like(pool, jnp.nan), pool])
    tables = np.random.default_rng(seed).permutation(
        np.arange(1, N)).reshape(B, pages).astype(np.int32)
    return (q, qi, wi, stack(pk), stack(pv), stack(pi), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


def _plain(q, qi, wi, pk, pv, pi, tables, lengths, layer=1, k=TOPK):
    """The plain form: (o, scores the selection saw (B, T), picked (B, T))."""
    B = tables.shape[0]
    rows = pi[jnp.full_like(tables, layer), tables].reshape(B, -1, 128)
    at, valid = sa.pick_positions(qi, wi, rows, lengths, k)
    o = sa.gathered_attention(q, pk, pv, tables, at, valid, layer,
                              HD ** -0.5, (KVH, HD))
    picked = np.zeros(rows.shape[:2], bool)
    for b in range(B):
        picked[b, np.asarray(at[b])[np.asarray(valid[b])]] = True
    return np.asarray(o, np.float32), picked


@pytest.mark.parametrize("what", [
    "under_top_k", "at_top_k", "over_top_k", "page_edges", "chunk_edges",
    "ties", "dead_slot", "float32"])
def test_the_paged_form_is_the_plain_form(what, two_page_chunks):
    """`index_select`'s scores are `pick_positions`' (-inf past a slot's
    length), the cut marks the same SETS (an exact tie at the cut goes to
    the least index), and the paged kernel under the marks is
    `gathered_attention` and a float32 softmax over exactly those rows."""
    lengths = {"under_top_k": [5, TOPK - 2, 17],
               "at_top_k": [TOPK - 1, TOPK, TOPK + 1],
               "over_top_k": [70, 127, 99],
               "page_edges": [PAGE - 2, PAGE - 1, PAGE, 3 * PAGE - 1,
                              3 * PAGE],
               "chunk_edges": [2 * PAGE - 2, 2 * PAGE - 1, 2 * PAGE,
                               4 * PAGE - 1, 4 * PAGE],
               "ties": [90, 127, 60], "dead_slot": [0, 100, 0],
               "float32": [70, 127, 20]}[what]
    case = _paged_case(lengths, ties=what == "ties", dtype=jnp.float32
                       if what == "float32" else jnp.bfloat16)
    q, qi, wi, pk, pv, pi, tables, lens = case
    if what == "dead_slot":     # as the engine hands them over: the scratch
        tables = tables.at[0].set(0).at[2].set(0)   # page, one token
        case = (*case[:6], tables, lens)
    want_o, want = _plain(*case)
    B, T = want.shape
    scores = np.asarray(sa.index_select(qi, wi, pi, tables, lens, 1,
                                        interpret=True))
    rows = pi[1][tables].reshape(B, T, 128).astype(jnp.float32)
    full = np.einsum("bjt,bj->bt", np.maximum(np.einsum(
        "bjw,btw->bjt", np.pad(np.asarray(qi, np.float32),
                               ((0, 0), (0, 0), (0, 64))), rows), 0),
        np.asarray(wi, np.float32))
    live = np.arange(T)[None] <= np.asarray(lens)[:, None]
    assert np.isneginf(scores[~live]).all()
    np.testing.assert_allclose(scores[live], full[live], rtol=1e-5,
                               atol=1e-4)
    if what == "ties":          # whole numbers: exact, and many are equal
        assert (scores[live] == full[live]).all()
        cut = np.sort(np.where(live, full, -np.inf), 1)[:, -TOPK]
        assert ((np.where(live, full, np.nan) == cut[:, None]).sum(1)
                > 1).any()
    o, seen = sa.paged_attention_over_picks(
        q, qi, wi, pk, pv, pi, tables, lens, 1, TOPK, HD ** -0.5,
        interpret=True)
    assert (np.asarray(seen) == want).all()
    assert (want.sum(1) == np.minimum(np.asarray(lens) + 1, TOPK)).all()
    at, valid = sa.positions_of(seen, TOPK)
    for b in range(B):
        assert sorted(np.asarray(at[b])[np.asarray(valid[b])]) \
            == np.flatnonzero(want[b]).tolist()
    # a float32 softmax over exactly the picked rows
    oracle = np.zeros_like(want_o)
    k32, v32 = (np.asarray(pool[1], np.float32)[np.asarray(tables)].reshape(
        B, T, KVH, HD) for pool in (pk, pv))
    for b in range(B):
        for h in range(8):
            s = k32[b, want[b], h // 2] @ np.asarray(q, np.float32)[b, h] \
                * HD ** -0.5
            p = np.exp(s - s.max())
            oracle[b, h] = (p / p.sum()) @ v32[b, want[b], h // 2]
    tol = 1e-4 if what == "float32" else 2e-2
    assert np.abs(np.asarray(o, np.float32) - oracle).max() < tol
    assert np.abs(np.asarray(o, np.float32) - want_o).max() < tol


def test_the_paged_form_reads_live_pages_only(two_page_chunks):
    """Every page no slot holds is NaN in all three pools and the rows of a
    slot's last page past its length are huge: scores, marks and output are
    what they were (the plain form gathers every slot's whole table)."""
    lengths = [0, PAGE - 1, PAGE, 3 * PAGE + 5, 8 * PAGE - 1]
    q, qi, wi, pk, pv, pi, tables, lens = _paged_case(lengths)
    run = lambda pk, pv, pi: (
        sa.index_select(qi, wi, pi, tables, lens, 1, interpret=True),
        *sa.paged_attention_over_picks(q, qi, wi, pk, pv, pi, tables, lens,
                                       1, TOPK, HD ** -0.5, interpret=True))
    clean = run(pk, pv, pi)
    held = np.zeros(pk.shape[1], bool)
    tail = np.zeros(pk.shape[1:3], bool)
    for b, n in enumerate(lengths):
        last = n // PAGE
        held[np.asarray(tables)[b, :last + 1]] = True
        tail[int(tables[b, last]), n % PAGE + 1:] = True
    over = lambda mask, pool: jnp.asarray(mask).reshape(
        (1,) + mask.shape + (1,) * (pool.ndim - 1 - mask.ndim))
    poison = lambda pool: jnp.where(
        over(tail, pool), 3e38, jnp.where(over(held, pool), pool, jnp.nan)
    ).astype(pool.dtype)
    dirty = run(poison(pk), poison(pv), poison(pi))
    for a, b in zip(dirty, clean):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert np.isfinite(np.asarray(dirty[1], np.float32)).all()


def test_the_rule_reads_shapes_and_the_counters_follow_the_form(monkeypatch):
    """`sparse_path`: a row block is "masked"; one row is "gathered" in
    this process (no TPU) and asked of no shapes, "paged" where a chip
    would take the paged kernel for the shapes and the index row is whole
    lane rows.  `sparse_stats()` counts what the step's program read by
    that form: every slot's whole table and `topk` rows a slot, or the live
    pages rounded up to the kernels' chunks, a dead slot one chunk."""
    from test_paged_attention import as_on_a_tpu
    shapes = ((8, 32, 128), (6, 4097, 16, 4, 128), (6, 4097, 16, 128),
              (8, 1024))
    assert sa.sparse_path(512) == sa.sparse_path(8, *shapes) == "masked"
    assert sa.sparse_path(1) == sa.sparse_path(1, *shapes) == "gathered"
    cfg, pc = tiny()
    eng = engine(pc, 0)
    eng.generate([prompt_of(cfg, 0, 60)])
    st = eng.sparse_stats()
    assert st["path"]["decode"] == eng.decode_stats()["path"] == "gathered"
    assert st["index_rows_scanned"] == st["steps"] * 2 * 512
    assert st["kv_rows_read"] == st["steps"] * 2 * 32
    monkeypatch.setattr(sa.paged_attention, "decode_path",
                        as_on_a_tpu(sa.paged_attention.decode_path))
    assert sa.sparse_path(1, *shapes) == "paged"
    assert sa.sparse_path(1, (8, 32, 64), (6, 4097, 16, 4, 64), shapes[2],
                          shapes[3]) == "gathered"      # heads of 64 by 4
    assert sa.sparse_path(1, shapes[0], shapes[1], (6, 4097, 16, 64),
                          shapes[3]) == "gathered"      # half a lane row
    wide = dataclasses.replace(pc, num_heads=32, num_kv_heads=4, head_dim=128)
    pool = jax.ShapeDtypeStruct(shapes[1], jnp.bfloat16)
    c = programs._sparse_zero(wide, 8, 16384, pool=pool)
    assert c["path"]["decode"] == "paged" and c["chunk_rows"] == [1024, 512]
    assert programs.CACHES["sparse"].decode_form(wide, pool, 8, 16384) \
        == "paged"
    # three live slots (cached tokens; the step's own lands at that index)
    # of 1, 32 and 65 pages: 1, 1 and 2 chunks of the index pass's 1,024
    # rows, 1, 1 and 3 of the attention's 512; five dead slots, one each
    programs._sparse_decode(c, np.asarray([0, 511, 1024]), None)
    assert c["index_rows_scanned"] == (4 + 5) * 1024
    assert c["kv_rows_read"] == (5 + 5) * 512
    assert c["index_rows_read"] == 1 + 512 + 1025
    programs._sparse_decode(c, np.asarray([16127] * 8), None)
    assert c["index_rows_scanned"] == 9 * 1024 + 8 * 16384   # 15.75 chunks
    assert c["kv_rows_read"] == 10 * 512 + 8 * 16384


@pytest.mark.parametrize("n", [20, 200])
def test_a_served_step_on_the_paged_form_picks_what_the_plain_one_picks(
        n, monkeypatch):
    """The engine's own decode step with the rule answering "paged" and the
    kernels interpreted, at TINY's widths: the logits are the plain form's
    and `picked`, filled from the marks the step attended under, holds the
    same sets; the counters say which form ran."""
    cfg, pc = tiny()
    prompt = prompt_of(cfg, 3, n)
    plain = engine(pc, 3).trace_logits(prompt, list(range(1, 6)))
    monkeypatch.setattr(programs, "sparse_path", lambda rows, *shapes:
                        "paged" if rows == 1 else "masked")
    monkeypatch.setattr(
        programs, "paged_attention_over_picks", functools.partial(
            sa.paged_attention_over_picks, interpret=True))
    eng = engine(pc, 3)
    got = eng.trace_logits(prompt, list(range(1, 6)))
    np.testing.assert_allclose(got["logits"], plain["logits"], rtol=2e-4,
                               atol=2e-4)
    assert (np.asarray(got["picked"]) == np.asarray(plain["picked"])).all()
    assert np.asarray(got["picked"])[:, n:].any()       # decoded rows too
    assert eng.sparse_stats()["path"]["decode"] == "paged"


@pytest.mark.parametrize("shape,p", [((5, 1024), 0.3), ((3, 16384), 1.0),
                                     ((2, 128), 0.5), ((8, 2048), 0.0)])
def test_the_running_count_is_the_cumulative_sum(shape, p):
    marks = jax.random.bernoulli(jax.random.key(1), p, shape)
    np.testing.assert_array_equal(sa.running_count(marks),
                                  jnp.cumsum(marks, axis=1))
    # and the cut with it marks what the cut with `jnp.cumsum` marks, ties
    # at the cut included
    scores = jnp.round(jax.random.normal(jax.random.key(2), shape) * 2) / 2
    seen = jnp.arange(shape[1])[None] < shape[1] - 7
    np.testing.assert_array_equal(
        sa.select_mask(scores, seen, 40, count=sa.running_count),
        sa.select_mask(scores, seen, 40))
