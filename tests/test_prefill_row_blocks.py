"""A prefill pays for its real rows (models/transformer.py: `row_blocks`,
`decoder_block`'s `length`): in a bucket of at least MIN_ROW_BLOCKS row
blocks the block's two row-wise halves run over the blocks that hold a real
row and leave zeros in the others.  Every real row's result is what the
unblocked form computes: the same products over the same rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import engine as E
from ray_tpu.models import PRESETS
from ray_tpu.models import transformer as T

CFG = PRESETS["tiny"]
# A 64-row bucket in blocks of 16: four, as a 2,048-row bucket has of 512.
ROWS, RB, PAGE, PREFIX = 64, 16, 16, 32
LENGTHS = (1, RB - 1, RB, RB + 1, ROWS - 1, ROWS)


@pytest.fixture(scope="module")
def model():
    params = T.init_params(CFG, jax.random.key(0))
    rng = np.random.default_rng(0)
    pool = [jnp.asarray(rng.standard_normal(
        (CFG.num_layers, 9, PAGE, CFG.num_kv_heads, CFG.head_dim_)),
        CFG.dtype) for _ in range(2)]
    # The slot's page row: two cached pages (PREFIX tokens), then its own.
    pages = jnp.asarray([3, 5, 1, 2, 4, 6], jnp.int32)
    tokens = rng.integers(1, CFG.vocab_size, (1, ROWS)).astype(np.int32)
    return params, pool, pages, tokens


def _same(got, want):
    """Float32 throughout: the same products, summed in the order a product
    of that many rows takes on this backend."""
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _forms(model, row_block):
    """{form: jitted (tokens, length) -> (logits, ks, vs)} with the halves
    over blocks of `row_block` rows; ROWS = one block = the unblocked form."""
    params, (pk, pv), pages, _ = model
    return {
        "whole": jax.jit(lambda t, n: E._prefill_fn(
            params, t, n, CFG, row_block=row_block)),
        "suffix": jax.jit(lambda t, n: E._suffix_prefill_fn(
            params, pk, pv, pages, t, PREFIX, n, CFG, PAGE,
            row_block=row_block))}


@pytest.fixture(scope="module")
def blocked(model):
    return _forms(model, RB)


@pytest.fixture(scope="module")
def unblocked(model):
    return _forms(model, ROWS)


@pytest.mark.parametrize("form", ["whole", "suffix"])
@pytest.mark.parametrize("length", LENGTHS)
def test_real_rows_are_the_unblocked_forms(form, length, model, blocked,
                                           unblocked):
    tokens = model[3]
    logits, ks, vs = blocked[form](tokens, length)
    want_logits, want_ks, want_vs = unblocked[form](tokens, length)
    _same(logits, want_logits)
    _same(ks[:, :length], want_ks[:, :length])
    _same(vs[:, :length], want_vs[:, :length])
    # Rows past the last block that ran: zeros, where the unblocked form
    # leaves what it computed for the padding.
    ran = -(-length // RB) * RB
    assert np.isfinite(np.asarray(ks)).all() and np.isfinite(vs).all()
    assert not np.asarray(ks[:, ran:]).any() and not np.asarray(
        vs[:, ran:]).any()
    if ran < ROWS:
        assert np.asarray(want_ks[:, ran:]).any()


@pytest.mark.parametrize("form", ["whole", "suffix"])
@pytest.mark.parametrize("length", [RB + 1, 3 * RB - 2])
def test_padding_reaches_no_real_row(form, length, model, blocked):
    """Other token ids in the padded positions, those of the last block that
    runs among them, change no real row's result."""
    tokens = model[3]
    other = tokens.copy()
    other[0, length:] = (tokens[0, length:] + 7) % CFG.vocab_size
    a, b = blocked[form](tokens, length), blocked[form](other, length)
    np.testing.assert_array_equal(a[0], b[0])
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x[:, :length], y[:, :length])
    ran = -(-length // RB) * RB
    assert (np.asarray(a[1][:, length:ran]) !=
            np.asarray(b[1][:, length:ran])).any()


def test_row_blocks_counts_what_runs():
    assert T.ROW_BLOCK == 512 and T.MIN_ROW_BLOCKS == 4
    assert T.row_blocks(2200, 4096) == (5, 8)
    assert T.row_blocks(2560, 4096) == (5, 8)
    assert T.row_blocks(2561, 4096) == (6, 8)
    assert T.row_blocks(4096, 4096) == (8, 8)
    assert T.row_blocks(1, 2048) == (1, 4)
    # Under four whole blocks, and for a caller with no length: all rows.
    assert T.row_blocks(600, 1024) == (2, 2)
    assert T.row_blocks(5, 8) == (1, 1)
    assert T.row_blocks(None, 4096) == (8, 8)
    assert T.row_blocks(20, 64, 16) == (2, 4)
    assert T.row_blocks(20, 72, 16) == (4, 4)       # not whole blocks


def test_a_caller_without_length_gets_the_plain_scan(model):
    """`scan_blocks` with no length (the sequence-parallel prefills) and a
    bucket under four blocks with one lower to one loop, the scan's; by row
    blocks there are the two halves' loops inside it."""
    params, _, _, tokens = model

    def whiles(row_block, rows=ROWS):
        text = jax.jit(lambda t, n: E._prefill_fn(
            params, t, n, CFG, row_block=row_block)).lower(
                tokens[:, :rows], 3).as_text()
        return text.count("stablehlo.while")
    assert whiles(ROWS) == whiles(RB, 3 * RB) == whiles(32) == 1
    assert whiles(RB) == 3


def test_engine_counts_row_blocks_and_serves_the_same_tokens():
    """At the real ROW_BLOCK: a 1,030-token prompt in a 2,048-row bucket
    runs 3 of its 4 blocks; its logits are the unblocked form's; buckets
    under 2,048 rows run what they ran."""
    eng = E.LLMEngine(CFG, max_batch=1, max_len=2048, page_size=64, seed=0)
    rng = np.random.default_rng(1)
    long = rng.integers(1, CFG.vocab_size, 1030).tolist()
    logits, ks, _ = eng._run_prefill(long)
    st = eng.prefill_stats()
    assert (st["row_blocks_run"], st["row_blocks_dense"]) == (3, 4)
    assert eng._prefill_ran["row_blocks"] == 3
    toks = np.zeros((1, 2048), np.int32)
    toks[0, :1030] = long
    want = jax.jit(lambda t, n: E._prefill_fn(
        eng.params, t, n, CFG, row_block=2048))(toks, 1030)
    _same(logits, want[0])
    _same(ks[:, :1030], want[1][:, :1030])
    assert not np.asarray(ks[:, 1536:]).any()
    for n in (1024, 600, 20):               # buckets of 1,024 and under
        eng._run_prefill(long[:n])
    st = eng.prefill_stats()        # 2 of 2, 2 of 2, the one of 32 rows
    assert (st["row_blocks_run"], st["row_blocks_dense"]) == (3 + 5, 4 + 5)


def test_debug_stats_and_the_span_carry_row_blocks(captured_recorder):
    import asyncio

    from ray_tpu.llm.serving import EngineReplica

    async def run():
        er = EngineReplica("tiny", max_batch=1, max_len=2048, page_size=64,
                           seed=0)
        await er.generate(list(range(1, 1101)), {"max_tokens": 2})
        return await er.debug_stats()
    # (Not the process's own recorder, swapped bare: where an earlier test
    # of the worker left a cluster up, its telemetry flush drains that one
    # every second, and the compile below takes longer in a loaded run.)
    with captured_recorder() as rec:
        stats = asyncio.run(run())
        spans = [r["args"] for r in rec.rows()
                 if r["cat"] == "request" and r["name"] == "prefill"]
    assert stats["prefill"]["row_blocks_run"] == 3
    assert stats["prefill"]["row_blocks_dense"] == 4
    assert [a["row_blocks"] for a in spans] == [3]
