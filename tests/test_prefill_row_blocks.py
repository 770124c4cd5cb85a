"""A prefill pays for its real rows (models/transformer.py: `row_blocks`,
`decoder_block`'s `length`): in a bucket of at least MIN_ROW_BLOCKS row
blocks the block's two row-wise halves run over the blocks that hold a real
row and leave zeros in the others.  Every real row's result is what the
unblocked form computes: the same products over the same rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import programs as E
from ray_tpu.llm.engine import LLMEngine, SamplingParams
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
        "suffix": jax.jit(lambda t, n: E._prefill_fn(
            params, t, n, CFG, row_block=row_block,
            cached=(pk, pv, pages, PREFIX, PAGE)))}


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
    eng = LLMEngine(CFG, max_batch=1, max_len=2048, page_size=64, seed=0)
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


# ---- a pattern of kinds (`run_pattern`) -----------------------------------
# Every kind once: M Mamba-2, C short convolution, * attention, F the
# feed-forward alone, E routed experts (latent, with a shared expert).  The
# stateful kinds' loop carries their state from block to block, the routed
# layer's grouped products stay one call over the bucket.

from ray_tpu.models import mamba2, routed, shortconv        # noqa: E402
from ray_tpu.ops.paged_attention import pool_shape          # noqa: E402

EVERY = RB                      # a checkpoint a block, as the engine's 512
PCFG = T.TransformerConfig(
    vocab_size=512, hidden_size=32, intermediate_size=48, num_layers=5,
    num_heads=4, num_kv_heads=2, head_dim=8, max_seq_len=2048,
    dtype=jnp.float32, pattern="MC*FE", qk_norm=True,
    mamba=mamba2.Mamba2Dims(num_heads=8, head_dim=4, state=8, groups=2,
                            conv_kernel=4, chunk=8),
    conv=shortconv.ShortConvDims(kernel=3, chunk=8),
    routed=routed.RoutedDims(experts=16, held=8, held_from=4, top_k=3,
                             latent=16, width=24, shared_width=40, scale=5.0))
PATTERN_LENGTHS = (RB, RB + 1, 3 * RB - 2, ROWS)


@pytest.fixture(scope="module")
def pattern():
    params = T.init_params(PCFG, jax.random.key(0))
    rng = np.random.default_rng(0)
    pool = [jnp.asarray(rng.standard_normal(pool_shape(
        PCFG.count("*"), 9, PAGE, PCFG.num_kv_heads, PCFG.head_dim_)),
        PCFG.dtype) for _ in range(2)]
    pages = jnp.asarray([3, 5, 1, 2, 4, 6], jnp.int32)
    tokens = rng.integers(1, PCFG.vocab_size, (1, ROWS)).astype(np.int32)
    # Checkpoint rows: 0 the state of having read nothing, 2 a state.
    ckpt = [jax.tree.map(lambda z: z.at[2].set(jnp.asarray(
        rng.standard_normal(z.shape[1:]), z.dtype)), T.zero_state(PCFG, k, 3))
        for k in PCFG.kinds if k in T.STATEFUL]
    return params, pool, pages, tokens, ckpt


def _pattern_forms(pattern, row_block, every=EVERY):
    """{form: jitted (tokens, length) -> `_state_prefill_fn`'s six}: a whole
    prompt from nothing, a suffix after PREFIX cached tokens from the state
    in checkpoint row 2."""
    params, pool, pages, _, ckpt = pattern

    def form(prefix, row):
        return jax.jit(lambda t, n: E._state_prefill_fn(
            params, *pool, pages, t, prefix, n, ckpt, row, PCFG, PAGE, every,
            row_block=row_block))
    return {"whole": form(0, 0), "suffix": form(PREFIX, 2)}


@pytest.fixture(scope="module")
def pattern_blocked(pattern):
    return _pattern_forms(pattern, RB)


@pytest.fixture(scope="module")
def pattern_unblocked(pattern):
    return _pattern_forms(pattern, ROWS)


@pytest.mark.parametrize("form", ["whole", "suffix"])
@pytest.mark.parametrize("length", PATTERN_LENGTHS)
def test_a_patterns_real_rows_are_the_unblocked_forms(
        form, length, pattern, pattern_blocked, pattern_unblocked):
    tokens = pattern[3]
    got = pattern_blocked[form](tokens, length)
    want = pattern_unblocked[form](tokens, length)
    ran = -(-length // RB) * RB
    _same(got[0], want[0])                              # last-token logits
    for g, w in zip(got[1:3], want[1:3]):               # ks, vs
        _same(g[:, :length], w[:, :length])
        assert not np.asarray(g[:, ran:]).any()
    # The state after `length` rows, every layer's; the checkpoints at the
    # boundaries the prompt reaches, and zeros at those no block ran to.
    jax.tree.map(_same, got[3], want[3])
    jax.tree.map(lambda g, w: _same(g[:, :length // EVERY],
                                    w[:, :length // EVERY]), got[4], want[4])
    for leaf in jax.tree.leaves(got[4]):
        assert not np.asarray(leaf[:, ran // EVERY:]).any()
    # The experts the real rows chose; a row nobody ran chose nothing.
    np.testing.assert_array_equal(got[5][:, :, :length], want[5][:, :, :length])
    assert not np.asarray(got[5][:, :, ran:]).any()
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree.leaves(got))
    if ran < ROWS:
        assert np.asarray(want[1][:, ran:]).any()


@pytest.mark.parametrize("form", ["whole", "suffix"])
@pytest.mark.parametrize("length", [RB + 1, 3 * RB - 2])
def test_a_patterns_padding_reaches_no_real_row(form, length, pattern,
                                                pattern_blocked):
    tokens = pattern[3]
    other = tokens.copy()
    other[0, length:] = (tokens[0, length:] + 7) % PCFG.vocab_size
    a = pattern_blocked[form](tokens, length)
    b = pattern_blocked[form](other, length)
    np.testing.assert_array_equal(a[0], b[0])
    for x, y in zip(a[1:3], b[1:3]):
        np.testing.assert_array_equal(x[:, :length], y[:, :length])
    jax.tree.map(np.testing.assert_array_equal, a[3], b[3])
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        x[:, :length // EVERY], y[:, :length // EVERY]), a[4], b[4])
    np.testing.assert_array_equal(a[5][:, :, :length], b[5][:, :, :length])
    ran = -(-length // RB) * RB
    assert (np.asarray(a[1][:, length:ran]) !=
            np.asarray(b[1][:, length:ran])).any()


def _plain_pattern(layers, x, cos, sin, attend, cfg, rec, per_layer=(),
                   length=None, live=None, every=0):
    """`run_pattern` as it was before any loop over rows: every half called
    plainly on all rows, each kind x + mixer(rms_norm(x))."""
    kept, new, ckpts, counts, chosen = [], [], [], [], []
    real = None
    if length is not None:
        real = jnp.broadcast_to(jnp.arange(x.shape[1]) < length, x.shape[:2])
    if live is not None:
        real = jnp.broadcast_to(live[:, None], x.shape[:2])
    eps = cfg.rms_norm_eps
    for kind, lp in zip(cfg.kinds, layers):
        if kind == "*":
            at = tuple(a[len(kept)] for a in per_layer)
            q, k, v = T.block_qkv(lp, x, cos, sin, cfg)
            o, k = attend(q, k, v, *at)
            x = T.attn_out(lp, x, o, cfg)
            kept.append(k)
        elif kind in T.STATEFUL:
            mixer, dims = (mamba2.mixer, cfg.mamba) if kind == "M" \
                else (shortconv.mixer, cfg.conv)
            y, state, ck = mixer(lp, T.rms_norm(x, lp["ln"], eps),
                                 rec[len(new)], dims, length=length,
                                 live=live, every=every)
            x = x + y
            new.append(state)
            ckpts.append(ck)
        elif kind == "F":
            x = T.ffn_block(lp, x, cfg)
        else:
            y, c, ch = routed.mixer(lp, T.rms_norm(x, lp["ln"], eps),
                                    cfg.routed, real)
            x = x + y
            counts.append(c)
            chosen.append(ch)
    kept = jax.tree.map(lambda *a: jnp.stack(a), *kept) \
        if kept[0] is not None else None
    return x, kept, new, ckpts, jnp.stack(counts), jnp.stack(chosen)


def _lowered(walk, args, **how):
    """The StableHLO of a walk over PCFG's layers (the same wrapper for
    both walks, so the text differs only where the program does)."""
    def program(layers, x, rec):
        cos, sin = T.rope_angles(jnp.arange(x.shape[1]), PCFG)
        return walk(layers, x, cos, sin,
                    lambda q, k, v: (T._xla_attention(q, k, v),
                                     (k[0], v[0])), PCFG, rec, **how)
    return jax.jit(program).lower(*args).as_text()


@pytest.mark.parametrize("case", ["no_length", "decode_step", "three_blocks",
                                  "every_splits_a_block"])
def test_a_pattern_without_row_blocks_lowers_as_before_the_loop(case, pattern):
    """A caller that gives no length, a decode step (one row a live slot),
    a bucket under four blocks and one whose checkpoints' spacing does not
    divide the block: the program is the halves called plainly."""
    layers = pattern[0]["layers"]
    kinds = [k for k in PCFG.kinds if k in T.STATEFUL]
    rows, batch, how = ROWS, 1, {}
    if case == "decode_step":
        rows, batch, how = 1, 4, {"live": jnp.arange(4) < 3}
    elif case == "three_blocks":
        rows, how = 3 * RB, {"length": 5, "every": EVERY}
    elif case == "every_splits_a_block":
        how = {"length": 5, "every": 24}
        assert not T.by_row_blocks(5, ROWS, RB, 24)
    args = (layers, jnp.zeros((batch, rows, PCFG.hidden_size), PCFG.dtype),
            [T.zero_state(PCFG, k, batch) for k in kinds])
    blocked = dict(how, row_block=RB)
    assert _lowered(T.run_pattern, args, **blocked) \
        == _lowered(_plain_pattern, args, **how)


def _primitives(jaxpr, in_loop=False):
    """[(primitive, whether inside a `while`)] of a jaxpr and all it calls."""
    found = []
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name, in_loop))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives(sub, in_loop or eqn.primitive.name == "while")
    return found


def test_a_pattern_by_row_blocks_loops_once_a_half(pattern):
    """By row blocks: one loop for M (around the scan's own), C and F, two
    for * (and the built scores' query blocks) and two for E, whose grouped
    products stay ONE call over the bucket, between its loops."""
    params, pool, pages, tokens, ckpt = pattern

    def primitives(row_block):
        return _primitives(jax.make_jaxpr(lambda t, n: E._state_prefill_fn(
            params, *pool, pages, t, 0, n, ckpt, 0, PCFG, PAGE, EVERY,
            row_block=row_block))(tokens, 3).jaxpr)
    plain, blocked = primitives(ROWS), primitives(RB)
    assert [p for p, _ in plain].count("while") == 0
    assert [p for p, _ in blocked].count("while") == 1 + 1 + 3 + 1 + 2
    for found in (plain, blocked):
        assert [inside for p, inside in found if p == "ragged_dot_general"] \
            == [False, False]
    # ... while the router's top-k and the shared expert go by blocks.
    assert [inside for p, inside in blocked if p == "top_k"] == [True]
    assert [inside for p, inside in plain if p == "top_k"] == [False]


def test_row_blocks_counts_a_patterns_checkpoint_spacing():
    assert T.row_blocks(2900, 4096, every=512) == (6, 8)
    assert T.row_blocks(2900, 4096, every=128) == (6, 8)
    assert T.row_blocks(2900, 4096, every=768) == (8, 8)    # splits a block
    assert T.row_blocks(600, 1024, every=512) == (2, 2)
    assert T.by_row_blocks(5, 64, 16, 16) and not T.by_row_blocks(5, 64, 16, 32)


def _pattern_engine():
    return LLMEngine(PCFG, max_batch=2, max_len=2048, page_size=32, seed=0,
                       prefix_cache=True)


def test_engine_counts_a_patterns_row_blocks_and_serves_the_same_tokens(
        monkeypatch):
    """At the real ROW_BLOCK (checkpoints every 4 x 8 = 32 tokens, which
    divide it): a 1,030-token prompt in a 2,048-row bucket runs 3 of its 4
    blocks, a re-ask's suffix under 2,048 rows all of its own; tokens and
    logits are those of an engine that runs every row."""
    rng = np.random.default_rng(1)
    doc = rng.integers(1, PCFG.vocab_size, 1030).tolist()
    ask = doc[:1024] + rng.integers(1, PCFG.vocab_size, 9).tolist()
    sp = SamplingParams(max_tokens=4)
    eng = _pattern_engine()
    assert eng._every == 32
    cold = eng.generate([doc], sp)[0]
    st = eng.prefill_stats()
    assert (st["row_blocks_run"], st["row_blocks_dense"]) == (3, 4)
    assert eng._prefill_ran["row_blocks"] == 3
    warm = eng.generate([ask], sp)[0]
    assert eng.prefix_cache_stats()["hits"] == 1
    st = eng.prefill_stats()            # the suffix: one block of 32 rows
    assert (st["row_blocks_run"], st["row_blocks_dense"]) == (3 + 1, 4 + 1)
    logits = eng._run_prefill(doc)[0]
    monkeypatch.setattr(T, "MIN_ROW_BLOCKS", 99)        # every row, always
    plain = _pattern_engine()
    assert plain.generate([doc], sp)[0] == cold
    assert plain.generate([ask], sp)[0] == warm
    st = plain.prefill_stats()
    assert st["row_blocks_run"] == st["row_blocks_dense"] == 4 + 1
    _same(logits, plain._run_prefill(doc)[0])


def test_a_patterns_debug_stats_and_span_carry_row_blocks(captured_recorder):
    import asyncio

    from ray_tpu.llm.serving import EngineReplica

    async def run():
        er = EngineReplica(PCFG, max_batch=1, max_len=2048, page_size=32,
                           seed=0)
        doc = list(range(1, 501)) * 3
        await er.generate(doc[:1100], {"max_tokens": 2})
        await er.generate(doc[:1088] + [7, 8, 9], {"max_tokens": 2})
        return await er.debug_stats()
    with captured_recorder() as rec:
        stats = asyncio.run(run())
        spans = [r["args"] for r in rec.rows()
                 if r["cat"] == "request" and r["name"] == "prefill"]
    # The miss runs 3 of its 4 blocks; the hit's suffix its own one.
    assert stats["prefill"]["row_blocks_run"] == 3 + 1
    assert stats["prefill"]["row_blocks_dense"] == 4 + 1
    assert [a["row_blocks"] for a in spans] == [3, 1]
    assert spans[1]["cached_tokens"] > 0
