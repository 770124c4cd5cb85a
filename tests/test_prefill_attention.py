"""Prefill attention (ops/prefill_attention.py): the Pallas kernel run in
interpret mode on the CPU against a float32 softmax written here — whole
prompts and the suffix form over a paged prefix — then the host-side block
count and the chooser.  The same kernel compiled for a described v5e is in
tests/test_paged_attention.py, the one file that describes the topology
(on-chip-measurement guide, section 2)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import prefill_attention as pa

D = 128
BLOCK = 128         # the smallest block: a few blocks fit a CPU test
BF16_TOL = 2e-2     # outputs are O(1) averages of bf16 values rounded to bf16


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 128 rows, so that 256-384 rows already cross query blocks,
    key blocks and prefix chunks (at the real constant a block is 512)."""
    monkeypatch.setattr(pa, "_BLOCK", BLOCK)


def _qkv(rows, kv, groups, seed=0, dtype=jnp.bfloat16, dk=D):
    """q, k `dk` wide, v `D` wide."""
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (rows, h, d), jnp.float32).astype(dtype)
                 for k, h, d in zip(ks, (kv * groups, kv, kv), (dk, dk, D)))


def _pool(kv, page, n_pages, layers=1, seed=1, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.key(seed), 2)
    shape = (layers, n_pages, page, kv, D)
    return tuple(jax.random.normal(k, shape, jnp.float32).astype(dtype)
                 for k in ks)


def _oracle(q, k, v, length, pool_k=None, pool_v=None, pages=None,
            prefix_len=0):
    """softmax(q K^T / sqrt(Dk)) V in float32, row by row, head by head:
    row i sees the `prefix_len` tokens of its pages, then new keys 0..i."""
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    rows, H, dk = q.shape
    kv = k.shape[1]
    if prefix_len:
        pages = np.asarray(pages)
        ck = np.asarray(pool_k, np.float32)[pages].reshape(-1, kv, D)
        cv = np.asarray(pool_v, np.float32)[pages].reshape(-1, kv, D)
        k = np.concatenate([ck[:prefix_len], k])
        v = np.concatenate([cv[:prefix_len], v])
    out = np.zeros((length, H, D), np.float32)
    for i in range(length):
        for h in range(H):
            kh = k[:prefix_len + i + 1, h // (H // kv)]
            s = kh @ q[i, h] / math.sqrt(dk)
            p = np.exp(s - s.max())
            out[i, h] = (p / p.sum()) @ v[:prefix_len + i + 1, h // (H // kv)]
    return out


def _kernel(q, k, v, length, *paged):
    """The Pallas kernel itself, interpreted (on a TPU the engine's prefill
    bodies choose it by `prefill_path`)."""
    return np.asarray(pa._prefill_attention_pallas(
        q, k, v, length, *paged, scale=1 / math.sqrt(q.shape[-1]),
        interpret=True), np.float32)


# (query heads a KV head, the keys' width): a dense decoder's, alone and
# grouped, and a latent layer's expanded form, 192 over values of 128.
WIDTHS = [(1, D), (4, D), (1, 192)]


@pytest.mark.parametrize("groups,dk", WIDTHS)
@pytest.mark.parametrize("length", [256, 200, 1])
def test_whole_prompt_matches_float32(length, groups, dk):
    """`length` = the bucket, inside the second block, one token."""
    q, k, v = _qkv(256, 2, groups, dk=dk)
    got = _kernel(q, k, v, length)
    assert got.shape == (256, 2 * groups, D)
    assert np.isfinite(got).all()           # rows past `length` too
    assert np.abs(got[:length] - _oracle(q, k, v, length)).max() < BF16_TOL


def _table(n_pages, held, seed=0):
    """A page row of `held` shuffled pages out of 1..n_pages-1, then zeros
    (the scratch page), as the engine's `_tables` rows are."""
    rng = np.random.default_rng(seed)
    row = np.zeros(n_pages - 1, np.int32)
    row[:held] = rng.permutation(np.arange(1, n_pages))[:held]
    return row


@pytest.mark.parametrize("what", ["no_pages", "some_pages", "unaligned",
                                  "stacked", "float32", "one_kv_head"])
def test_suffix_cases(what):
    page, kv, groups, dtype, layer, layers = 16, 2, 4, jnp.bfloat16, 0, 1
    rows, length, prefix_len = 256, 180, 10 * page      # 160: two chunks
    if what == "no_pages":
        prefix_len = 0
    elif what == "unaligned":
        prefix_len = 10 * page - 5      # the kernel masks by token, not page
    elif what == "stacked":
        layer, layers = 1, 3            # the engine's form: the whole pool
    elif what == "float32":
        dtype = jnp.float32
    elif what == "one_kv_head":
        kv, groups = 1, 8
    q, k, v = _qkv(rows, kv, groups, dtype=dtype)
    pk, pv = _pool(kv, page, 33, layers, dtype=dtype)
    pages = _table(33, 16)
    if what == "stacked":
        keep = jnp.arange(layers)[:, None, None, None, None] == layer
        pk, pv = (jnp.where(keep, p, jnp.nan) for p in (pk, pv))
    got = _kernel(q, k, v, length, pk, pv, jnp.asarray(pages), prefix_len,
                  layer)
    want = _oracle(q, k, v, length, pk[layer], pv[layer], pages, prefix_len)
    assert np.isfinite(got).all()
    tol = 1e-4 if what == "float32" else BF16_TOL
    assert np.abs(got[:length] - want).max() < tol


def test_pages_shared_between_two_slots():
    """A prefix-cache hit: two slots' page rows start with the same pages
    and go on to their own; each suffix sees the shared prefix."""
    page, kv, groups = 16, 2, 4
    pk, pv = _pool(kv, page, 33)
    row_a, row_b = _table(33, 12, seed=1), _table(33, 12, seed=2)
    row_b[:6] = row_a[:6]
    for seed, row in ((3, row_a), (4, row_b)):
        q, k, v = _qkv(128, kv, groups, seed=seed)
        got = _kernel(q, k, v, 100, pk, pv, jnp.asarray(row), 6 * page, 0)
        want = _oracle(q, k, v, 100, pk[0], pv[0], row, 6 * page)
        assert np.abs(got[:100] - want).max() < BF16_TOL


def test_reads_live_pages_only():
    """Every page past `prefix_len` and the scratch page are NaN: the
    output is bit for bit what it was.  (The gather this replaces read the
    slot's whole row and masked it.)"""
    page, kv, groups, prefix_len = 16, 2, 4, 7 * 16
    q, k, v = _qkv(256, kv, groups)
    pk, pv = _pool(kv, page, 33)
    pages = _table(33, 12)
    clean = _kernel(q, k, v, 256, pk, pv, jnp.asarray(pages), prefix_len, 0)
    live = np.zeros(33, bool)
    live[pages[:prefix_len // page]] = True
    poison = lambda pool: jnp.where(
        jnp.asarray(live)[None, :, None, None, None], pool, jnp.nan
    ).astype(pool.dtype)
    dirty = _kernel(q, k, v, 256, poison(pk), poison(pv), jnp.asarray(pages),
                    prefix_len, 0)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("what", ["whole", "paged", "keys_192"])
def test_rows_past_length_do_not_reach_the_rows_below(what):
    """What q, k and v hold at and past `length` (the padding's rows) moves
    no row below it, and whatever comes back there is finite."""
    page, kv, groups, length = 16, 2, 4, 140
    q, k, v = _qkv(256, kv, groups, dk=192 if what == "keys_192" else D)
    extra = ()
    if what == "paged":
        pk, pv = _pool(kv, page, 33)
        extra = (pk, pv, jnp.asarray(_table(33, 8)), 5 * page, 0)
    clean = _kernel(q, k, v, length, *extra)
    past = (jnp.arange(256) >= length)[:, None, None]
    noisy = [jnp.where(past, 50 * a, a) for a in (q, k, v)]
    dirty = _kernel(*noisy, length, *extra)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty[:length], clean[:length])


def test_kv_blocks_counts_what_runs(monkeypatch):
    """The host-side count: 2.9k real tokens in a 4,096 bucket run 21 of
    the 64 block pairs; a suffix adds its prefix's chunks per live query
    block against the whole page row of the dense form."""
    monkeypatch.setattr(pa, "_BLOCK", 512)
    assert pa.kv_blocks(2900, 4096) == (21, 64)
    assert pa.kv_blocks(4096, 4096) == (36, 64)
    assert pa.kv_blocks(1, 4096) == (1, 64)
    assert pa.kv_blocks(600, 1024) == (3, 4)
    assert pa.kv_blocks(100, 128) == (1, 1)
    # 100 suffix rows after 3,520 cached tokens of a 4,096-token row
    assert pa.kv_blocks(100, 128, 3520, 4096) == (1 + 28, 1 * 33)
    assert pa.kv_blocks(560, 1024, 3536, 4096) == (3 + 2 * 7, 2 * 10)
    for args in ((2900, 4096), (37, 128, 16, 4096), (4096, 4096, 4080, 4096)):
        run, dense = pa.kv_blocks(*args)
        assert 0 < run <= dense


def test_chooser_adapts_to_platform_and_shape(monkeypatch):
    """On the CPU of these tests every shape takes the XLA form; on a TPU
    the kernel takes what it tiles from `MIN_ROWS` (`MIN_ROWS_PAGED` with a
    prefix in pages) up."""
    monkeypatch.setattr(pa, "_BLOCK", 512)
    bf16, cell = jnp.bfloat16, dict(page=16, table_len=256)
    assert pa.prefill_path((4096, 32, D), 8, bf16) == "xla"        # no TPU
    assert pa.kernel_tiles((4096, 32, D), 8, bf16)
    assert pa.kernel_tiles((128, 32, D), 8, bf16, **cell)
    assert pa.kernel_tiles((1024, 8, D), 1, bf16, **cell)
    assert pa.kernel_tiles((256, 9, D), 3, jnp.float32, page=16)
    assert not pa.kernel_tiles((64, 32, D), 8, bf16)       # under 128 lanes
    assert not pa.kernel_tiles((3000, 32, D), 8, bf16)     # not whole blocks
    assert not pa.kernel_tiles((256, 8, 16), 4, jnp.float32)        # `tiny`
    assert not pa.kernel_tiles((256, 9, D), 3, bf16, page=16)   # odd stride
    assert not pa.kernel_tiles((256, 32, D), 8, bf16, page=16,
                               table_len=1 << 20)           # scalar memory
    monkeypatch.setattr(jax, "devices", lambda *a: [
        type("Dev", (), {"platform": "tpu"})()])
    assert pa.prefill_path((4096, 32, D), 8, bf16) == "kernel"
    assert pa.prefill_path((pa.MIN_ROWS // 2, 32, D), 8, bf16) == "xla"
    assert pa.prefill_path((128, 32, D), 8, bf16, **cell) == "kernel"
    assert pa.prefill_path((64, 32, D), 8, bf16, **cell) == "xla"
    assert pa.prefill_path((4096, 8, 16), 4, jnp.float32) == "xla"
    # keys of 192 over values of 128 (a latent layer expanded): unpaged
    # alone, since a pool holds keys and values of one width
    wide = (8192, 16, 192)
    assert pa.kernel_tiles(wide, 16, bf16, value=D)
    assert pa.prefill_path(wide, 16, bf16, value=D) == "kernel"
    assert not pa.kernel_tiles(wide, 16, bf16, value=D, **cell)
    assert pa.prefill_path(wide, 16, bf16, value=D, **cell) == "xla"
    assert not pa.kernel_tiles(wide, 16, bf16)          # values of 192
    assert not pa.kernel_tiles((8192, 16, D), 16, bf16, value=64)
    assert pa.kernel_tiles((4096, 32, D), 8, bf16, value=D)
    assert pa.prefill_path((128, 32, D), 8, bf16, value=D, **cell) == "kernel"
