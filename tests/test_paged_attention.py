"""Paged decode attention (ops/paged_attention.py): the Pallas kernel run in
interpret mode on the CPU, against the module's plain function, against a
float32 softmax written here; then the kernel and the engine's decode step
compiled for a described v5e (no chip: the TPU's compiler is installed)."""

import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa

D = 128
BF16_TOL = 2e-2     # outputs are O(1) averages of bf16 values rounded to bf16


class _Tpu:
    """What `decode_path` asks of a device, answering as a chip would."""
    platform = "tpu"


def as_on_a_tpu(fn):
    """`fn` (the chooser) answering as it would in a process whose devices
    are TPUs."""
    @functools.wraps(fn)
    def asked(*a, **k):
        with mock.patch.object(jax, "devices", lambda *b: [_Tpu]):
            return fn(*a, **k)
    return asked


def _case(groups, page, lengths, *, kv=2, n_slots_pages=6, seed=0,
          shared=(), dtype=jnp.bfloat16, d=D):
    """q, pools, tables, lengths for `len(lengths)` slots of P pages each,
    the pools' rows as `pool_row` has them for `kv` heads of `d`.
    `shared`: pairs (a, b, n): slot b's first n pages are slot a's."""
    B, P = len(lengths), n_slots_pages
    H = kv * groups
    N = 1 + B * P
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, H, d), jnp.float32).astype(dtype)
    pk, pv = (pa.pool_rows(jax.random.normal(
        key, (N, page, kv, d), jnp.float32).astype(dtype), kv, d)
        for key in ks[1:])
    rng = np.random.default_rng(seed)
    tables = rng.permutation(np.arange(1, N)).reshape(B, P).astype(np.int32)
    for a, b, n in shared:
        tables[b, :n] = tables[a, :n]
    return q, pk, pv, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def _oracle(q, pk, pv, tables, lengths, scale=None, seen=None):
    """softmax(q K^T / sqrt(D)) V (or x `scale`) in float32, slot by slot,
    head by head, over positions 0..length of the slot's own pages (of
    them, those `seen` (B, T) marks)."""
    q, pk, pv = (np.asarray(a, np.float32) for a in (q, pk, pv))
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    B, H, D = q.shape
    kv = pk[0, 0].size // D         # a row is (KV, D), or KV * D lanes
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        n = lengths[b] + 1
        at = slice(None) if seen is None else np.asarray(seen)[b, :n]
        k = pk[tables[b]].reshape(-1, kv, D)[:n][at]
        v = pv[tables[b]].reshape(-1, kv, D)[:n][at]
        for h in range(H):
            s = k[:, h // (H // kv)] @ q[b, h] * (scale or 1 / math.sqrt(D))
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[:, h // (H // kv)]
    return out


def _kernel(q, pk, pv, tables, lengths, layer=None, seen=None):
    """The Pallas kernel itself, interpreted (on a TPU the public function
    would choose it)."""
    if layer is None:
        pk, pv, layer = pk[None], pv[None], 0
    return pa._paged_decode_pallas(q, pk, pv, tables, lengths, layer,
                                   1 / math.sqrt(D), interpret=True,
                                   seen=seen)


def _lanes_kernel(q, pk, pv, tables, lengths, layer=None, scale=None,
                  seen=None):
    """The kernel over a pool whose rows are lanes, interpreted."""
    if layer is None:
        pk, pv, layer = pk[None], pv[None], 0
    return pa._paged_lanes_pallas(
        q, pk, pv, tables, lengths, layer,
        scale or 1 / math.sqrt(q.shape[-1]), interpret=True, seen=seen)


def _marks(tables, lengths, page, seed=0):
    """(B, T) bool: about a third of each slot's positions up to its length,
    the slot's FIRST chunk of two pages left wholly unmarked where it has a
    later one (the kernel's running maximum then starts on a chunk that
    holds nothing it attends), marks past the length set (they are not
    attended) and at least one position a slot."""
    B, T = tables.shape[0], tables.shape[1] * page
    seen = np.random.default_rng(seed).random((B, T)) < 0.33
    for b, n in enumerate(np.asarray(lengths)):
        if n >= 2 * page:
            seen[b, :2 * page] = False
        seen[b, n] = True
        seen[b, n + 1:] = True
    return jnp.asarray(seen)


def _lengths(page, pages):
    """0, page - 1, page, several pages, the table's last position."""
    return [0, page - 1, page, 3 * page + 5, pages * page - 1]


@pytest.fixture
def small_chunks(monkeypatch):
    """Two pages a chunk, so a few pages already cross chunk boundaries
    (at the real constant a chunk is 16 pages of the cells' shape)."""
    def set_for(page, kv):
        monkeypatch.setattr(pa, "_CHUNK_ROWS", 2 * page * kv)
    return set_for


@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("groups", [1, 4, 8])
def test_kernel_matches_reference_and_float32(groups, page, small_chunks):
    small_chunks(page, 2)
    args = _case(groups, page, _lengths(page, 6))
    got = np.asarray(_kernel(*args), np.float32)
    ref = np.asarray(pa.reference_paged_attention(*args), np.float32)
    want = _oracle(*args)
    assert np.abs(got - want).max() < BF16_TOL
    assert np.abs(ref - want).max() < BF16_TOL
    assert np.abs(got - ref).max() < BF16_TOL


@pytest.mark.parametrize("what", ["inactive", "shared", "stacked", "real_chunk",
                                  "float32", "seen", "seen_stacked",
                                  "seen_lanes"])
def test_kernel_cases(what, small_chunks, small_lane_chunks):
    page, kv, kwargs, layer = 16, 2, {}, None
    lengths = _lengths(page, 6)
    if what.startswith("seen"):
        # An attention that SELECTS (ops/sparse_attention.py): of a slot's
        # positions up to its length the kernel attends those marked.
        return _seen_case(what, page, lengths, small_chunks)
    if what == "inactive":
        # As the engine hands them over: table row 0 (the scratch page),
        # length 0.  They cost one page and give a finite row.
        lengths = [0, 40, 0, 17]
    elif what == "shared":
        # A prefix-cache hit: slots 1 and 2 read slot 0's first 3 pages.
        kwargs["shared"] = [(0, 1, 3), (0, 2, 3)]
        lengths = [70, 3 * page, 3 * page + 20]
    elif what == "real_chunk":
        kv = 8                    # the cells' page: 16 x 8 rows, 16 a chunk
        kwargs["n_slots_pages"] = 20
        lengths = [0, 15 * page + 3, 16 * page, 20 * page - 1]
    elif what == "float32":
        kwargs["dtype"] = jnp.float32
    if what != "real_chunk":
        small_chunks(page, kv)
    q, pk, pv, tables, lens = _case(4, page, lengths, kv=kv, **kwargs)
    if what == "inactive":
        tables = tables.at[0].set(0).at[2].set(0)
    want = _oracle(q, pk, pv, tables, lens)
    if what == "stacked":
        # The engine's form: the whole (L, N, page, KV, D) pool and a
        # traced layer index, so that no layer is ever sliced out.
        other = jnp.full_like(pk, jnp.nan)
        pk, pv, layer = (jnp.stack([other, pk, other]),
                         jnp.stack([other, pv, other]), jnp.int32(1))
    got = np.asarray(jax.jit(_kernel)(q, pk, pv, tables, lens, layer),
                     np.float32)
    ref = np.asarray(pa.reference_paged_attention(
        q, pk, pv, tables, lens, layer), np.float32)
    tol = 1e-4 if what == "float32" else BF16_TOL
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < tol
    assert np.abs(ref - want).max() < tol


def _seen_case(what, page, lengths, small_chunks):
    kv = 4
    small_chunks(page, kv)
    lanes = what == "seen_lanes"
    q, pk, pv, tables, lens = _case(2, page, lengths, kv=kv,
                                    d=64 if lanes else D)
    seen = _marks(tables, lens, page)
    scale = 1 / math.sqrt(q.shape[-1])
    want = _oracle(q, pk, pv, tables, lens, scale, seen)
    layer = None
    if what == "seen_stacked":
        other = jnp.full_like(pk, jnp.nan)
        pk, pv, layer = (jnp.stack([other, pk, other]),
                         jnp.stack([other, pv, other]), jnp.int32(1))
    fn = _lanes_kernel if lanes else _kernel
    got = np.asarray(jax.jit(lambda *a: fn(*a, layer=layer, seen=seen))(
        q, pk, pv, tables, lens), np.float32)
    ref = np.asarray(pa.reference_paged_attention(
        q, pk, pv, tables, lens, layer, seen=seen), np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < BF16_TOL
    assert np.abs(ref - want).max() < BF16_TOL
    # and it is not the attention over every row
    assert np.abs(got - _oracle(q, pk[1] if layer is not None else pk,
                                pv[1] if layer is not None else pv,
                                tables, lens, scale)).max() > 10 * BF16_TOL


@pytest.mark.parametrize("tokens,kv,T", [(32, 4, 96), (32, 4, 100),
                                         (512, 4, 16384), (16, 1, 48)])
def test_chunk_marks_repeat_a_tokens_mark_for_its_kv_heads(tokens, kv, T):
    """A row a chunk of a slot's table, filled with zeros to whole chunks,
    each token's mark once for each of its KV heads' rows."""
    seen = jax.random.bernoulli(jax.random.key(0), 0.3, (3, T))
    got = np.asarray(pa._chunk_marks(seen, tokens, kv))
    fill = -T % tokens
    want = np.repeat(np.pad(np.asarray(seen), ((0, 0), (0, fill))), kv, 1)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(
        got, want.reshape(3 * (T + fill) // tokens, tokens * kv))


@pytest.fixture
def small_lane_chunks(monkeypatch):
    """Two pages a chunk of a lanes pool (the rule gives the cells' rows
    of 512 lanes 16)."""
    monkeypatch.setattr(pa, "_lanes_chunk_pages", lambda page, width: 2)


@pytest.mark.parametrize("impl", ["kernel", "reference", "lanes",
                                  "lanes_kernel", "kernel_seen",
                                  "reference_seen"])
def test_reads_live_pages_only(impl, small_chunks, small_lane_chunks):
    """Every page no slot holds is NaN, and the rows of a slot's last page
    past its length are huge: the output is what it was before.  (The old
    gather multiplied dead values by zero and could not pass this.)"""
    page, kv = 16, 2
    small_chunks(page, kv)
    lengths = [0, page - 1, page, 3 * page + 5, 6 * page - 1]
    q, pk, pv, tables, lens = _case(4, page, lengths, kv=kv,
                                    d=64 if impl.startswith("lanes") else D)
    fn = {"kernel": _kernel, "lanes_kernel": _lanes_kernel,
          "kernel_seen": _kernel}.get(impl, pa.reference_paged_attention)
    if impl.endswith("_seen"):
        fn = functools.partial(fn, seen=_marks(tables, lens, page))
    clean = np.asarray(fn(q, pk, pv, tables, lens), np.float32)
    held = np.zeros(pk.shape[0], bool)
    tail = np.zeros(pk.shape[:2], bool)
    for b, n in enumerate(lengths):
        last = n // page
        held[np.asarray(tables)[b, :last + 1]] = True
        tail[int(tables[b, last]), n % page + 1:] = True
    over = lambda mask, pool: jnp.asarray(mask).reshape(
        mask.shape + (1,) * (pool.ndim - mask.ndim))
    poison = lambda pool: jnp.where(
        over(tail, pool), 3e38, jnp.where(over(held, pool), pool, jnp.nan)
    ).astype(pool.dtype)
    dirty = np.asarray(fn(q, poison(pk), poison(pv), tables, lens),
                       np.float32)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


def test_chooser_adapts_to_platform_and_shape():
    """On the CPU of these tests every shape takes the plain function; on a
    TPU the kernel takes what it can tile."""
    pool, tables = (4, 9, 16, 8, 128), (16, 128)
    assert pa.decode_path((32, 128), pool, tables) == "reference"  # no TPU
    assert pa.kernel_tiles((32, 128), pool, tables)
    assert pa.kernel_tiles((16, 128), (9, 64, 16, 128), (4, 4))
    assert not pa.kernel_tiles((8, 16), (9, 16, 4, 16), tables)     # `tiny`
    assert not pa.kernel_tiles((32, 128), (9, 8, 8, 128), tables)   # page 8
    assert not pa.kernel_tiles((32, 128), pool, (256, 2048))  # scalar memory
    args = _case(4, 16, [5, 40])
    np.testing.assert_array_equal(
        np.asarray(pa.paged_decode_attention(*args), np.float32),
        np.asarray(pa.reference_paged_attention(*args), np.float32))


# ---- heads narrower than a lane row: the pool's row (`pool_row`) ----------

def test_the_pools_row_follows_the_head_width():
    from ray_tpu.models.transformer import PRESETS
    tiny = PRESETS["tiny"]
    assert pa.pool_row(8, 128) == pa.pool_row(2, 128) == "heads"
    assert pa.pool_row(8, 256) == "heads"
    assert pa.pool_row(tiny.num_kv_heads, tiny.head_dim_) == "heads"  # 4 x 16
    assert pa.pool_row(1, 64) == pa.pool_row(3, 64) == "heads"
    assert pa.pool_row(8, 64) == pa.pool_row(2, 64) == "lanes"
    assert pa.pool_shape(2, 3073, 16, 8, 64) == (2, 3073, 16, 512)
    assert pa.pool_shape(2, 3073, 16, 8, 128) == (2, 3073, 16, 8, 128)
    assert pa.pool_shape(2, 9, 16, 4, 16) == (2, 9, 16, 4, 16)
    x = jnp.arange(3 * 5 * 2 * 64).reshape(3, 5, 2, 64)
    rows = pa.pool_rows(x, 2, 64)
    assert rows.shape == (3, 5, 128)
    np.testing.assert_array_equal(rows[1, 2, 64:], x[1, 2, 1])  # KV major
    np.testing.assert_array_equal(pa.head_rows(rows, 2, 64), x)
    wide = jnp.zeros((3, 5, 2, 128))
    assert pa.pool_rows(wide, 2, 128) is wide
    assert pa.head_rows(wide, 2, 128) is wide
    # the kernel takes rows of whole lane rows as ONE head as wide as the
    # row (LFM2's and Granite's cells, stacked or one layer's); the chooser
    # reads no pool row as heads
    assert pa.kernel_tiles((32, 64), (2, 3073, 16, 512), (8, 256))
    assert pa.kernel_tiles((32, 64), (4, 4097, 16, 512), (32, 128))
    assert pa.kernel_tiles((4, 64), (9, 64, 128), (4, 4))
    assert pa.kernel_tiles((12, 96), (9, 16, 384), (4, 4))      # 4 x 96
    assert pa.decode_path((32, 64), (2, 3073, 16, 512), (8, 256)) \
        == "reference"                                          # no TPU
    on_chip = as_on_a_tpu(pa.decode_path)
    assert on_chip((32, 64), (2, 3073, 16, 512), (8, 256)) \
        == on_chip((32, 64), (4, 4097, 16, 512), (32, 128)) == "pallas"
    assert on_chip((3, 64), (9, 16, 3, 64), (4, 4)) == "reference"
    # and what still does not tile: rows of 64 or 192 lanes (held by heads,
    # `pool_row`), a page of 8 rows, heads that do not group, a table over
    # scalar memory
    assert not pa.kernel_tiles((4, 64), (9, 16, 1, 64), (4, 4))
    assert not pa.kernel_tiles((6, 64), (9, 16, 3, 64), (4, 4))
    assert not pa.kernel_tiles((6, 64), (2, 9, 16, 192), (4, 4))
    assert not pa.kernel_tiles((32, 64), (2, 3073, 8, 512), (8, 256))
    assert not pa.kernel_tiles((12, 64), (2, 3073, 16, 512), (8, 256))
    assert not pa.kernel_tiles((32, 64), (2, 3073, 16, 512), (256, 2048))
    # the third form: a latent layer's ONE row a token (512 + 64 values),
    # one KV head that is no whole number of lane rows, padded to them
    assert pa.pool_row(1, 576) == pa.pool_row(1, 160) == "latent"
    assert pa.pool_row(1, 512) == pa.pool_row(2, 576) == "heads"
    assert pa.pool_shape(9, 12289, 16, 1, 576) == (9, 12289, 16, 640)
    assert pa.pool_shape(3, 9, 16, 1, 160) == (3, 9, 16, 256)
    y = jnp.arange(1, 1 + 3 * 5 * 160).reshape(3, 5, 1, 160)
    rows = pa.pool_rows(y, 1, 160)
    assert rows.shape == (3, 5, 256) and not rows[..., 160:].any()
    np.testing.assert_array_equal(pa.head_rows(rows, 1, 160), y)
    assert pa.latent_kernel_tiles((9, 12289, 16, 640), (16, 512), 512)
    assert not pa.latent_kernel_tiles((3, 9, 16, 256), (4, 8), 144)
    assert pa.decode_path((16, 192), (9, 12289, 16, 640), (16, 512), 512) \
        == "reference"                                          # no TPU


@pytest.mark.parametrize("impl", ["chosen", "kernel"])
@pytest.mark.parametrize("page", [16, 64])
@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("kv", [2, 8])
def test_narrow_heads_match_float32(kv, groups, page, impl,
                                    small_lane_chunks):
    """D 64: the pool's rows are lanes, and the plain function (what the
    chooser takes on the CPU) and the kernel (interpreted) read them as they
    lie (lengths 0, page - 1, page, several pages, the last)."""
    args = _case(groups, page, _lengths(page, 6), kv=kv, d=64)
    assert args[1].shape == (31, page, kv * 64)
    fn = pa.paged_decode_attention if impl == "chosen" else _lanes_kernel
    got = np.asarray(fn(*args), np.float32)
    assert np.abs(got - _oracle(*args)).max() < BF16_TOL
    # and against the same keys held by heads, which take the other form
    heads = [pa.head_rows(pool, kv, 64) for pool in args[1:3]]
    assert heads[0].shape == (31, page, kv, 64)
    split = pa.reference_paged_attention(args[0], *heads, *args[3:])
    assert np.abs(got - np.asarray(split, np.float32)).max() < BF16_TOL


@pytest.mark.parametrize("impl", ["chosen", "kernel"])
@pytest.mark.parametrize("what", ["inactive", "shared", "stacked", "float32",
                                  "scale", "real_chunk"])
def test_narrow_head_cases(what, impl, monkeypatch):
    page, kv, kwargs, layer, scale = 16, 8, {}, None, None
    lengths = _lengths(page, 6)
    if what == "inactive":
        lengths = [0, 40, 0, 17]
    elif what == "shared":
        kwargs["shared"] = [(0, 1, 3), (0, 2, 3)]
        lengths = [70, 3 * page, 3 * page + 20]
    elif what == "float32":
        kwargs["dtype"] = jnp.float32
    elif what == "scale":
        scale = 1 / 64                      # Granite's: the caller's, not
    elif what == "real_chunk":              # 1 / sqrt(D)
        # the cells' row of 512 lanes at the rule's own chunk: slots that
        # end inside a chunk, on its last row, and two chunks on
        chunk = pa._lanes_chunk_pages(page, kv * 64)
        assert chunk == 16
        kwargs["n_slots_pages"] = 2 * chunk + 4
        lengths = [0, (chunk - 1) * page + 3, chunk * page - 1, chunk * page,
                   (2 * chunk + 4) * page - 1]
    if what != "real_chunk":
        monkeypatch.setattr(pa, "_lanes_chunk_pages", lambda page, width: 2)
    q, pk, pv, tables, lens = _case(4, page, lengths, kv=kv, d=64, **kwargs)
    if what == "inactive":
        tables = tables.at[0].set(0).at[2].set(0)
    want = _oracle(q, pk, pv, tables, lens, scale)
    if what == "stacked":
        # The engine's form: (L, N, page, KV * D) and a traced layer index.
        other = jnp.full_like(pk, jnp.nan)
        pk, pv, layer = (jnp.stack([other, pk, other]),
                         jnp.stack([other, pv, other]), jnp.int32(1))
    fn = pa.paged_decode_attention if impl == "chosen" else _lanes_kernel
    got = np.asarray(jax.jit(functools.partial(fn, scale=scale))(
        q, pk, pv, tables, lens, layer), np.float32)
    assert np.isfinite(got).all() and got.shape == q.shape
    assert np.abs(got - want).max() < (1e-4 if what == "float32"
                                       else BF16_TOL)


@pytest.mark.parametrize("kv,d", [(8, 64), (2, 64), (2, 128), (4, 16),
                                  (1, 576), (1, 160)])
def test_install_then_read_gives_back_the_rows(kv, d):
    """`_install_fn` writes whole pages of rows as the pool holds them; read
    through the slot's page row they are the rows installed, one by one.  A
    latent row (one head of 576 or 160) has ONE pool and None beside it."""
    from ray_tpu.llm import programs as E
    L, page, P_, Sb = 2, 16, 4, 40
    latent = pa.pool_row(kv, d) == "latent"
    shape = pa.pool_shape(L, 9, page, kv, d)
    assert len(shape) == (4 if d == 64 or latent else 5)
    ks, vs = (jax.random.normal(jax.random.key(i), (L, Sb, kv, d),
                                jnp.float32).astype(jnp.bfloat16)
              for i in range(2))
    pages = jnp.asarray([5, 2, 7, 0], jnp.int32)    # 3 reserved, then scratch
    empty = jnp.full(shape, jnp.nan, jnp.bfloat16)
    if latent:
        vs = None
    pk, pv = jax.jit(lambda *a: E._install_fn(*a, page, None))(
        empty, None if latent else empty, ks, vs, pages)
    assert pk.shape == shape and (pv is None) == latent
    for pool, rows in ((pk, ks), (pv, vs))[:1 if latent else 2]:
        back = pa.head_rows(pool[:, pages[:3]], kv, d).reshape(L, -1, kv, d)
        np.testing.assert_array_equal(np.asarray(back[:, :Sb], np.float32),
                                      np.asarray(rows, np.float32))
        untouched = np.asarray(pool[:, jnp.asarray([1, 3, 4, 6, 8])],
                               np.float32)
        assert np.isnan(untouched).all()
    # one token a slot, as the decode step writes it, read back by attention:
    # a slot of one token attends to that token alone
    q = jnp.ones((1, kv, d), jnp.bfloat16)
    if latent:
        o = pa.paged_latent_attention(
            jnp.ones((1, 4, d), jnp.bfloat16), pk, pages[None, :1],
            jnp.zeros((1,), jnp.int32), jnp.int32(1), scale=0.1,
            value_lanes=d - 16)
        want = jnp.broadcast_to(ks[1, 0, 0, :d - 16], (4, d - 16))
    else:
        o = pa.paged_decode_attention(q, pk, pv, pages[None, :1],
                                      jnp.zeros((1,), jnp.int32), jnp.int32(1))
        want = vs[1, 0]
    np.testing.assert_array_equal(np.asarray(o[0], np.float32),
                                  np.asarray(want, np.float32))


# ---- a latent pool: one row a token, key and value at once ----------------

def _latent_case(lengths, *, page=16, width=576, n_slots_pages=6, seed=0,
                 heads=16, shared=(), dtype=jnp.bfloat16):
    """Wide queries, ONE pool of padded rows, tables, lengths."""
    q, pool, _, tables, lens = _case(heads, page, lengths, kv=1,
                                     n_slots_pages=n_slots_pages, seed=seed,
                                     shared=shared, dtype=dtype, d=width)
    return q, pool, tables, lens


def _latent_oracle(q, pool, tables, lengths, scale, lanes):
    q, pool = np.asarray(q, np.float32), np.asarray(pool, np.float32)
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    out = np.zeros((*q.shape[:2], lanes), np.float32)
    for b in range(q.shape[0]):
        rows = pool[tables[b]].reshape(-1, pool.shape[-1])[:lengths[b] + 1]
        for h in range(q.shape[1]):
            s = rows[:, :q.shape[2]] @ q[b, h] * scale
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ rows[:, :lanes]
    return out


@pytest.mark.parametrize("what", ["page16", "page64", "inactive", "shared",
                                  "stacked", "narrow", "float32"])
def test_latent_kernel_cases(what, monkeypatch):
    """The kernel over a latent pool (interpreted), the plain function and a
    float32 softmax written here: every head of a slot against each row
    once, the row's first lanes its value."""
    page, width, lanes, kwargs, layer = 16, 576, 512, {}, None
    lengths, scale = _lengths(16, 6), 1 / math.sqrt(192)
    if what == "page64":
        page, lengths = 64, _lengths(64, 6)
    elif what == "inactive":
        lengths = [0, 40, 0, 17]
    elif what == "shared":
        kwargs["shared"] = [(0, 1, 3), (0, 2, 3)]
        lengths = [70, 3 * page, 3 * page + 20]
    elif what == "narrow":
        width, lanes = 160, 128     # a rehearsal's row with whole-lane values
        kwargs["heads"] = 8
    elif what == "float32":
        kwargs["dtype"] = jnp.float32
    monkeypatch.setattr(pa, "_CHUNK_ROWS", 4 * page)    # two pages a chunk
    q, pool, tables, lens = _latent_case(lengths, page=page, width=width,
                                         **kwargs)
    assert pool.shape[-1] % 128 == 0 and pool.ndim == 3
    if what == "inactive":
        tables = tables.at[0].set(0).at[2].set(0)
    want = _latent_oracle(q, pool, tables, lens, scale, lanes)
    if what == "stacked":
        other = jnp.full_like(pool, jnp.nan)
        pool, layer = jnp.stack([other, pool, other]), jnp.int32(1)
    ref = np.asarray(jax.jit(
        lambda *a: pa.paged_latent_attention(*a, scale=scale,
                                             value_lanes=lanes))(
        q, pool, tables, lens, layer), np.float32)
    got = np.asarray(jax.jit(
        lambda q, pool, tb, ln, li: pa._paged_latent_pallas(
            q, pool if li is not None else pool[None], tb, ln,
            0 if li is None else li, scale, lanes, interpret=True))(
        q, pool, tables, lens, layer), np.float32)
    tol = 1e-4 if what == "float32" else BF16_TOL
    assert np.isfinite(got).all() and got.shape == (*q.shape[:2], lanes)
    assert np.abs(got - want).max() < tol
    assert np.abs(ref - want).max() < tol


# ---- compiled for the chip, without the chip -----------------------------
# The topology is described inside a fixture of THIS file only: the process
# that does so holds the TPU library until it exits (on-chip-measurement
# guide, section 2).

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape", [
    # B, P, page, KV, groups, dtype: serve_chat, serve_doc_reask, the
    # engine's defaults with the `1b` preset's heads, float32
    (16, 128, 16, 8, 4, jnp.bfloat16),
    (8, 256, 16, 8, 4, jnp.bfloat16),
    (4, 4, 64, 16, 1, jnp.bfloat16),
    (4, 4, 64, 8, 4, jnp.float32),
])
def test_kernel_compiles_for_v5e(shape, topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    B, P, page, KV, groups, dtype = shape
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    L, N = 3, 1 + B * P
    compiled = jax.jit(
        lambda q, pk, pv, tb, ln, li: pa._paged_decode_pallas(
            q, pk, pv, tb, ln, li, 1 / math.sqrt(D))
    ).lower(S((B, KV * groups, D), dtype), S((L, N, page, KV, D), dtype),
            S((L, N, page, KV, D), dtype), S((B, P), jnp.int32),
            S((B,), jnp.int32), S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    # The pool reaches the kernel as it lies in HBM: no copy of it, no slice.
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("shape", [
    # B, P, N, attention layers, scale: serve_chat_ssm (Granite: 32 / 8
    # heads of 64), serve_doc_reask_moe (LFM2: the same heads)
    (32, 128, 4097, 4, 1 / 64),
    (8, 256, 3073, 2, 1 / 8),
])
def test_lanes_kernel_compiles_for_v5e(shape, topo, monkeypatch,
                                       no_compile_cache):
    """`paged_decode_attention` as a chip's chooser takes it, over a pool
    whose rows are 512 lanes: the kernel, both pools where they lie (no
    gather of a slot's table, no copy, no slice), the widened queries and
    the kept lanes the only things beside it."""
    import re

    from jax.sharding import SingleDeviceSharding
    B, P, N, L, scale = shape
    monkeypatch.setattr(pa, "decode_path", as_on_a_tpu(pa.decode_path))
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    pool = S(pa.pool_shape(L, N, 16, 8, 64), jnp.bfloat16)
    assert pool.shape == (L, N, 16, 512)
    compiled = jax.jit(functools.partial(pa.paged_decode_attention,
                                         scale=scale)).lower(
        S((B, 32, 64), jnp.bfloat16), pool, pool, S((B, P), jnp.int32),
        S((B,), jnp.int32), S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    assert "gather" not in text
    assert not re.search(rf"bf16\[({B},{P * 16}|{B * P},16),512\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _cell(topo, mesh_axes, page=16):
    """The serving cells' widths (two layers) placed on one described chip
    or a two-chip `mesh_axes` mesh: cfg, kv_sharding, a ShapeDtypeStruct
    maker, the params' shapes and the pool's."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from ray_tpu.models.transformer import (TransformerConfig, init_params,
                                            param_logical_axes)
    from ray_tpu.parallel.sharding import LogicalAxisRules, tree_shardings

    cfg = TransformerConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_layers=2, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, rope_theta=1e6)
    if mesh_axes is None:
        rep = SingleDeviceSharding(topo.devices[0])
        kv_shd, par_shd, pool_shd = None, rep, rep
    else:
        (axis, n), = mesh_axes.items()
        mesh = Mesh(np.array(topo.devices[:n]), (axis,))
        rep = NamedSharding(mesh, P())
        if axis == "tp":
            kv_shd = NamedSharding(mesh, P(None, None, None, "tp"))
            rules = LogicalAxisRules.default().with_overrides(
                ("vocab", None), ("embed", None))
            par_shd = tree_shardings(param_logical_axes(cfg), mesh, rules)
        else:
            kv_shd, par_shd = rep, rep
        pool_shd = kv_shd
    S = lambda s, t, shd=rep: jax.ShapeDtypeStruct(s, t, sharding=shd)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    if not isinstance(par_shd, dict):
        par_shd = jax.tree.map(lambda _: par_shd, params)
    params = jax.tree.map(lambda a, shd: S(a.shape, a.dtype, shd),
                          params, par_shd)
    pool = S((cfg.num_layers, 3073, page, 8, 128), cfg.dtype, pool_shd)
    return cfg, kv_shd, S, params, pool


@pytest.mark.parametrize("mesh_axes", [None, {"tp": 2}, {"sp": 2}])
def test_decode_step_compiles_for_v5e_in_place(mesh_axes, topo, monkeypatch,
                                               no_compile_cache):
    """The engine's decode step at the cells' widths (two layers), on one
    chip and under `shard_map` on a tp and an sp mesh: the kernel is in the
    program, both pools and the step's resident state alias their outputs,
    and nothing pool-sized is made beside them."""
    import re

    from ray_tpu.llm import programs as E

    monkeypatch.setattr(pa, "decode_path", lambda *shapes: "pallas")
    B, page, P_ = 16, 16, 128
    cfg, kv_shd, S, params, pool = _cell(topo, mesh_axes, page)
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = {"slots": S((B, P_ + 4), jnp.int32),
             "rng": S(key.shape, key.dtype)}

    def decode_step(p, pk, pv, state, update):
        return E._decode_fn(p, pk, pv, state, update, cfg, page, kv_shd)
    compiled = jax.jit(decode_step, donate_argnums=(1, 2, 3)).lower(
        params, pool, pool, state, S((B, P_ + 5), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_decode_attention" in text
    n_params, n_state = len(jax.tree.leaves(params)), 2
    aliases = {int(o): int(i) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", text.split("\n", 1)[0])}
    assert aliases == {o: n_params + o for o in range(2 + n_state)}
    mem = compiled.memory_analysis()
    per_device = math.prod(pool.shape) * 2 // (2 if mesh_axes == {"tp": 2}
                                               else 1)
    assert mem.alias_size_in_bytes >= 2 * per_device
    assert mem.temp_size_in_bytes < per_device // 8


def _pool_sized_copies(compiled, pool) -> list:
    """The compiled program's `copy` (or `transpose`) instructions whose
    result is as large as one layer of a pool half or larger."""
    import re
    layer, n_pages = math.prod(pool.shape[1:]), pool.shape[1]
    found = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= bf16\[([\d,]+)\]\S* (copy|transpose)\(", line)
        dims = list(map(int, m.group(1).split(","))) if m else []
        if n_pages in dims and math.prod(dims) >= layer:
            found.append(line.strip()[:160])
    return found


def test_lfm2_decode_step_and_install_compile_for_v5e_without_pool_copies(
        topo, monkeypatch, no_compile_cache):
    """LFM2's decode step and install at serve_doc_reask_moe's shapes (two
    attention layers, 3,073 pages of 16, KV 8 x 64, 8 slots x 256 pages):
    both pools alias their outputs, no copy or transpose gives a pool half
    (the `(L, N, page, 8, 64)` pool had eight in the step, four in the
    install, and 0.95 GiB of scratch), and the temporaries stay under a
    quarter of one.  The step's two attention layers are the paged kernel
    (the chooser answering as on a chip): nothing as large as the slots'
    whole tables, (8, 4096, 512), is gathered, selected or multiplied, and
    no pool is copied to VMEM and back around them."""
    import json
    import os
    import re

    from jax.sharding import SingleDeviceSharding

    from benchmark.families import lfm2_moe
    from benchmark.run import ROOT
    from ray_tpu.llm import programs as E
    from ray_tpu.models import routed
    from ray_tpu.models.transformer import STATEFUL, init_params, zero_state

    monkeypatch.setattr(routed, "grouped_path", lambda: "megablox")
    monkeypatch.setattr(pa, "decode_path", as_on_a_tpu(pa.decode_path))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b-l9.json")) as f:
        cfg = lfm2_moe.program_config(json.load(f))
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    on_chip = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    B, page, P_ = 8, 16, 256
    L, KV, D_ = cfg.count("*"), cfg.num_kv_heads, cfg.head_dim_
    assert (L, KV, D_) == (2, 8, 64) and pa.pool_row(KV, D_) == "lanes"
    pool = S(pa.pool_shape(L, 3073, page, KV, D_), cfg.dtype)
    half = math.prod(pool.shape) * 2
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = {"slots": S((B, P_ + 4), jnp.int32),
             "rng": S(key.shape, key.dtype),
             "rec": on_chip(jax.eval_shape(lambda: [
                 zero_state(cfg, k, B) for k in cfg.kinds if k in STATEFUL]))}

    def decode_step(p, pk, pv, state, update):
        return E._decode_fn(p, pk, pv, state, update, cfg, page, None)
    step = jax.jit(decode_step, donate_argnums=(1, 2, 3)).lower(
        params, pool, pool, state, S((B, P_ + 5), jnp.int32)).compile()
    aliases = {int(o): int(i) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", step.as_text().split("\n", 1)[0])}
    n_params = len(jax.tree.leaves(params))
    assert aliases[0] == n_params and aliases[1] == n_params + 1
    assert "gmm" in step.as_text()
    assert len(re.findall(r"custom-call\(.*paged_decode_attention",
                          step.as_text())) == L
    assert not re.search(rf"\[{B},{P_ * page},512\]", step.as_text())
    # nor is a pool parked in VMEM between the two layers' kernels (100 MB
    # fit, and XLA copied one in and out, 0.21 ms a step on the chip, until
    # the wrapper held both in HBM)
    assert [line for line in step.as_text().splitlines()
            if "copy-start(" in line and "3073" in line] == []

    def install_kv(pk, pv, ks, vs, pages):
        return E._install_fn(pk, pv, ks, vs, pages, page, None)
    programs = [step]
    for rows in (4096, 64):
        kv = S((L, rows, KV, D_), cfg.dtype)
        programs.append(jax.jit(install_kv, donate_argnums=(0, 1)).lower(
            pool, pool, kv, kv, S((P_,), jnp.int32)).compile())
    for compiled in programs:
        assert _pool_sized_copies(compiled, pool) == []
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= 2 * half
        # (The step reads 15.1 MiB: 8.0 MiB in HBM, 512-byte tuple headers
        # 16 KiB apart by the compiler's buffer assignment; an install 0.)
        assert mem.temp_size_in_bytes < half // 4


def test_moonlight_programs_compile_for_v5e_around_one_pool(
        topo, monkeypatch, no_compile_cache):
    """Moonlight's decode step, install and a re-ask's suffix prefill at
    serve_doc_reask_mla's shapes (9 latent layers, 8,193 pages of 16 rows of
    640 lanes, 16 slots x 512 pages): the ONE pool aliases its output, the
    step holds nine latent kernels and no copy of the pool or of a layer of
    it (a layer sliced out before the suffix's gather was a whole pool of
    scratch), and the kernel alone compiles at those shapes.  And a whole
    prompt's prefill in the check's 4,096-row bucket: nine prefill kernels
    and no scores array, where the suffix keeps its built scores."""
    import json
    import os
    import re

    from jax.sharding import SingleDeviceSharding

    from benchmark.families import deepseek_v3
    from benchmark.run import ROOT
    from ray_tpu.llm import programs as E
    from ray_tpu.models import routed
    from ray_tpu.models.transformer import init_params

    monkeypatch.setattr(routed, "grouped_path", lambda: "megablox")
    monkeypatch.setattr(pa, "decode_path", lambda *a: "pallas")
    # `prefill_path` asks the platform: let it see the described chips.
    monkeypatch.setattr(jax, "devices", lambda *a: topo.devices)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "moonlight-16b-a3b-l9.json")) as f:
        cfg = deepseek_v3.program_config(json.load(f))
    assert E._prefill_path(cfg, 8192, None) == "kernel"
    assert E._prefill_path(cfg, 512, None) == "xla"         # under MIN_ROWS
    assert E._prefill_path(cfg, 1024, None, 16, 512) == "xla"   # over pages
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    on_chip = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    B, page, P_ = 16, 16, 512
    L = cfg.count("L")
    assert (L, cfg.cache_row) == (9, (1, 576))
    pool = S(pa.pool_shape(L, 8193, page, *cfg.cache_row), cfg.dtype)
    assert pool.shape == (9, 8193, 16, 640)
    whole = math.prod(pool.shape) * 2

    kernel = jax.jit(lambda q, pool, tb, ln, li: pa._paged_latent_pallas(
        q, pool, tb, ln, li, cfg.latent.scale, 512)).lower(
        S((B, 16, 576), cfg.dtype), pool, S((B, P_), jnp.int32),
        S((B,), jnp.int32), S((), jnp.int32)).compile()
    assert "paged_latent_attention" in kernel.as_text()
    assert kernel.memory_analysis().temp_size_in_bytes < 1 << 20

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = {"slots": S((B, P_ + 4), jnp.int32),
             "rng": S(key.shape, key.dtype)}

    def decode_step(p, pk, pv, state, update):
        return E._decode_fn(p, pk, pv, state, update, cfg, page, None)
    step = jax.jit(decode_step, donate_argnums=(1, 2, 3)).lower(
        params, pool, None, state, S((B, P_ + 5), jnp.int32)).compile()
    aliases = {int(o): int(i) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", step.as_text().split("\n", 1)[0])}
    assert aliases[0] == len(jax.tree.leaves(params))
    text = step.as_text()
    assert len(set(re.findall(r"%(paged_latent_attention[.\d]*) =", text))) \
        == 9 and "gmm" in text

    def install_kv(pk, pv, ks, vs, pages):
        return E._install_fn(pk, pv, ks, vs, pages, page, None)
    install = jax.jit(install_kv, donate_argnums=(0, 1)).lower(
        pool, None, S((L, 8192, 1, 576), cfg.dtype), None,
        S((P_,), jnp.int32)).compile()

    def suffix(p, pk, pv, pg, t, pl, n, ckpt, row):
        return E._state_prefill_fn(p, pk, pv, pg, t, pl, n, ckpt, row, cfg,
                                   page, 0)
    reask = jax.jit(suffix).lower(
        params, pool, None, S((P_,), jnp.int32), S((1, 64), jnp.int32),
        S((), jnp.int32), S((), jnp.int32), [], S((), jnp.int32)).compile()
    assert "prefill_attention" not in reask.as_text()

    def whole_prompt(p, t, n, row):
        return E._state_prefill_fn(p, None, None, None, t, 0, n, [], row,
                                   cfg, page, 0)
    text = jax.jit(whole_prompt).lower(
        params, S((1, 4096), jnp.int32), S((), jnp.int32),
        S((), jnp.int32)).compile().as_text()
    assert len(set(re.findall(r"%(prefill_attention[.\d]*) =", text))) == 9
    assert not re.search(r"f32\[(1,)?16,512,\d+\]", text)     # no scores
    for compiled in (step, install, reask):
        assert _pool_sized_copies(compiled, pool) == []
        assert compiled.memory_analysis().temp_size_in_bytes < whole // 16
    for compiled in (step, install):
        assert compiled.memory_analysis().alias_size_in_bytes >= whole


def test_brumby_programs_compile_for_v5e_around_the_state(
        topo, monkeypatch, no_compile_cache):
    """Brumby's decode step, a whole prompt's prefill and the install of
    its state at serve_doc_reask_retention's shapes (six power retention
    layers, 8 slots, 14 checkpoint rows, NO pool): the step holds six
    `retention_step` kernels, the state rows alias their results (1.83 GB
    in place, no copy of a layer's 304 MB), the prefill returns the last
    TWO boundaries' checkpoints and the end, and its scratch leaves the
    chip room beside 12.1 GB at rest."""
    import json
    import os
    import re

    from jax.sharding import SingleDeviceSharding

    from benchmark.families import brumby
    from benchmark.run import ROOT
    from ray_tpu.llm import programs as E
    from ray_tpu.models import retention
    from ray_tpu.models.transformer import init_params, zero_state

    monkeypatch.setattr(retention, "step_path", lambda dims: "pallas")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-base-l6.json")) as f:
        cfg = brumby.program_config(json.load(f), max_seq_len=4096)
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    on_chip = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    B, page, P_ = 8, 16, 256
    rows = lambda n: [on_chip(jax.eval_shape(
        lambda: zero_state(cfg, "P", n))) for _ in range(6)]
    layer = 38_043_648
    assert cfg.retention.state_bytes() == layer

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = {"slots": S((B, P_ + 4), jnp.int32),
             "rng": S(key.shape, key.dtype), "rec": rows(B)}

    def decode_step(p, pk, pv, state, update):
        return E._decode_fn(p, pk, pv, state, update, cfg, page, None)
    step = jax.jit(decode_step, donate_argnums=(1, 2, 3)).lower(
        params, None, None, state, S((B, P_ + 5), jnp.int32)).compile()
    text = step.as_text()
    assert len(set(re.findall(r"%(retention_step[.\d]*) =", text))) == 6
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * B * layer
    assert mem.temp_size_in_bytes < layer               # 9 MB

    def prefill(p, pk, pv, pg, t, pl, n, ckpt, row):
        return E._state_prefill_fn(p, pk, pv, pg, t, pl, n, ckpt, row, cfg,
                                   page, 512, keep=2)
    whole = jax.jit(prefill).lower(
        params, None, None, None, S((1, 4096), jnp.int32), S((), jnp.int32),
        S((), jnp.int32), rows(14), S((), jnp.int32))
    kept = jax.tree.leaves(jax.eval_shape(
        prefill, params, None, None, None, S((1, 4096), jnp.int32),
        S((), jnp.int32), S((), jnp.int32), rows(14), S((), jnp.int32))[4][0])
    assert kept[0].shape[:2] == (1, 2)                  # a ring of `keep`
    mem = whole.compile().memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9               # 1.10 GB
    assert mem.output_size_in_bytes < 3.1 * 6 * layer   # 2 kept + the end

    ring = [jax.tree.map(lambda a: S((1, 2, *a.shape[1:]), a.dtype), r)
            for r in rows(1)]
    install = jax.jit(E._install_state_fn, donate_argnums=(0, 1)).lower(
        rows(B), rows(14), S((), jnp.int32), rows(1), ring,
        S((2,), jnp.int32)).compile()
    mem = install.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * (B + 14) * layer
    assert mem.temp_size_in_bytes < layer


@pytest.mark.parametrize("shape", [
    # repeats, slots, heads, head width, state, groups: serve_chat_ssm's
    # stacked leaf, serve_doc_reask_hybrid's plain one
    (4, 32, 64, 64, 128, 1), (None, 8, 128, 64, 128, 8)])
def test_mamba_step_compiles_for_v5e_in_place(shape, topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import mamba2
    R, B, H, P_, N, G = shape
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    leaf = (B, H, P_, N) if R is None else (R, B, H, P_, N)

    def step(xdt, dec, Bm, Cm, ssm, order, n, r):
        return mamba2.mamba_step(xdt, dec, Bm, Cm, ssm, order, n,
                                 None if R is None else r)
    compiled = jax.jit(step, donate_argnums=(4,)).lower(
        S((B, H, P_), jnp.bfloat16), S((B, H), jnp.float32),
        S((B, G, N), jnp.bfloat16), S((B, G, N), jnp.bfloat16),
        S(leaf, jnp.float32), S((B,), jnp.int32), S((), jnp.int32),
        S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mamba_step" in text
    # The whole leaf, stacked or not, is the kernel's operand and result.
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == math.prod(leaf) * 4
    assert mem.temp_size_in_bytes < 1 << 20


def test_granite_decode_step_compiles_for_v5e_with_the_state_where_it_lies(
        topo, monkeypatch, no_compile_cache):
    """Granite's decode step at serve_chat_ssm's shapes (all 40 layers, 32
    slots): nine `mamba_step` calls in the scan's body, each given the whole
    stacked leaf (4, 32, 64, 64, 128) and giving it back; no copy, slice or
    update of a state leaf anywhere (a repeat sliced out for the kernel
    would be 67 MB copied in and out a layer); the period's one attention
    layer is the paged kernel over the stacked lanes pool, and nothing as
    large as the slots' whole tables, (32, 2048, 512), is gathered, selected
    or multiplied; the pools and the resident state alias their outputs; the
    temporaries stay where the parent's were (0.08 GB)."""
    import re

    from jax.sharding import SingleDeviceSharding

    from benchmark.run import load_cell
    from ray_tpu.llm import programs as E
    from ray_tpu.models import mamba2
    from ray_tpu.models.transformer import init_params, zero_states
    from tests.test_mamba_step import as_on_a_tpu as backend_a_tpu

    monkeypatch.setattr(mamba2, "step_path", backend_a_tpu(mamba2.step_path))
    monkeypatch.setattr(pa, "decode_path", as_on_a_tpu(pa.decode_path))
    cell = load_cell("serve_chat_ssm")
    eng = cell["traffic"]["engine"]
    cfg = cell["family"].program_config(cell["config"],
                                        max_seq_len=eng["max_len"])
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    on_chip = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    B, page = eng["max_batch"], eng["page_size"]
    P_ = eng["max_len"] // page
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    pools = on_chip(jax.eval_shape(
        lambda: E.make_pools(cfg, eng["kv_pages"] + 1, page, None)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    rec = on_chip(jax.eval_shape(lambda: zero_states(cfg, B)))
    state = {"slots": S((B, P_ + 4), jnp.int32),
             "rng": S(key.shape, key.dtype), "rec": rec}
    leaf = rec[0]["ssm"].shape
    assert leaf == (4, 32, 64, 64, 128) and len(rec) == 9

    def decode_step(p, pk, pv, state, update):
        return E._decode_fn(p, pk, pv, state, update, cfg, page, None)
    compiled = jax.jit(decode_step, donate_argnums=(1, 2, 3)).lower(
        params, *pools, state, S((B, P_ + 5), jnp.int32)).compile()
    text = compiled.as_text()
    dims = ",".join(map(str, leaf))
    calls = re.findall(r"= \(f32\[32,64,64\]\S*, f32\[" + dims
                       + r"\]\S*\) custom-call\(", text)
    assert len(calls) == 9 and text.count("mamba_step") >= 9
    moved = [line.strip()[:160] for line in text.splitlines() if re.search(
        r"= f32\[(" + dims + "|" + dims[2:] + r")\]\S* "
        r"(copy|transpose|dynamic-slice|dynamic-update-slice|fusion)\(",
        line)]
    assert moved == []
    assert pools[0].shape == (4, 4097, page, 512)
    assert len(re.findall(r"custom-call\(.*paged_decode_attention",
                          text)) == 1
    assert not re.search(rf"\[{B},{P_ * page},512\]", text)
    mem = compiled.memory_analysis()
    resident = sum(math.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves((pools, rec)))
    assert mem.alias_size_in_bytes >= resident
    assert mem.temp_size_in_bytes < 128 << 20


@pytest.mark.parametrize("tokens", [16, 64, 4096])
@pytest.mark.parametrize("name", ["nemotron_h", "lfm2_moe", "deepseek_v3"])
def test_grouped_products_compile_for_v5e_at_k_whole(
        name, tokens, topo, monkeypatch, no_compile_cache):
    """A decode step's rows, a suffix's and a whole prompt's bucket of the
    three routed cells: both products of a layer take a matrix's k in ONE
    tile (`models/routed.py:_tiling`) and the kernel's blocks fit its VMEM
    at the published widths."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import routed
    from tests.test_moonlight_model import K_WHOLE, _published

    monkeypatch.setattr(routed, "grouped_path", lambda: "megablox")
    dims, shapes = _published(name)
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    rows = tokens * dims.top_k
    for (k, n), tile in zip(shapes, K_WHOLE[name]):
        assert routed._tiling(k, n) == tile
        text = jax.jit(routed._grouped).lower(
            S((rows, k), jnp.bfloat16), S((dims.held, k, n), jnp.bfloat16),
            S((dims.held,), jnp.int32)).compile().as_text()
        assert "tpu_custom_call" in text and "gmm" in text


@pytest.mark.parametrize("k,n,dtype,whole", [
    # the widest blocks the guard lets through whole: 14.9 and 15.0 MiB by
    # its reckoning; the compiler took up to 15.1 and refused from 16.4
    (2304, 1408, jnp.bfloat16, True),
    (1536, 1024, jnp.float32, True),
    (2560, 1408, jnp.bfloat16, False),      # 16.4 MiB whole: refused, so cut
    (2048, 2816, jnp.float32, False),       # Moonlight's w1 in float32
])
def test_what_the_vmem_guard_lets_through_compiles_for_v5e(
        k, n, dtype, whole, topo, monkeypatch, no_compile_cache):
    """`routed._tiling`'s reckoning of the kernel's blocks against `_VMEM`,
    held to the compiler at its edge: the tiles it gives compile, k whole
    or cut."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import routed

    monkeypatch.setattr(routed, "grouped_path", lambda: "megablox")
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    tile = routed._tiling(k, n, jnp.dtype(dtype).itemsize)
    assert (tile[1] == k) == whole
    text = jax.jit(routed._grouped).lower(
        S((1024, k), dtype), S((8, k, n), dtype),
        S((8,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "gmm" in text


# ---- the prefill kernel (ops/prefill_attention.py) ------------------------
# Its interpreted cases are in tests/test_prefill_attention.py; what needs
# the described chip is here, in the one file that describes it.

@pytest.mark.parametrize("shape", [
    # rows, KV, groups, dtype, prefix in pages: serve_doc_reask's bucket,
    # the reference check's, the suffix form at its largest and smallest,
    # float32 and one KV head
    (4096, 8, 4, jnp.bfloat16, False),
    (1024, 8, 4, jnp.bfloat16, False),
    (4096, 8, 4, jnp.bfloat16, True),
    (128, 8, 4, jnp.bfloat16, True),
    (512, 4, 2, jnp.float32, True),
    (256, 1, 8, jnp.bfloat16, True),
    # serve_doc_reask_mla's bucket and its check's: a latent layer expanded,
    # every head its own keys of 192 over values of 128
    (8192, 16, 1, jnp.bfloat16, False, 192),
    (4096, 16, 1, jnp.bfloat16, False, 192),
])
def test_prefill_kernel_compiles_for_v5e(shape, topo, no_compile_cache):
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import prefill_attention as pfa
    rows, KV, groups, dtype, paged, Dk = (*shape, D)[:6]
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    assert pfa.kernel_tiles((rows, KV * groups, Dk), KV, dtype, value=D,
                            **(dict(page=16, table_len=256) if paged else {}))
    args = [S((rows, KV * groups, Dk), dtype), S((rows, KV, Dk), dtype),
            S((rows, KV, D), dtype), S((), jnp.int32)]
    if paged:
        pool = S((3, 3073, 16, KV, D), dtype)
        args += [pool, pool, S((256,), jnp.int32), S((), jnp.int32),
                 S((), jnp.int32)]
    compiled = jax.jit(lambda *a: pfa._prefill_attention_pallas(
        *a, scale=1 / math.sqrt(Dk))).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "prefill_attention" in text
    # The pool reaches the kernel as it lies in HBM: no copy of it, no slice
    # (keys of 192: q and k filled to 256 lanes, k and v by heads, 128 MiB).
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1 << 20 if Dk == D else 129 << 20)


@pytest.mark.parametrize("rows", [16384, 1024])
def test_masked_prefill_kernel_compiles_for_v5e(rows, topo, no_compile_cache):
    """The prefill kernel with a caller's mask (int8 tiles beside the key
    blocks) at serve_doc_reask_sparse's widths: 32 / 4 heads of 128."""
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import prefill_attention as pfa
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)
    bf = jnp.bfloat16
    compiled = jax.jit(lambda q, k, v, n, m: pfa._prefill_attention_pallas(
        q, k, v, n, scale=1 / math.sqrt(D), mask=m)).lower(
            S((rows, 32, D), bf), S((rows, 4, D), bf), S((rows, 4, D), bf),
            S((), jnp.int32), S((rows, rows), jnp.int8)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "prefill_attention" in text
    # q by rows, k and v by heads: no copy of the mask, no scores
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 20480


def test_sparse_programs_compile_for_v5e(topo, monkeypatch, no_compile_cache):
    """serve_doc_reask_sparse's three programs at its widths (two layers,
    eight experts, a pool of 4,097 pages): the decode step keeps its three
    pools in place and reads them through its two kernels a layer (the
    chooser answering as on a chip: `index_select`, then the paged kernel
    under the marks, `sparse_decode_attention`): no gather of a slot's whole index table, (8, 16,384,
    128), none of the picked rows, no pool copied or parked in VMEM round
    the kernels; a whole prompt of 4,096 rows goes through the masked
    prefill kernel and builds no float32 scores; a suffix over cached pages
    gathers the slot's table."""
    import re
    from jax.sharding import SingleDeviceSharding
    from benchmark.families import keye_vl2
    from benchmark.run import load_cell
    from ray_tpu.llm import programs as E
    from ray_tpu.models import routed
    from ray_tpu.models.transformer import init_params
    from ray_tpu.ops import prefill_attention as pfa
    monkeypatch.setattr(routed, "grouped_path", lambda: "megablox")
    monkeypatch.setattr(pfa, "prefill_path", as_on_a_tpu(pfa.prefill_path))
    monkeypatch.setattr(pa, "decode_path", as_on_a_tpu(pa.decode_path))
    config = dict(load_cell("serve_doc_reask_sparse")["config"],
                  num_hidden_layers=2, num_experts=8, num_local_experts=8)
    cfg = keye_vl2.program_config(config, max_seq_len=16384)
    one = SingleDeviceSharding(topo.devices[0])
    S = lambda s, t=jnp.int32: jax.ShapeDtypeStruct(s, t, sharding=one)
    on = lambda tree: jax.tree.map(lambda a: S(a.shape, a.dtype), tree)
    B, P, page = 8, 1024, 16
    params = on(jax.eval_shape(lambda: init_params(cfg, jax.random.key(0))))
    pools = on(jax.eval_shape(lambda: E.make_pools(cfg, 4097, page, None)))
    pool_bytes = 2 * 4097 * page * 4 * 128 * 2
    state = {"slots": S((B, P + 4)),
             "rng": on(jax.eval_shape(lambda: jax.random.key(1)))}
    step = jax.jit(lambda p, pk, pv, st, up: E._decode_fn(
        p, pk, pv, st, up, cfg, page, None), donate_argnums=(1, 2, 3)).lower(
            params, *pools, state, S((B, P + 5))).compile()
    assert step.memory_analysis().temp_size_in_bytes < pool_bytes // 2
    assert step.memory_analysis().alias_size_in_bytes > 2 * pool_bytes
    text = step.as_text()
    for kernel in ("index_select", "sparse_decode_attention"):
        assert len(re.findall(rf"custom-call\(.*{kernel}", text)) == 2
    # the slots' whole index tables, the picked rows of 4 x 128
    assert not re.search(rf"bf16\[{B},{P * page},128\]", text)
    assert not re.search(rf"bf16\[{B},2048,4,128\]", text)
    for pool in jax.tree.leaves(pools):
        assert _pool_sized_copies(step, pool) == []
    assert [line for line in text.splitlines()
            if "copy-start(" in line and "4097" in line] == []
    whole = jax.jit(lambda p, pk, pv, t, n: E._state_prefill_fn(
        p, pk, pv, None, t, 0, n, [], 0, cfg, page, 0)).lower(
            params, *pools, S((1, 4096)), S(())).compile()
    assert "prefill_attention" in whole.as_text()
    # (a KV group's float32 scores of a row block, which the XLA form
    # builds, would be this array)
    assert "f32[8,512,4096]" not in whole.as_text()
    suffix = jax.jit(lambda p, pk, pv, pg, t, pl, n: E._state_prefill_fn(
        p, pk, pv, pg, t, pl, n, [], 0, cfg, page, 0)).lower(
            params, *pools, S((P,)), S((1, 64)), S(()), S(())).compile()
    assert "prefill_attention" not in suffix.as_text()


@pytest.mark.parametrize("mesh_axes", [None, {"tp": 2}])
@pytest.mark.parametrize("form", ["whole", "suffix"])
def test_prefill_bodies_compile_for_v5e_without_scores(
        form, mesh_axes, topo, monkeypatch, no_compile_cache):
    """The engine's two prefill bodies at the cells' widths (two layers) and
    the 4,096 bucket, on one chip and under `shard_map` on a tp mesh: the
    kernel is in the program, and no (H, S, S) or (H, Sb, T + Sb) array, no
    widened K or V and nothing pool-sized is made."""
    import re
    from ray_tpu.llm import programs as E

    # `prefill_path` asks the platform: let it see the described chips.
    monkeypatch.setattr(jax, "devices", lambda *a: topo.devices)
    rows, page, P_ = 4096, 16, 256
    cfg, kv_shd, S, params, pool = _cell(topo, mesh_axes, page)
    assert E._prefill_path(cfg, rows, kv_shd) == "kernel"
    assert E._prefill_path(cfg, 128, kv_shd, page, P_) == "kernel"
    assert E._prefill_path(cfg, 512, kv_shd) == "xla"       # under MIN_ROWS
    toks = S((1, rows), jnp.int32)
    if form == "whole":
        def prefill(p, t, n):
            return E._prefill_fn(p, t, n, cfg, kv_shd)
        lowered = jax.jit(prefill).lower(params, toks, S((), jnp.int32))
    else:
        def suffix_prefill(p, pk, pv, pg, t, pl, n):
            return E._prefill_fn(p, t, n, cfg, kv_shd,
                                 cached=(pk, pv, pg, pl, page))
        lowered = jax.jit(suffix_prefill).lower(
            params, pool, pool, S((P_,), jnp.int32), toks,
            S((), jnp.int32), S((), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "prefill_attention" in text
    assert not re.search(r"\[(1,)?(32|16),4096,(4096|8192)\]", text)
    assert not re.search(r"[\[,]8192[\],]", text)    # no [row's keys | new]
    # 145 MiB (q, k, v and the MLP's rows); with the scores the whole form
    # took 1.07 GiB and the suffix form, with its page row's keys, 2.1.
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
