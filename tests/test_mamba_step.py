"""A Mamba-2 decode step moves the state of the slots that are live and of
no others (models/mamba2.py: `mamba_step`, `live_order`, `step_path`; the
scan's carry in models/transformer.py: `run_pattern`, `carried_whole`; the
counter `rows_moved`, llm/programs.py; the reader `ssm_moved`).  CPU, the
kernel interpreted, small widths; compiled for the chip in
tests/test_paged_attention.py, the one file that describes the topology.
"""

import dataclasses
import functools
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import selftest
from benchmark.readers import ssm_moved, ssm_step
from benchmark.run import load_cell
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models import mamba2
from ray_tpu.models.mamba2 import Mamba2Dims

# Granite's and Nemotron's group counts, at small widths that tile: a state
# of 128 lanes, heads of 8 rows.
SHAPES = {"one_group": Mamba2Dims(num_heads=8, head_dim=8, state=128,
                                  groups=1, conv_kernel=4, chunk=16),
          "eight_groups": Mamba2Dims(num_heads=16, head_dim=8, state=128,
                                     groups=8, conv_kernel=4, chunk=16)}
LIVE = {"none": [0, 0, 0, 0, 0], "one": [0, 0, 0, 1, 0],
        "some": [1, 0, 1, 0, 1], "all": [1, 1, 1, 1, 1]}
HIDDEN = 32


def as_on_a_tpu(fn):
    """`fn` answering as it would in a process whose backend is a TPU."""
    @functools.wraps(fn)
    def asked(*a, **k):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return fn(*a, **k)
    return asked


@pytest.fixture
def kernel_here(monkeypatch):
    """The rule as a TPU reads it, the kernel interpreted."""
    monkeypatch.setattr(mamba2, "step_path", as_on_a_tpu(mamba2.step_path))
    monkeypatch.setattr(mamba2, "mamba_step", functools.partial(
        mamba2.mamba_step, interpret=True))


def _layer(dims, seed, dtype):
    lp = mamba2.init_layer(jax.random.key(seed), HIDDEN, dims, dtype)
    rng = np.random.default_rng(seed)
    B = len(LIVE["all"])
    u = jnp.asarray(rng.normal(size=(B, 1, HIDDEN)), dtype)
    state = {"ssm": jnp.asarray(rng.normal(size=(
        B, dims.num_heads, dims.head_dim, dims.state)), jnp.float32),
        "tail": jnp.asarray(rng.normal(size=(
            B, dims.conv_kernel - 1, dims.conv_width)), dtype)}
    return lp, u, state


@pytest.mark.parametrize("leaf", ["plain", "repeat0", "repeat1", "repeat2"])
@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_step_is_the_mixer_for_the_live_and_nothing_for_the_dead(
        shape, live, leaf, kernel_here):
    """Against the parent's form (`ssd` over every slot, a dead one's dt 0):
    the outputs and the live rows to float32 rounding, the dead rows and
    every other repeat's bit for bit."""
    dims = SHAPES[shape]
    lp, u, state = _layer(dims, 3, jnp.float32)
    alive = jnp.asarray(LIVE[live], bool)
    want_y, want, _ = mamba2.mixer(lp, u, state, dims, live=alive)
    assert mamba2.step_path(dims, 1, None, alive, 0) == "pallas"
    given, repeat = dict(state), None
    if leaf != "plain":
        repeat = int(leaf[-1])
        others = np.random.default_rng(4).normal(
            size=(3, *state["ssm"].shape)).astype(np.float32)
        given["ssm"] = jnp.asarray(others).at[repeat].set(state["ssm"])
    with mock.patch.object(mamba2, "ssd", side_effect=AssertionError):
        got_y, got, kept = jax.jit(
            lambda u, s, a, r: mamba2.mixer(lp, u, s, dims, live=a, repeat=r)
        )(u, given, alive, None if repeat is None else jnp.int32(repeat))
    assert kept is None and got["ssm"].dtype == jnp.float32
    ssm = np.asarray(got["ssm"])
    if repeat is not None:
        assert ssm.shape == (3, *state["ssm"].shape)
        for r in set(range(3)) - {repeat}:
            assert np.array_equal(ssm[r], others[r])
        ssm = ssm[repeat]
    L = np.asarray(alive)
    assert np.array_equal(ssm[~L], np.asarray(state["ssm"])[~L])
    np.testing.assert_allclose(ssm[L], np.asarray(want["ssm"])[L],
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(got["tail"], want["tail"])
    np.testing.assert_allclose(np.asarray(got_y)[L], np.asarray(want_y)[L],
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(np.asarray(got_y)).all()


@pytest.mark.parametrize("shape", list(SHAPES))
def test_in_bfloat16_the_step_adds_dt_x_unrounded(shape, kernel_here):
    """Activations in bfloat16, as the cells have them.  `ssd` rounds dt x
    to bfloat16 before its product with B (a round trip that XLA takes out
    on a TPU as excess precision: there the parent's form adds it
    unrounded); the kernel is given it in float32, so it stands within
    that rounding of `ssd` here, and at float32 rounding of the recurrence
    written out."""
    dims = SHAPES[shape]
    lp, u, state = _layer(dims, 5, jnp.bfloat16)
    alive = jnp.asarray(LIVE["some"], bool)
    _, want, _ = mamba2.mixer(lp, u, state, dims, live=alive)
    with mock.patch.object(mamba2, "ssd", side_effect=AssertionError):
        _, got, _ = mamba2.mixer(lp, u, state, dims, live=alive,
                                 order=mamba2.live_order(alive))
    np.testing.assert_allclose(got["ssm"], want["ssm"], rtol=0, atol=2e-3)
    rng = np.random.default_rng(6)
    B, H, P, G, N = (len(LIVE["some"]), dims.num_heads, dims.head_dim,
                     dims.groups, dims.state)
    xdt = jnp.asarray(rng.normal(size=(B, H, P)) * 0.3, jnp.float32)
    dec = jnp.asarray(rng.uniform(0.2, 1, size=(B, H)), jnp.float32)
    Bm, Cm = (jnp.asarray(rng.normal(size=(B, G, N)), jnp.bfloat16)
              for _ in range(2))
    y, ssm = mamba2.mamba_step(xdt, dec, Bm, Cm, state["ssm"],
                               *mamba2.live_order(alive))
    want_y, want_ssm = mamba2.reference_step(
        xdt, dec, Bm.astype(jnp.float32), Cm.astype(jnp.float32),
        state["ssm"])
    L = np.asarray(alive)
    np.testing.assert_allclose(np.asarray(ssm)[L], np.asarray(want_ssm)[L],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[L], np.asarray(want_y)[L],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("active, order, n", [
    ([0, 0, 0, 0], [0, 0, 0, 0], 0),
    ([0, 0, 1, 0], [2, 2, 2, 2], 1),
    ([1, 0, 1, 1], [0, 2, 3, 3], 3),
    ([1, 1, 1, 1], [0, 1, 2, 3], 4),
    ([0, 1, 0, 1, 1, 0, 0, 1], [1, 3, 4, 7, 7, 7, 7, 7], 4),
])
def test_the_live_slots_come_first_and_the_last_one_fills_the_rest(
        active, order, n):
    got, n_live = jax.jit(mamba2.live_order)(
        jnp.asarray(active, jnp.int32) != 0)
    assert got.dtype == jnp.int32 and got.tolist() == order
    assert int(n_live) == n


@pytest.mark.parametrize("what, change, call", [
    ("granite", {}, {}),
    ("nemotron", {"num_heads": 128, "groups": 8}, {}),
    ("a state of 16 lanes", {"state": 16}, {}),
    ("heads of 4 rows", {"head_dim": 4}, {}),
    ("a state held in bfloat16", {"state_dtype": "bfloat16"}, {}),
    ("a prompt's rows", {}, {"rows": 64}),
    ("a padded bucket", {}, {"rows": 64, "length": 7}),
    ("one row of a prompt", {}, {"live": None}),
    ("checkpoints", {}, {"every": 512}),
    ("heads that no block holds by groups", {"num_heads": 100, "groups": 4},
     {}),
])
def test_the_path_is_read_from_the_shapes(what, change, call):
    dims = dataclasses.replace(
        Mamba2Dims(num_heads=64, head_dim=64, state=128, groups=1), **change)
    call = {"rows": 1, "length": None, "live": True, "every": 0, **call}
    want = "pallas" if what in ("granite", "nemotron") else "ssd"
    assert as_on_a_tpu(mamba2.step_path)(dims, **call) == want
    assert mamba2.step_path(dims, **call) == "ssd"          # this is a CPU


def test_a_block_of_heads_holds_whole_groups_or_lies_in_one():
    def block(num_heads, head_dim, state, groups):
        return mamba2._head_block(num_heads, head_dim, state, groups)
    assert block(num_heads=64, head_dim=64, state=128, groups=1) == 64
    assert block(num_heads=128, head_dim=64, state=128, groups=8) == 64
    assert block(num_heads=128, head_dim=64, state=256, groups=8) == 32
    assert block(num_heads=8, head_dim=8, state=128, groups=2) == 8
    assert block(num_heads=12, head_dim=64, state=128, groups=4) == 12
    assert block(num_heads=100, head_dim=64, state=128, groups=4) == 0


# ---- the engine ------------------------------------------------------------

def _tiny(cell_name):
    """The cell's TINY configuration with a state that tiles."""
    cell = load_cell(cell_name)
    selftest.shrink(cell)
    pc = cell["family"].program_config(cell["config"], max_seq_len=256)
    return cell["config"], dataclasses.replace(
        pc, mamba=dataclasses.replace(pc.mamba, state=128))


def _serve(pc, vocab):
    """Three slots, five requests of different lengths: slots are admitted
    and retired between the decode steps, and one stands empty at times."""
    eng = LLMEngine(pc, seed=3, max_batch=3, max_len=256, page_size=16,
                    kv_pages=64, prefix_cache=True)
    rng = np.random.default_rng(11)
    asks = [(30, 9), (45, 24), (20, 5), (33, 13), (25, 17)]
    ids = [eng.add_request(rng.integers(1, vocab, n).tolist(),
                           SamplingParams(max_tokens=out))
           for n, out in asks[:3]]
    done, steps = {}, 0
    while eng.has_unfinished():
        for req in eng.step():
            done[req.req_id] = list(req.out)
        steps += 1
        if steps in (7, 12):
            n, out = asks[len(ids)]
            ids.append(eng.add_request(rng.integers(1, vocab, n).tolist(),
                                       SamplingParams(max_tokens=out)))
    return [done[i] for i in ids], eng.mamba_stats()


@pytest.mark.parametrize("cell", ["serve_chat_ssm", "serve_doc_reask_hybrid"])
def test_an_engine_on_the_kernel_says_what_the_reference_path_says(
        cell, request):
    cfg, pc = _tiny(cell)
    want, ref = _serve(pc, cfg["vocab_size"])
    assert ref["path"] == "ssd" and ref["steps"] >= 24
    assert ref["rows_moved"] == ref["steps"] * 3 > ref["rows_stepped"]
    request.getfixturevalue("kernel_here")
    got, st = _serve(pc, cfg["vocab_size"])
    assert got == want and [len(t) for t in got] == [9, 24, 5, 13, 17]
    assert st["path"] == "pallas" and st["steps"] == ref["steps"]
    assert st["rows_moved"] == st["rows_stepped"] == ref["rows_stepped"]


# ---- the reader ------------------------------------------------------------

def test_the_reader_reads_what_the_program_moved_and_nothing_without_it():
    row = 76_437_504
    before = {"mamba": {"enabled": True, "row_bytes": row, "steps": 100,
                        "rows_stepped": 1000, "rows_moved": 3200}}
    after = {"mamba": {"enabled": True, "row_bytes": row, "steps": 300,
                       "rows_stepped": 5000, "rows_moved": 9600}}
    ctx = {"stats_before": before, "stats_after": after}
    assert ssm_moved.read(ctx, {}) == 32 * row * 2 / 2 ** 20
    assert ssm_step.read(ctx, {}) == 20 * row * 2 / 2 ** 20
    # a program that has no such counter: the parent's
    for side in (before, after):
        del side["mamba"]["rows_moved"]
    assert ssm_moved.read(ctx, {}) is None
    for none in ({}, {"stats_before": {"mamba": {"enabled": False}},
                      "stats_after": {"mamba": {"enabled": False}}},
                 {"stats_before": after, "stats_after": after}):
        assert ssm_moved.read(none, {}) is None
