"""There is ONE decoder block (models/transformer.py: `block_qkv`, the
caller's attention, `block_out`) and every path that runs the model runs
it: training's `forward`, the engine's whole-prompt, suffix and decode
bodies, the two sequence-parallel prefills and the streamed path.

Each case traces one entry point on the `tiny` preset with the two halves
wrapped by counters: the halves are entered once (inside the path's scan,
or its cached jit), the norm runs nowhere but in them and the head, and the
entry point's own source names no layer weight.  The parity tests in
test_llm.py / test_long_context.py / test_models.py pin the numerics."""

import inspect
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import programs as E
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm import sequence_parallel as SP
from ray_tpu.models import PRESETS
from ray_tpu.models import transformer as T

CFG = PRESETS["tiny"]
PAGE, PAGES, ROWS = 16, 4, 16
LAYER_WEIGHTS = re.compile(
    r'"(ln_attn|ln_mlp|attn|mlp|wq|wk|wv|wo|w_gate|w_up|w_down)"')


@pytest.fixture
def halves(monkeypatch):
    """Counts of the calls into the block's two halves, and of the norms
    run inside and outside them."""
    seen = {"qkv": 0, "out": 0, "norm_inside": 0, "norm_outside": 0,
            "depth": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            seen[name] += 1
            seen["depth"] += 1
            try:
                return fn(*a, **kw)
            finally:
                seen["depth"] -= 1
        return wrapper

    norm = T.rms_norm

    def rms_norm(*a, **kw):
        seen["norm_inside" if seen["depth"] else "norm_outside"] += 1
        return norm(*a, **kw)

    monkeypatch.setattr(T, "block_qkv", counted("qkv", T.block_qkv))
    monkeypatch.setattr(T, "block_out", counted("out", T.block_out))
    monkeypatch.setattr(T, "rms_norm", rms_norm)
    return seen


def _shapes():
    params = jax.eval_shape(lambda: T.init_params(CFG, jax.random.key(0)))
    pool = jax.ShapeDtypeStruct(
        (CFG.num_layers, 9, PAGE, CFG.num_kv_heads, CFG.head_dim_), CFG.dtype)
    S = jax.ShapeDtypeStruct
    return params, pool, S((1, ROWS), jnp.int32), S((PAGES,), jnp.int32), \
        S((), jnp.int32)


def _forward():
    params, _, toks, _, _ = _shapes()
    jax.eval_shape(lambda p, t: T.forward(p, t, CFG), params, toks)
    return T.forward


def _prefill():
    params, _, toks, _, n = _shapes()
    jax.eval_shape(lambda p, t, n: E._prefill_fn(p, t, n, CFG), params, toks,
                   n)
    return E._prefill_fn


def _prefill_by_rows():
    # Four row blocks of the bucket: the halves inside a loop each.
    params, _, toks, _, n = _shapes()
    jax.eval_shape(lambda p, t, n: E._prefill_fn(p, t, n, CFG,
                                                 row_block=ROWS // 4),
                   params, toks, n)
    return E._prefill_fn


def _suffix_prefill():
    params, pool, toks, pages, n = _shapes()
    jax.eval_shape(
        lambda p, pk, pv, pg, t, pl, n: E._prefill_fn(
            p, t, n, CFG, cached=(pk, pv, pg, pl, PAGE)), params, pool, pool,
        pages, toks, n, n)
    return E._pair_prefill_attend   # the suffix's own code: one body now


def _decode():
    params, pool, _, _, _ = _shapes()
    B = 2
    S = jax.ShapeDtypeStruct
    jax.eval_shape(
        lambda p, pk, pv, tb, lt, ln, ac: E._decode_logits_fn(
            p, pk, pv, tb, lt, ln, ac, CFG, PAGE, None), params, pool, pool,
        S((B, PAGES), jnp.int32), S((B,), jnp.int32), S((B,), jnp.int32),
        S((B,), jnp.bool_))
    return E._decode_logits_fn


def _sp_prefill():
    params, _, toks, _, n = _shapes()
    mesh = SP.sp_mesh(2)
    jax.eval_shape(lambda p, t, n: SP.sp_prefill_fn(p, t, n, CFG, mesh),
                   params, toks, n)
    return SP.sp_prefill_fn


def _sp_suffix_prefill():
    params, pool, toks, pages, n = _shapes()
    mesh = SP.sp_mesh(2)
    jax.eval_shape(
        lambda p, pk, pv, pg, t, pl, n: SP.sp_prefill_fn(
            p, t, n, CFG, mesh, cached=(pk, pv, pg, pl, PAGE)), params, pool,
        pool, pages, toks, n, n)
    return SP._sp_suffix_shard      # the suffix's own code: one body now


def _streamed():
    eng = LLMEngine(CFG, max_batch=1, max_len=64, page_size=PAGE,
                    kv_pages=PAGES, seed=0)
    prompt = list(np.random.default_rng(0).integers(1, CFG.vocab_size, 12))
    part, logits = eng.prefill_paged_chunk(prompt, 0, [], span=ROWS,
                                           is_last=True)
    assert part["k"].shape[0] == CFG.num_layers and logits is not None
    return SP.StreamAttn


@pytest.mark.parametrize("trace", [
    _forward, _prefill, _prefill_by_rows, _suffix_prefill, _decode,
    _sp_prefill, _sp_suffix_prefill, _streamed],
    ids=lambda f: f.__name__.strip("_"))
def test_every_path_runs_the_one_block(trace, halves):
    entry = trace()
    # Once: a scan traces its body once, and the streamed path's per-layer
    # calls share one cached jit.
    assert halves["qkv"] == halves["out"] == 1, halves
    # ln_attn and ln_mlp inside the halves, ln_f in the head, and no other.
    assert halves["norm_inside"] == 2 and halves["norm_outside"] == 1, halves
    assert not LAYER_WEIGHTS.search(inspect.getsource(entry)), entry


def test_layer_weights_and_the_head_are_read_in_one_place():
    root = pathlib.Path(T.__file__).parent.parent
    text = "".join(p.read_text() for d in ("models", "llm")
                   for p in sorted((root / d).glob("*.py")))
    for read in ('["ln_attn"]', '["ln_mlp"]', '["lm_head"].astype',
                 'params["ln_f"]', '"wo"]', "cfg.rope_theta"):
        assert text.count(read) == 1, read
    assert "rope1" not in text
    # The model paths of sequence_parallel.py take nothing from the
    # scheduler's file (its CPU bench entry, below them, still does).
    model_paths = inspect.getsource(SP).split("# Bench entry")[0]
    assert "from .engine import" not in model_paths
