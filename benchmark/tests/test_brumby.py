"""The `brumby` family's own cases (CPU, tiny sizes, seeded weights,
float32: every tolerance is rounding of float32 sums in another order):

- the sizes from the keys: the cut's 3.54B and the published 14.77B, the
  state's bytes as published and as held, `decode_step_bytes` against a
  hand count;
- the plain reference (the ATTENTION form of power retention) against the
  program at `TINY`: a whole-prompt prefill, decode through the state, and
  a stream served from a checkpoint with tokens run again;
- the family's own `check` decides `refcheck.report`: a zeroed and a stale
  checkpoint FAIL it under the seeded gate (the plain check sees neither),
  and both controls of `retention_control` (weights in float8, state in
  bfloat16) read past `TOLERANCE` by its comparison.

`tests/test_brumby_model.py` runs these in tier-1 beside the program's.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from benchmark import refcheck, selftest                    # noqa: E402
from benchmark.families import brumby as family             # noqa: E402
from benchmark.run import ROOT, load_cell                   # noqa: E402
from benchmark.tests import retention_control               # noqa: E402
from benchmark.tests.test_lfm2_moe import prompt_of         # noqa: E402

CELL = "serve_doc_reask_retention"
TOL = dict(rtol=2e-4, atol=2e-4)


def tiny():
    cell = load_cell(CELL)
    selftest.shrink(cell)
    return cell["config"], family.program_config(cell["config"],
                                                 max_seq_len=512)


def engine(pc, seed, **kw):
    from ray_tpu.llm.engine import LLMEngine
    kw = {"max_batch": 2, "max_len": 512, "page_size": 16, "ckpt_rows": 6,
          "prefix_cache": True, **kw}
    return LLMEngine(pc, seed=seed, **kw)


def _file():
    return load_cell(CELL)["config"]


def _against_reference(eng, cfg, prompt, out, cached):
    got = eng.trace_logits(prompt, out[:-1], cached=cached)
    toks = jnp.asarray([prompt + out[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0][len(prompt) - 1:]
    return got, np.asarray(got["logits"]), np.asarray(ref)


# ---- the configuration and its sizes ---------------------------------------

def test_sizes_from_the_keys_are_the_published_ones():
    cut = _file()
    whole = dict(cut, num_hidden_layers=cut["published"]["num_hidden_layers"])
    assert round(family.param_count(whole) / 1e9, 2) == 14.77
    assert round(family.param_count(cut) / 1e9, 3) == 3.538
    assert round(family.weight_bytes(cut) / 1e9, 2) == 7.08
    for cfg in (cut, whole):
        assert family.program_config(cfg).param_count() \
            == family.param_count(cfg)
    pc = family.program_config(cut)
    assert pc.pattern == " ".join(["PF"] * 6) and pc.num_layers == 6
    assert pc.count("P") == 6 and pc.count("*") == pc.count("L") == 0
    assert pc.retention.expanded == 9216 and pc.qk_norm
    # the state: as published (the exact square) and as the program holds it
    assert family.state_bytes(cut) == 6 * 8 * 8256 * 129 * 4
    assert family.state_bytes(cut, published=False) \
        == 6 * pc.retention.state_bytes() == 6 * 8 * 9216 * 129 * 4
    from ray_tpu.models.transformer import state_bytes, state_chunk
    assert state_bytes(pc) == 6 * 38_043_648 and state_chunk(pc) == 128


def test_the_file_and_benchmark_json_name_the_same_cut():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "brumby-14b-base-l6")
    cut = _file()
    assert sorted(entry["reduced"]) == sorted(cut["reduced"]) == [
        "num_hidden_layers"]
    assert entry["source"] == cut["source"]
    want = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu",
            "hidden_size": 5120, "intermediate_size": 17408,
            "max_position_embeddings": 32768, "max_window_layers": 40,
            "model_type": "brumby", "num_attention_heads": 40,
            "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
            "rope_scaling": None, "rope_theta": 1000000,
            "sliding_window": None, "tie_word_embeddings": False,
            "use_sliding_window": False, "vocab_size": 151936}
    assert {k: cut[k] for k in want} == want        # the catalog's row
    assert cut["num_hidden_layers"] == 6
    spec = load_cell(CELL)["traffic"]
    assert spec["engine"]["ckpt_rows"] == 12 and spec["callers"] == 8


def test_program_config_refuses_what_the_kinds_cannot_express():
    for key, value in (("attention_bias", True), ("retention_degree", 3),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("use_sliding_window", True), ("hidden_act", "gelu")):
        with pytest.raises(ValueError):
            family.program_config(dict(_file(), **{key: value}))


def test_decode_step_bytes_against_a_hand_count():
    cut = _file()
    h, d = 5120, 128
    mix = h * 40 * d * 2 + h * 8 * d * 2 + h * 8
    assert mix == 62_955_520
    weights = 6 * (mix + 3 * h * 17408) + 151936 * h
    state = 6 * 8 * 8256 * 129 * 4
    assert state == 204_484_608                     # 204.5 MB a sequence
    want = 2 * weights + 2 * 7.9 * state
    assert family.decode_step_bytes(cut, 8 * 3000.0, 7.9) == want
    # nothing follows the tokens read; the state is the live sequences'
    assert family.decode_step_bytes(cut, 0.0, 7.9) == want
    assert family.retention_step_bytes(cut, 7.9) == 2 * 7.9 * state
    with pytest.raises(NotImplementedError):
        family.train_flops_per_token(cut, 2048)
    assert family.reference_loss is None


# ---- the reference against the program -------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_then_decode_through_the_state_is_the_full_forward(seed):
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    eng = engine(pc, seed)
    prompt = prompt_of(cfg, seed, 150)
    out = eng.generate([prompt], SamplingParams(max_tokens=8))[0]
    got, mine, ref = _against_reference(eng, cfg, prompt, out, False)
    assert got["from"] == 0 and got["chosen"] is None
    np.testing.assert_allclose(mine, ref, **TOL)    # logits, not tokens
    assert mine.argmax(-1).tolist() == out


@pytest.mark.parametrize("seed", [3, 4])
def test_a_hit_from_a_checkpoint_runs_tokens_again_and_is_the_reference(seed):
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    eng = engine(pc, seed)
    doc = prompt_of(cfg, seed, 150)             # checkpoints every 64 tokens
    first, second = doc + prompt_of(cfg, 40, 9), doc + prompt_of(cfg, 41, 12)
    eng.generate([first], SamplingParams(max_tokens=4))
    warm = eng.generate([second], SamplingParams(max_tokens=8))[0]
    st = eng.state_stats()
    assert st["tokens_recomputed"] == 144 - 128 \
        and st["hit_prompt_tokens"] == len(second)
    assert eng.retention_stats()["prefills"] == {"attention": 1, "chunked": 1}
    got, mine, ref = _against_reference(eng, cfg, second, warm, True)
    assert got["from"] == 128
    np.testing.assert_allclose(mine, ref, **TOL)
    assert mine.argmax(-1).tolist() == warm


def _served_twice(eng, prompt, tokens):
    """As `serve_cell._check` serves the check's prompt: cold, then again
    (a hit from the last checkpoint it kept)."""
    from ray_tpu.llm.engine import SamplingParams
    served = [eng.generate([prompt], SamplingParams(max_tokens=tokens))[0]
              for _ in range(2)]
    assert eng.prefix_cache_stats()["hits"] == 1
    return served


@pytest.mark.parametrize("how", ["zeroed", "stale"])
def test_a_wrong_checkpoint_fails_the_harness_under_the_seeded_gate(how):
    """With the gate's bias drawn like a matrix a head forgets in two
    tokens and any checkpoint reads as right; with the seeded gate
    (`retention.init_layer`) what a checkpoint holds still weighs after the
    tokens run again behind it.  It fails `refcheck.report`, the harness's
    own comparison, through the family's `check`; the plain check beside it
    reads the cold prefill alone and sees nothing."""
    cfg, pc = tiny()
    eng = engine(pc, 5)
    prompt = prompt_of(cfg, 5, 150)
    served = _served_twice(eng, prompt, 8)
    assert refcheck.report(eng, family, cfg, prompt, served)["ok"]
    row = eng._cache.lookup(prompt)[2]
    assert row >= 2
    other = eng._cache._rows[eng._cache._keys(prompt, 4)[3]]   # 64 tokens
    assert other not in (0, row)
    eng._ckpt = [{k: c[k].at[row].set(0 if how == "zeroed" else c[k][other])
                  for k in c} for c in eng._ckpt]
    report = refcheck.report(eng, family, cfg, prompt, served)
    tol = family.TOLERANCE
    assert not report["ok"] and report["plain"]["ok"]
    assert report["traced_from"] == [0, 128]
    assert report["logit_max"] > tol["logit_max"] \
        and report["logit_rms"] > tol["logit_rms"]


def test_a_second_ask_that_found_no_checkpoint_checks_nothing_of_one():
    cfg, pc = tiny()
    eng = engine(pc, 7, prefix_cache=False)
    prompt = prompt_of(cfg, 7, 150)
    from ray_tpu.llm.engine import SamplingParams
    served = [eng.generate([prompt], SamplingParams(max_tokens=4))[0]] * 2
    report = refcheck.report(eng, family, cfg, prompt, served)
    assert report["traced_from"] == [0, 0] and not report["ok"]
    assert report["logit_max"] < 1e-3 and report["plain"]["ok"]


def test_the_family_check_decides_and_both_controls_fail_it():
    """`refcheck.report` through the family's `check`: every row of both
    streams, the second from its checkpoint.  Both controls of
    `retention_control` (the weights in float8; the STATE in bfloat16, which
    shows only as the steps through it add up: 180 here) read past
    `TOLERANCE` by the same comparison."""
    cfg, pc = tiny()
    eng = engine(pc, 6)
    prompt = prompt_of(cfg, 6, 150)
    served = _served_twice(eng, prompt, 180)
    report = refcheck.report(eng, family, cfg, prompt, served)
    assert report["ok"] and report["owned_by"] == family.__name__
    assert report["plain"]["ok"] and report["traced_from"] == [0, 128]
    assert report["rows"] == 2 * 180 and report["logit_max"] < 1e-3
    tol = family.TOLERANCE
    refs = family.reference_rows(eng.params, prompt, served, cfg)
    short = [out[:8] for out in served]
    for control in ("state", "weights"):
        low = retention_control.control_engine(
            family, cfg, jax.tree.map(jnp.copy, eng.params), control,
            max_batch=2, max_len=512, page_size=16, ckpt_rows=6,
            prefix_cache=True, seed=6)
        _served_twice(low, prompt, 2)
        got = family.read(low, prompt, served, refs)
        assert got["traced_from"] == [0, 128]
        assert got["logit_max"] > tol["logit_max"] \
            or got["logit_rms"] > tol["logit_rms"], (control, got)
        if control == "state":      # and not by the eight tokens of old
            few = family.read(low, prompt, short,
                              [ref[:8] for ref in refs])
            assert few["logit_rms"] < tol["logit_rms"] < got["logit_rms"]
