"""The `lfm2_moe` family's own cases (CPU, tiny sizes, seeded weights,
float32: program and reference then decide alike, and every tolerance is
rounding of float32 sums in another order):

- the plain reference against the program at `TINY`: prefill, then decode
  through the cache and the slots' convolution tails, and a stream served
  from a cached prefix and a state checkpoint;
- the check the family owns passes sound seeds, fails the float8 control,
  an altered token and a decision moved outside the tie zone;
- `decode_step_bytes` against a hand count, and the sizes from the keys.

`tests/test_lfm2_model.py` runs these in tier-1 beside the program's own.
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
from benchmark.families import lfm2_moe as family           # noqa: E402
from benchmark.run import ROOT, load_cell                   # noqa: E402

CELL = "serve_doc_reask_moe"
TOL = dict(rtol=1e-4, atol=1e-4)


def tiny():
    cell = load_cell(CELL)
    selftest.shrink(cell)
    return cell["config"], family.program_config(cell["config"],
                                                 max_seq_len=512)


def engine(pc, seed, **kw):
    from ray_tpu.llm.engine import LLMEngine
    kw = {"max_batch": 2, "max_len": 512, "page_size": 16, "kv_pages": 64,
          "prefix_cache": True, **kw}
    return LLMEngine(pc, seed=seed, **kw)


def prompt_of(cfg, seed, n=75):
    return np.random.default_rng([seed, 5]).integers(
        1, cfg["vocab_size"], n).tolist()


def served_twice(seed):
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    eng = engine(pc, seed)
    prompt = prompt_of(cfg, seed)
    served = [eng.generate([prompt], SamplingParams(max_tokens=8))[0]
              for _ in range(2)]
    return cfg, eng, prompt, served


# ---- the configuration and its sizes ---------------------------------------

def _file():
    return load_cell(CELL)["config"]


def test_sizes_from_the_keys_are_the_published_ones():
    cut = _file()
    whole = dict(cut, **{k: v for k, v in cut["published"].items()
                         if k != "parameters"})
    assert round(family.param_count(whole) / 1e9, 2) == 23.84
    assert round(family.param_count(whole, active=True) / 1e9, 2) == 2.33
    assert round(family.param_count(cut) / 1e9, 3) == 5.178
    assert round(family.weight_bytes(cut) / 1e9, 2) == 10.36
    assert family.program_config(cut).param_count() == family.param_count(cut)
    assert cut["layer_types"] == whole["layer_types"][1:10]
    assert family.state_bytes(cut) == 7 * 2 * 2048 * 2 == 57344
    pc = family.program_config(cut)
    assert pc.pattern == "CF *E CE CE CE *E CE CE CE" and pc.num_layers == 9
    assert pc.count("*") == 2 and pc.routed.held == pc.routed.experts == 64


def test_the_file_and_benchmark_json_name_the_same_cut():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b-l9")
    cut = _file()
    assert sorted(entry["reduced"]) == sorted(cut["reduced"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers"]
    assert entry["source"] == cut["source"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "vocab_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache"):
        assert key not in cut["reduced"]
    assert (cut["num_experts"], cut["vocab_size"]) == (64, 65536)


def test_program_config_refuses_what_the_kinds_cannot_express():
    for key, value in (("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False),
                       ("layer_types", ["conv"] * 8 + ["sliding_attention"]),
                       ("num_hidden_layers", 8)):
        with pytest.raises(ValueError):
            family.program_config(dict(_file(), **{key: value}))


def test_decode_step_bytes_against_a_hand_count():
    cut = _file()
    h, w = 2048, 1536
    conv = h * 3 * h + h * h                    # W_in, W_out
    attn = h * 64 * (32 + 8 + 8 + 32)           # q, k, v, o
    outside = 7 * conv + 2 * attn + 3 * h * 11776 + 8 * h * 64 + 65536 * h
    expert = 3 * h * w
    assert 2 * expert == 18_874_368             # 18.87 MB
    live, touched, seqs = 8 * 3000.0, 8 * 25.8, 7.9
    want = 2 * outside + touched * 2 * expert + live * 4096 \
        + 2 * seqs * 57344
    assert family.decode_step_bytes(cut, live, touched, seqs) == want
    # never all experts: twice the touched experts, that many more bytes
    more = family.decode_step_bytes(cut, live, 2 * touched, seqs)
    assert more - want == pytest.approx(touched * 2 * expert, rel=1e-12)


# ---- the reference against the program -------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(seed):
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    eng = engine(pc, seed)
    prompt = prompt_of(cfg, seed)
    out = eng.generate([prompt], SamplingParams(max_tokens=8))[0]
    got = eng.trace_logits(prompt, out[:-1])
    toks = jnp.asarray([prompt + out[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0, len(prompt) - 1:]
    np.testing.assert_allclose(got["logits"], ref, **TOL)
    assert np.asarray(ref).argmax(-1).tolist() == out       # greedy, served
    assert got["chosen"].shape == (3, len(prompt) + 7, 2)
    assert eng.decode_stats()["path"] == "reference"
    assert eng.prefill_stats()["path"] == "xla"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_check_passes_sound_seeds(seed):
    cfg, eng, prompt, served = served_twice(seed)
    assert eng.prefix_cache_stats()["hits"] == 1    # the second was a hit
    r = refcheck.report(eng, family, cfg, prompt, served)
    assert r["ok"] and r["owned_by"].endswith("lfm2_moe"), r
    assert r["forgiven"]["outside_zone"] == 0 and r["logit_max"] < 1e-3
    assert r["traced_from"] == [0, 64]      # cold, then from the checkpoint
    assert "prefill_logit_max" in r["plain"]


def test_check_fails_the_float8_control():
    cfg, eng, prompt, served = served_twice(1)
    r = family.check(eng, prompt, served, cfg, "float8_e4m3fn")
    assert not r["ok"] and r["logit_rms"] > family.TOLERANCE["logit_rms"], r


def test_check_fails_an_altered_token():
    cfg, eng, prompt, served = served_twice(2)
    worst = int(np.argmin(np.asarray(eng._run_prefill(prompt)[0])))
    r = refcheck.report(eng, family, cfg, prompt,
                        [[worst] + served[0][1:], served[1]])
    assert not r["ok"] and r["margin"] > family.TOLERANCE["margin"], r


def test_check_fails_a_decision_outside_the_zone():
    cfg, eng, prompt, served = served_twice(3)
    first = np.asarray(eng.trace_logits(prompt, served[0][:-1])
                       ["chosen"][0, 0]).tolist()
    others = [e for e in range(cfg["num_experts"]) if e not in first][:3]
    reports = []
    for moved in others:        # an expert the program did not choose there

        class Flipped:
            params = eng.params
            _run_prefill = eng._run_prefill

            @staticmethod
            def trace_logits(p, toks, cached=False, moved=moved):
                got = eng.trace_logits(p, toks, cached)
                if not cached:
                    got["chosen"] = got["chosen"].at[0, 0, 0].set(moved)
                return got
        reports.append(refcheck.report(Flipped, family, cfg, prompt, served))
    assert not any(r["ok"] for r in reports), reports
    assert any(r["forgiven"]["outside_zone"] > 0 for r in reports), reports
