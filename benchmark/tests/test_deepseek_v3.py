"""The `deepseek_v3` family's own cases (CPU, tiny sizes, seeded weights,
float32: program and reference then decide alike, and every tolerance is
rounding of float32 sums in another order):

- the sizes from the keys: the cut's 5.43B and the published 15.96B, the
  cache row's 1,152 B, `decode_step_bytes` against a hand count;
- the plain reference (expanded attention) against the program at `TINY`:
  a whole-prompt prefill, then decode through the latent cache (the
  program's absorbed form), and a stream served from a cached prefix;
- the check the family owns passes sound seeds, fails the float8 weights
  control, the float8 CACHE ROWS control, an altered token and a decision
  moved outside the tie zone.

`tests/test_moonlight_model.py` runs these in tier-1 beside the program's.
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
from benchmark.families import deepseek_v3 as family        # noqa: E402
from benchmark.run import ROOT, load_cell                   # noqa: E402
from benchmark.tests import latent_control                  # noqa: E402
from benchmark.tests.test_lfm2_moe import engine, prompt_of  # noqa: E402

CELL = "serve_doc_reask_mla"
TOL = dict(rtol=1e-4, atol=1e-4)


def tiny():
    cell = load_cell(CELL)
    selftest.shrink(cell)
    return cell["config"], family.program_config(cell["config"],
                                                 max_seq_len=512)


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
    whole = dict(cut, num_hidden_layers=cut["published"]["num_hidden_layers"])
    assert round(family.param_count(whole) / 1e9, 2) == 15.96
    assert round(family.param_count(whole, active=True) / 1e9, 2) == 2.91
    assert round(family.param_count(cut) / 1e9, 3) == 5.433
    assert round(family.weight_bytes(cut) / 1e9, 2) == 10.87
    for cfg in (cut, whole):
        assert family.program_config(cfg).param_count() \
            == family.param_count(cfg)
    assert family.cache_row_bytes(cut) == 1152
    pc = family.program_config(cut)
    assert pc.pattern == "LF " + " ".join(["LE"] * 8) and pc.num_layers == 9
    assert pc.count("L") == 9 and pc.cache_row == (1, 576)
    assert pc.routed.held == pc.routed.experts == 64 and pc.routed.top_k == 6
    assert pc.routed.shared_width == 2816 and pc.routed.gated


def test_the_file_and_benchmark_json_name_the_same_cut():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "moonlight-16b-a3b-l9")
    cut = _file()
    assert sorted(entry["reduced"]) == sorted(cut["reduced"]) == [
        "num_hidden_layers"]
    assert entry["source"] == cut["source"]
    want = {"hidden_size": 2048, "num_attention_heads": 16,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "intermediate_size": 11264, "n_routed_experts": 64,
            "moe_intermediate_size": 1408, "num_experts_per_tok": 6,
            "n_shared_experts": 2, "routed_scaling_factor": 2.446,
            "vocab_size": 163840, "q_lora_rank": None}
    assert {k: cut[k] for k in want} == want


def test_program_config_refuses_what_the_kinds_cannot_express():
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("topk_group", 4), ("scoring_func", "softmax"),
                       ("attention_bias", True), ("moe_layer_freq", 2),
                       ("rope_scaling", {"type": "yarn", "factor": 40}),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError):
            family.program_config(dict(_file(), **{key: value}))


def test_decode_step_bytes_against_a_hand_count():
    cut = _file()
    h = 2048
    attn = h * 16 * 192 + h * 576 + 512 * 16 * 256 + 16 * 128 * h
    assert attn == 13_762_560                       # 13.76M a layer
    shared = 3 * h * 2816
    outside = 9 * attn + 3 * h * 11264 + 8 * (h * 64 + shared) + 163840 * h
    expert = 3 * h * 1408
    assert 2 * expert == 17_301_504                 # 17.30 MB
    live, touched, seqs = 16 * 6000.0, 8 * 51.0, 15.9
    want = 2 * outside + touched * 2 * expert + live * 1152 * 9
    assert family.decode_step_bytes(cut, live, touched, seqs) == want
    assert family.latent_decode_bytes(cut, live) == live * 1152 * 9
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
    ref = family.reference_logits(eng.params, toks, cfg)[0][len(prompt) - 1:]
    np.testing.assert_allclose(got["logits"], ref, **TOL)
    assert np.asarray(ref).argmax(-1).tolist() == out       # greedy, served
    assert got["chosen"].shape == (2, len(prompt) + 7, 2)
    assert eng.decode_stats()["path"] == "reference"
    assert eng.decode_stats()["pool_row"] == "latent"
    assert eng.latent_stats()["form"] == "expanded"     # a whole prompt


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_check_passes_sound_seeds(seed):
    cfg, eng, prompt, served = served_twice(seed)
    assert eng.prefix_cache_stats()["hits"] == 1    # the second was a hit
    assert eng.latent_stats()["prefills"] == {"expanded": 1, "absorbed": 1}
    r = refcheck.report(eng, family, cfg, prompt, served)
    assert r["ok"] and r["owned_by"].endswith("deepseek_v3"), r
    assert r["forgiven"]["outside_zone"] == 0 and r["logit_max"] < 1e-3
    assert r["traced_from"] == [0, 64]      # cold, then after 4 cached pages
    assert "prefill_logit_max" in r["plain"]


def test_check_fails_the_float8_controls():
    """The weights in float8, and then the CACHE ROWS in float8: each is the
    precision below, and each must read as another result."""
    cfg, eng, prompt, served = served_twice(1)
    r = latent_control.readings(family, eng, prompt, served, cfg)
    assert r["sound"]["ok"], r["sound"]
    assert not r["control"]["ok"] \
        and r["control"]["logit_rms"] > family.TOLERANCE["logit_rms"], r
    assert not r["cache"]["ok"] \
        and r["cache"]["logit_rms"] > family.TOLERANCE["logit_rms"], r


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
    others = [e for e in range(cfg["n_routed_experts"])
              if e not in first][:3]
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


# ---- the readers this family brings ----------------------------------------

def test_latent_readers_on_hand_made_counters_and_a_cut():
    from benchmark.readers import (latent_decode_roofline, latent_expand,
                                   latent_read)
    lat = lambda **kw: {"latent": {"enabled": True, "row_bytes": 1152, **kw}}
    ctx = {"stats_before": lat(steps=100, rows_read=1_000_000,
                               rows_attended=50_000, rows_expanded=20_000),
           "stats_after": lat(steps=300, rows_read=1_000_000 + 200 * 96_000,
                              rows_attended=150_000, rows_expanded=60_000)}
    assert latent_expand.read(ctx, {}) == pytest.approx(40.0)
    assert latent_read.read(ctx, {}) == pytest.approx(96_000 * 1152 / 2 ** 20)
    # a program without the counter (the parent): nothing, and no raise
    for reader in (latent_expand, latent_read, latent_decode_roofline):
        assert reader.read({"stats_before": {}, "stats_after": {}}, {}) is None
    # two whole decode steps of two kernels each, one step cut short, and a
    # prefill whose operations do not count
    step = "jit__lambda(1)"
    ops, mods = [], [["jit_state_prefill(2)", 0, 900]]
    for i, t in enumerate((1_000, 3_000, 5_000)):
        mods.append([step, t, 1_500])
        ops += [["fusion.1", t, 400], ["paged_latent_attention.3", t + 400, 250],
                ["paged_latent_attention.5", t + 700, 150]]
    mods.pop()                                  # the last step did not end
    ops.append(["paged_latent_attention.3", 100, 700])      # in no step
    cut = {"devices": [{"id": 0, "ops": ops, "modules": mods}]}
    ms, steps = latent_decode_roofline.kernel_ms_per_step(cut)
    assert steps == 2 and ms == pytest.approx(400e-6)
    assert latent_decode_roofline.kernel_ms_per_step({"devices": []}) \
        == (None, 0)


def test_the_kernels_share_reads_only_this_runs_cut(tmp_path, monkeypatch):
    """The cut is found from this process's command line and taken only if
    it was written after the run's trace began; the live rows are those of
    the stretch's first second."""
    import sys
    import time
    from benchmark import trace
    from benchmark.readers import latent_decode_roofline as reader
    step = "jit__lambda(1)"
    cut = {"devices": [{"id": 0, "modules": [[step, 0, 2_000_000]], "ops": [
        ["paged_latent_attention.3", 100, 1_000_000]]}]}
    trace.save(cut, str(tmp_path / "trace_cut.json.gz"))
    now = time.time()
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "moonlight-16b-a3b-l9.json")))
    ctx = {"trace": {"window_s": 5.0, "t0": now - 60, "t1": now},
           "family": family, "config": config,
           "device": {"device_kind": "TPU v5 lite"},
           "records": [{"prompt_len": 6000, "token_times": [now - 100],
                        "end": now - 59.5},        # gone after half of it
                       {"prompt_len": 5000, "token_times": [now - 200]}]}
    argv = ["run", "--workload", "serve_doc_reask_mla", "--seed", "1",
            "--trace", "1"]
    monkeypatch.setattr(sys, "argv", argv + ["--out", str(tmp_path)])
    rows = 5001 + 0.5 * 6001
    want = 100 * rows * 1152 * 9 / 819e9 / 1e-3
    assert reader.read(ctx, {}) == pytest.approx(want, rel=1e-3)
    ctx["trace"]["t0"] = now + 60           # the cut is an earlier run's
    assert reader.read(ctx, {}) is None
    ctx["trace"]["t0"] = now - 60
    monkeypatch.setattr(sys, "argv", ["pytest"])    # another program's line
    assert reader.read(ctx, {}) is None
