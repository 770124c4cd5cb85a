"""The `granite_hybrid` family's own cases (CPU, tiny sizes, seeded weights,
float32: every tolerance is rounding of float32 sums in another order):

- the sizes from the keys: 3,191,396,096 parameters and 76,437,504 bytes of
  state a sequence, `decode_step_bytes` against a hand count, the period
  `program_config` finds;
- the files: the configuration is the catalog's row key for key with
  nothing reduced, the traffic file is `serve_chat.json` in every key but
  the rate, the engine, the check's length and the whys;
- the plain reference (its own equations, the recurrence token by token)
  against the program at `TINY`: a whole-prompt prefill, decode through the
  pages and the slot's state, a stream served from a state checkpoint, a
  prompt longer than one row block, a batch with a dead slot; each of the
  four multipliers alone;
- the family's own `check` decides `refcheck.report`, and the three
  controls of `granite_control` read past the sound reading.

`tests/test_granite_model.py` runs these in tier-1 beside the program's.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp                                     # noqa: E402

from benchmark import refcheck, selftest                    # noqa: E402
from benchmark.families import granite_hybrid as family     # noqa: E402
from benchmark.run import HERE, ROOT, load_cell             # noqa: E402
from benchmark.tests import granite_control                 # noqa: E402

CELL = "serve_chat_ssm"
TOL = dict(rtol=2e-4, atol=2e-5)


def tiny(max_len: int = 512):
    cell = load_cell(CELL)
    selftest.shrink(cell)
    return cell["config"], family.program_config(cell["config"],
                                                 max_seq_len=max_len)


def engine(pc, seed, **kw):
    from ray_tpu.llm.engine import LLMEngine
    kw = {"max_batch": 2, "max_len": 512, "page_size": 16, "kv_pages": 96,
          "ckpt_rows": 6, "prefix_cache": True, **kw}
    return LLMEngine(pc, seed=seed, **kw)


def prompt_of(cfg, seed, n=75):
    return np.random.default_rng([seed, 5]).integers(
        1, cfg["vocab_size"], n).tolist()


def _file():
    return load_cell(CELL)["config"]


def _against_reference(eng, cfg, prompt, out, cached=False):
    got = eng.trace_logits(prompt, out[:-1], cached=cached)
    toks = jnp.asarray([prompt + out[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0][len(prompt) - 1:]
    return got, np.asarray(got["logits"]), np.asarray(ref)


# ---- the configuration and its sizes ---------------------------------------

def test_sizes_from_the_keys_are_the_published_ones():
    cfg = _file()
    assert family.param_count(cfg) == 3_191_396_096
    assert family.weight_bytes(cfg) == 6_382_792_192
    assert family.state_bytes(cfg) == 76_437_504
    assert family.kv_bytes_per_token(cfg) == 8192
    z = family._sizes(cfg)
    assert (z["mamba"], z["attn"], z["ffn"], z["embed"]) == (
        25_849_280, 10_487_808, 50_333_696, 205_520_896)
    pc = family.program_config(cfg)
    assert pc.param_count() == family.param_count(cfg)
    assert pc.pattern == "MF MF MF MF MF *F MF MF MF MF" and pc.repeats == 4
    assert pc.num_layers == pc.pattern_layers == 40 and len(pc.kinds) == 80
    assert pc.count("M") == 36 and pc.count("*") == 4 and pc.count("F") == 40
    assert not pc.rope and pc.tie_embeddings and pc.cache_row == (8, 64)
    assert (pc.embedding_multiplier, pc.residual_multiplier,
            pc.attention_scale, pc.logit_divisor) == (12.0, 0.22, 1 / 64, 8.0)
    assert pc.mamba == dataclasses.replace(
        pc.mamba, num_heads=64, head_dim=64, state=128, groups=1, chunk=256,
        conv_kernel=4, state_dtype="float32")
    from ray_tpu.models.transformer import state_bytes, state_chunk
    assert state_bytes(pc) == family.state_bytes(cfg)
    assert state_chunk(pc) == 256


@pytest.mark.parametrize("kinds, period", [
    ("MMMMM*MMMM" * 4, "MMMMM*MMMM"), ("M*M" * 2, "M*M"), ("MM*", "MM*"),
    ("MMMM", "M"), ("M*MM*", "M*MM*")])
def test_the_period_is_the_shortest_that_spells_the_stack(kinds, period):
    assert family.period_of(kinds) == period


def test_the_file_is_the_catalogs_row_and_nothing_is_reduced():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    cfg = _file()
    assert entry["reduced"] == [] and cfg["reduced"] == {}
    assert entry["source"] == cfg["source"]
    want = {"attention_bias": False, "attention_multiplier": 0.015625,
            "embedding_multiplier": 12, "hidden_act": "silu",
            "hidden_size": 2048, "intermediate_size": 8192,
            "logits_scaling": 8, "mamba_chunk_size": 256,
            "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
            "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
            "mamba_n_heads": 64, "mamba_proj_bias": False,
            "max_position_embeddings": 131072,
            "model_type": "granitemoehybrid",
            "normalization_function": "rmsnorm", "num_attention_heads": 32,
            "num_experts_per_tok": 0, "num_hidden_layers": 40,
            "num_key_value_heads": 8, "num_local_experts": 0,
            "position_embedding_type": "nope", "residual_multiplier": 0.22,
            "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
            "shared_intermediate_size": 8192, "tie_word_embeddings": True,
            "vocab_size": 100352}
    assert {k: cfg[k] for k in want} == want        # the catalog's row
    assert cfg["layer_types"] == (["mamba"] * 5 + ["attention"]
                                  + ["mamba"] * 4) * 4


def test_the_traffic_is_serve_chats_but_for_the_rate_and_the_engine():
    with open(os.path.join(HERE, "traffic", "serve_chat.json")) as f:
        chat = json.load(f)
    with open(os.path.join(HERE, "traffic", f"{CELL}.json")) as f:
        mine = json.load(f)
    own = {"rate_hz", "rate_found", "engine", "check_output_tokens"}
    same = [k for k in chat if k not in own and "why" not in k]
    assert {k: mine[k] for k in same} == {k: chat[k] for k in same}
    assert sorted(same) == ["check_prompt_len", "drain_s", "kind",
                            "layout_seed", "output_len", "prompt_len",
                            "ramp_s", "stratum_slots", "trace_s"]
    assert not [k for k in mine if k not in chat and "why" not in k]
    assert mine["engine"] == {"max_batch": 32, "max_len": 2048,
                              "page_size": 16, "kv_pages": 4096,
                              "ckpt_rows": 32}


def test_program_config_refuses_what_the_kinds_cannot_express():
    for key, value in (("attention_bias", True), ("num_local_experts", 8),
                       ("tie_word_embeddings", False),
                       ("position_embedding_type", "rope"),
                       ("mamba_proj_bias", True), ("hidden_act", "gelu"),
                       ("shared_intermediate_size", 4096),
                       ("layer_types", ["mamba"] * 39 + ["window"])):
        with pytest.raises(ValueError):
            family.program_config(dict(_file(), **{key: value}))


def test_decode_step_bytes_against_a_hand_count():
    cfg = _file()
    h = 2048
    mamba = h * (4096 + 4096 + 2 * 128 + 64) + 4096 * h
    attn = h * 64 * (2 * 32 + 2 * 8)
    weights = 36 * mamba + 4 * attn + 40 * 3 * h * 8192 + 100352 * h
    assert 2 * weights == 6_380_584_960         # every matrix, the head once
    want = 2 * weights + 9000.0 * 8192 + 2 * 19.5 * 76_437_504
    assert family.decode_step_bytes(cfg, 9000.0, 19.5) == want
    with pytest.raises(NotImplementedError):
        family.train_flops_per_token(cfg, 2048)
    assert family.reference_loss is None


# ---- the reference against the program -------------------------------------

@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_then_decode_through_pages_and_state_is_the_reference(seed):
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    assert pc.pattern == "MF *F MF" and pc.repeats == 2
    eng = engine(pc, seed)
    prompt = prompt_of(cfg, seed)
    out = eng.generate([prompt], SamplingParams(max_tokens=8))[0]
    got, mine, ref = _against_reference(eng, cfg, prompt, out)
    np.testing.assert_allclose(mine, ref, **TOL)
    assert ref.argmax(-1).tolist() == out           # greedy, served
    assert got["from"] == 0 and got["chosen"] is None
    # the second ask starts from the checkpoint at 64 and attends its pages
    again = eng.generate([prompt], SamplingParams(max_tokens=8))[0]
    assert again == out and eng.prefix_cache_stats()["hits"] == 1
    got, mine, ref = _against_reference(eng, cfg, prompt, out, cached=True)
    assert got["from"] == 64
    np.testing.assert_allclose(mine, ref, **TOL)


def test_a_prompt_longer_than_one_row_block_is_the_reference():
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny(2048)
    eng = engine(pc, 3, max_len=2048, kv_pages=160)
    prompt = prompt_of(cfg, 3, 1100)        # 3 of the bucket's 4 row blocks
    out = eng.generate([prompt], SamplingParams(max_tokens=3))[0]
    st = eng.prefill_stats()
    assert (st["row_blocks_run"], st["row_blocks_dense"]) == (3, 4)
    _, mine, ref = _against_reference(eng, cfg, prompt, out)
    np.testing.assert_allclose(mine, ref, **TOL)
    assert ref.argmax(-1).tolist() == out


def test_a_batch_with_a_dead_slot_is_the_reference():
    """Three slots, two requests of unlike lengths: one slot is never live,
    and one dies while the other decodes on."""
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    eng = engine(pc, 4, max_batch=3)
    prompts = [prompt_of(cfg, 4, 75), prompt_of(cfg, 5, 40)]
    ids = [eng.add_request(p, SamplingParams(max_tokens=n))
           for p, n in zip(prompts, (12, 4))]
    done = {}
    while eng.has_unfinished():
        for req in eng.step():
            done[req.req_id] = req.out
    for rid, prompt in zip(ids, prompts):
        toks = jnp.asarray([prompt + done[rid][:-1]], jnp.int32)
        ref = np.asarray(family.reference_logits(eng.params, toks, cfg)[0])
        assert ref[len(prompt) - 1:].argmax(-1).tolist() == done[rid]
    ms = eng.mamba_stats()
    assert ms["steps"] == 11 and ms["rows_stepped"] == 3 * 2 + 8 * 1


NEUTRAL = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "attention_multiplier": 0.25, "logits_scaling": 1.0}
FIELD = {"embedding_multiplier": "embedding_multiplier",
         "residual_multiplier": "residual_multiplier",
         "attention_multiplier": "attention_scale",
         "logits_scaling": "logit_divisor"}


@pytest.mark.parametrize("key", sorted(NEUTRAL))
def test_each_multiplier_alone_changes_the_logits_as_the_reference_has_it(key):
    """The program with ONE multiplier set and the other three ABSENT (None)
    against the reference with the others at their neutral values (1, and
    1 / sqrt(16) for the scores)."""
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    absent = dataclasses.replace(pc, **{f: None for f in FIELD.values()})
    one = dataclasses.replace(absent, **{FIELD[key]: getattr(pc, FIELD[key])})
    prompt = prompt_of(cfg, 6, 40)
    rows = {}
    for name, config in (("absent", absent), ("one", one)):
        eng = engine(config, 6)
        out = eng.generate([prompt], SamplingParams(max_tokens=4))[0]
        ref_cfg = dict(cfg, **NEUTRAL)
        if name == "one":
            ref_cfg[key] = cfg[key]
        _, mine, ref = _against_reference(eng, ref_cfg, prompt, out)
        np.testing.assert_allclose(mine, ref, **TOL)
        rows[name] = mine[0]
    assert np.abs(rows["one"] - rows["absent"]).max() > 1e-3


# ---- the check, and its controls -------------------------------------------

def _served(seed, tokens=24):
    from ray_tpu.llm.engine import SamplingParams
    cfg, pc = tiny()
    eng = engine(pc, seed)
    prompt = prompt_of(cfg, seed)
    served = [eng.generate([prompt], SamplingParams(max_tokens=tokens))[0]
              for _ in range(2)]
    return cfg, eng, prompt, served


def test_the_familys_check_decides_the_report():
    cfg, eng, prompt, served = _served(7)
    report = refcheck.report(eng, family, cfg, prompt, served)
    assert report["ok"] and report["owned_by"] == family.__name__
    assert report["traced_from"] == [0, 64] and report["rows"] == 48
    assert report["plain"]["ok"]
    # a served token that was not the model's fails it by the margin
    wrong = [list(served[0]), list(served[1])]
    wrong[1][5] = (wrong[1][5] + 1) % cfg["vocab_size"]
    assert not refcheck.report(eng, family, cfg, prompt, wrong)["ok"]
    # and a second stream that started from no checkpoint checked none
    cold = dict(family.check(eng, prompt, served, cfg), traced_from=[0, 0])
    assert cold["ok"] and not all(at > 0 for at in cold["traced_from"][1:])


def test_every_control_reads_past_the_sound_reading():
    cell = load_cell(CELL)
    selftest.shrink(cell)
    cell["traffic"].update(check_output_tokens=24)
    cell["traffic"]["engine"].update(max_batch=2)
    line = granite_control.readings(cell["family"], cell["config"],
                                    cell["traffic"], 8)
    assert line["hit_on_second"] and line["sound"]["traced_from"] == [0, 64]
    sound = line["sound"]["logit_rms"]
    assert sound < 1e-6
    for control in granite_control.CONTROLS:
        assert line[control]["logit_rms"] > 20 * sound, (control, line)
    # the cold stream reads no cached row: the cache control is the hit's
    rows = line["cache"]["by_row"]
    assert rows[0] == line["sound"]["by_row"][0] and rows[1][0] > 20 * sound
