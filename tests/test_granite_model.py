"""A pattern that repeats a period is scanned over its repeats
(models/transformer.py: `TransformerConfig.repeats`, `run_pattern`,
`zero_states`), the four multipliers a family may publish,
the `mamba` counter (llm/programs.py: `COUNTED`) and the three readers that
read it; and, by name, the `granite_hybrid` family's own cases
(benchmark/tests/test_granite_hybrid.py: the engine against the plain
reference).  CPU, tiny sizes, seeded weights, float32.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.readers import decode_roofline_ssm, scan_pad, ssm_step
from benchmark.tests.test_granite_hybrid import *           # noqa: F401,F403
from benchmark.tests.test_granite_hybrid import engine, prompt_of, tiny
from ray_tpu.llm import programs
from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.models.transformer import (TransformerConfig, init_params,
                                        state_axis, zero_states)

PERIOD = "MF *F MF"
TOL = dict(rtol=1e-5, atol=1e-6)


def _pair():
    """(scanned, walked): one period x 4, and the same 4 periods spelled
    out; the same seed gives both the same weights."""
    _, pc = tiny()
    scanned = dataclasses.replace(pc, pattern=PERIOD, repeats=4,
                                  num_layers=12)
    walked = dataclasses.replace(pc, pattern=" ".join([PERIOD] * 4),
                                 repeats=1, num_layers=12)
    return scanned, walked


def _unstacked(trees, repeats):
    """A scanned engine's state trees (one a stateful block of the period,
    the repeats in front) as the walked engine orders them: repeat-major."""
    return [jax.tree.map(lambda a: a[r], t)
            for r in range(repeats) for t in trees]


def test_the_spelling_is_a_period_and_how_often():
    scanned, walked = _pair()
    assert scanned.kinds == walked.kinds == "MF*FMF" * 4
    assert scanned.period == "MF*FMF" and walked.period == walked.kinds
    assert scanned.pattern_layers == walked.pattern_layers == 12
    assert scanned.param_count() == walked.param_count()
    assert state_axis(scanned) == 1 and state_axis(walked) == 0
    ps = init_params(scanned, jax.random.key(0))
    pw = init_params(walked, jax.random.key(0))
    assert len(ps["layers"]) == 6 and len(pw["layers"]) == 24
    for j, block in enumerate(ps["layers"]):    # repeat r: block r x 6 + j
        again = jax.tree.map(lambda *a: jnp.stack(a), *pw["layers"][j::6])
        for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(block)):
            assert a.shape[0] == 4 and np.array_equal(a, b)
    rec = zero_states(scanned, 3)
    assert len(rec) == 2 and rec[0]["ssm"].shape[:2] == (4, 3)
    assert len(zero_states(walked, 3)) == 8
    with pytest.raises(ValueError):
        init_params(dataclasses.replace(scanned, num_layers=3),
                    jax.random.key(0))


def test_a_period_scanned_is_the_periods_spelled_out_and_walked():
    """Logits, pools, per-slot state and checkpoints, after a prompt that
    passes two checkpoint boundaries, decode steps beside a dead slot, and
    a second ask from a checkpoint."""
    scanned, walked = _pair()
    cfg, _ = tiny()
    engines = [engine(c, 1, max_batch=3) for c in (scanned, walked)]
    prompts = [prompt_of(cfg, 1, 150), prompt_of(cfg, 2, 40)]
    outs, traces = [], []
    for eng in engines:
        outs.append(eng.generate(prompts, SamplingParams(max_tokens=6))
                    + eng.generate(prompts[:1], SamplingParams(max_tokens=6)))
        traces.append([eng.trace_logits(prompts[0], outs[-1][0][:-1],
                                        cached=c) for c in (False, True)])
    assert outs[0] == outs[1]
    es, ew = engines
    assert es.prefix_cache_stats() == ew.prefix_cache_stats()
    assert es.prefix_cache_stats()["hits"] == 2     # the re-ask, its trace
    for a, b in zip(*traces):
        assert a["from"] == b["from"]
        np.testing.assert_allclose(a["logits"], b["logits"], **TOL)
    assert traces[0][1]["from"] == 128
    np.testing.assert_allclose(es._pk, ew._pk, **TOL)
    np.testing.assert_allclose(es._pv, ew._pv, **TOL)
    for name in ("rec", "ckpt"):
        mine = es._dev["rec"] if name == "rec" else es._ckpt
        theirs = ew._dev["rec"] if name == "rec" else ew._ckpt
        for a, b in zip(_unstacked(mine, 4), theirs):
            for key in a:
                np.testing.assert_allclose(a[key], b[key], err_msg=name,
                                           **TOL)
    assert np.abs(np.asarray(es._ckpt[0]["ssm"][:, 2:])).max() > 0


def _lowered(cfg, what):
    """The StableHLO text of one engine program at small shapes."""
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    B, P, page = 2, 4, 16
    pools = programs.make_pools(cfg, 9, page, None)
    if what == "decode":
        state = {"slots": jnp.zeros((B, P + 4), jnp.int32),
                 "rng": jax.random.key(0), "rec": zero_states(cfg, B)}
        return jax.jit(lambda p, pk, pv, s, u: programs._decode_fn(
            p, pk, pv, s, u, cfg, page, None)).lower(
            params, *pools, state, jnp.zeros((B, P + 5), jnp.int32)).as_text()
    return jax.jit(lambda p, pk, pv, pg, t, ck: programs._state_prefill_fn(
        p, pk, pv, pg, t, 0, 20, ck, 0, cfg, page, 32)).lower(
        params, *pools, jnp.zeros(P, jnp.int32),
        jnp.zeros((1, 64), jnp.int32), zero_states(cfg, 3)).as_text()


@pytest.mark.parametrize("what", ["decode", "prefill"])
def test_a_scanned_period_is_traced_once_and_one_repeat_not_scanned(what):
    """One traced period however deep the stack: the scanned program holds
    one period's matrix products and ONE loop more than the period walked
    (the Mamba layers' own chunk scans are loops too); a repeat count of 1
    adds no loop."""
    scanned, walked = _pair()
    once = dataclasses.replace(scanned, repeats=1, num_layers=3)
    texts = {name: _lowered(c, what) for name, c in (
        ("scanned", scanned), ("walked", walked), ("once", once))}
    dots = {k: t.count("stablehlo.dot_general") for k, t in texts.items()}
    loops = {k: t.count("stablehlo.while") for k, t in texts.items()}
    assert dots["scanned"] == dots["once"]
    assert dots["walked"] == 4 * dots["once"] - 3      # the head, once
    # (a period's own loops: its Mamba layers' chunk scans; a decode step
    # has two more outside the stack)
    assert loops["walked"] - loops["once"] == 3 * once.count("M")
    assert loops["scanned"] == loops["once"] + 1


def test_a_scanned_periods_narrow_attention_through_the_paged_kernel(
        monkeypatch):
    """A period with an attention layer of 64-wide heads (KV 2 x 64: the
    pool's row is 128 lanes), scanned twice: the decode step's logits with
    the paged kernel in the scan's body (the chooser steered here, as
    tests/test_llm.py does: there is no TPU; the kernel interpreted, two
    pages a chunk, the stacked pool and the repeat's traced layer number)
    against the plain function's, over a prompt that ends inside its fifth
    page and 2 x page + 3 decoded tokens beside two dead slots."""
    import functools

    from ray_tpu.ops import paged_attention as pa

    cfg, pc = tiny()
    narrow = dataclasses.replace(pc, pattern=PERIOD, repeats=2, num_layers=6,
                                 num_heads=4, num_kv_heads=2, head_dim=64)
    prompt, tokens = prompt_of(cfg, 3, 75), prompt_of(cfg, 4, 35)
    traces = []
    for path in ("reference", "pallas"):
        if path == "pallas":
            calls, real = [], pa._paged_lanes_pallas
            def lanes_kernel(*a, **k):
                calls.append(a[1].shape)
                return real(*a, **k, interpret=True)
            monkeypatch.setattr(pa, "_paged_lanes_pallas", lanes_kernel)
            monkeypatch.setattr(pa, "decode_path", lambda *shapes: "pallas")
            monkeypatch.setattr(pa, "_lanes_chunk_pages",
                                lambda page, width: 2)
        eng = engine(narrow, 1, max_batch=3)
        assert eng.decode_stats()["pool_row"] == "lanes"
        assert eng._pk.shape == (2, 97, 16, 128)
        traces.append(eng.trace_logits(prompt, tokens)["logits"])
    assert calls == [(2, 97, 16, 128)]          # one site: the scan's body
    assert traces[0].shape == (36, cfg["vocab_size"])
    np.testing.assert_allclose(traces[1], traces[0], rtol=2e-4, atol=2e-5)
    assert np.abs(traces[0]).max() > 1e-2


@pytest.mark.parametrize("field", ["embedding_multiplier",
                                   "residual_multiplier", "attention_scale",
                                   "logit_divisor"])
@pytest.mark.parametrize("what", ["decode", "prefill"])
def test_a_multiplier_that_is_absent_adds_no_operation(what, field):
    """Set, each multiplier costs operations the absent one does not: the
    program without any is shorter by exactly what the four add."""
    _, pc = tiny()
    fields = ("embedding_multiplier", "residual_multiplier",
              "attention_scale", "logit_divisor")
    absent = dataclasses.replace(pc, **{f: None for f in fields})
    one = dataclasses.replace(absent, **{field: getattr(pc, field)})
    ops = {name: _lowered(c, what).count(" = stablehlo.")
           for name, c in (("absent", absent), ("one", one))}
    if field == "attention_scale":      # a multiply in the place of a divide
        assert ops["one"] <= ops["absent"]
    else:
        assert ops["one"] > ops["absent"]
    text = _lowered(absent, what)
    assert text == _lowered(dataclasses.replace(absent), what)


def test_checkpoints_lie_512_tokens_apart_at_a_chunk_of_256():
    _, pc = tiny()
    for chunk, every in ((8, 32), (128, 512), (256, 512), (512, 512),
                         (1024, 4096)):
        cfg = dataclasses.replace(pc, mamba=dataclasses.replace(
            pc.mamba, chunk=chunk))
        eng = LLMEngine(cfg, max_batch=1, max_len=4096, page_size=16,
                        kv_pages=8, ckpt_rows=2, prefix_cache=True,
                        params=jax.eval_shape(
                            lambda: init_params(cfg, jax.random.key(0))))
        assert eng._every == every, chunk


# ---- the counter and its readers -------------------------------------------

def test_the_mamba_counter_counts_live_rows_and_the_rows_a_scan_ran():
    cfg, pc = tiny(2048)
    eng = engine(pc, 9, max_len=2048, kv_pages=160)
    zero = eng.mamba_stats()
    assert zero == {"enabled": True, "layers": 4,
                    "row_bytes": 4 * (8 * 32 * 16 * 4 + 3 * 288 * 4),
                    "path": "ssd", "slots": 2,
                    "rows_stepped": 0, "step_rows_stepped": 0,
                    "rows_moved": 0,
                    "prefill_rows": 0, "prefill_rows_run": 0, "steps": 0}
    eng.generate([prompt_of(cfg, 9, 40), prompt_of(cfg, 10, 75)],
                 SamplingParams(max_tokens=5))
    eng.generate([prompt_of(cfg, 11, 1100)], SamplingParams(max_tokens=2))
    st = eng.mamba_stats()
    assert st["prefill_rows"] == 40 + 75 + 1100
    assert st["prefill_rows_run"] == 64 + 128 + 3 * 512     # blocks of 2,048
    assert st["steps"] == 4 + 1 and st["rows_stepped"] == 4 * 2 + 1
    assert st["step_rows_stepped"] == 1
    # The reference path's program moves every slot's state, live or not.
    assert st["rows_moved"] == (4 + 1) * 2
    dense = LLMEngine(TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=1,
        num_heads=2, num_kv_heads=2, dtype=jnp.float32), max_batch=1,
        max_len=32)
    assert dense.mamba_stats() == {"enabled": False}


def test_the_three_readers_read_the_counter_and_nothing_without_it():
    before = {"mamba": {"enabled": True, "row_bytes": 76_437_504,
                        "steps": 100, "rows_stepped": 1000,
                        "prefill_rows": 5000, "prefill_rows_run": 8000}}
    after = {"mamba": {"enabled": True, "row_bytes": 76_437_504,
                       "steps": 300, "rows_stepped": 5000,
                       "prefill_rows": 35000, "prefill_rows_run": 48000}}
    ctx = {"stats_before": before, "stats_after": after}
    assert ssm_step.read(ctx, {}) == 20 * 76_437_504 * 2 / 2 ** 20
    assert scan_pad.read(ctx, {}) == pytest.approx(25.0)
    for reader in (ssm_step, scan_pad, decode_roofline_ssm):
        for none in ({}, {"stats_before": {"mamba": {"enabled": False}},
                          "stats_after": {"mamba": {"enabled": False}}}):
            assert reader.read(none, {}) is None
    same = {"stats_before": after, "stats_after": after}
    assert ssm_step.read(same, {}) is None and scan_pad.read(same, {}) is None
    # the roofline share: the family's bytes over the bandwidth and the tick
    from benchmark.families import granite_hybrid as family
    from benchmark.run import load_cell
    cfg = load_cell("serve_chat_ssm")["config"]
    one = {"token_times": [1.0, 2.0], "end": 9.0, "prompt_len": 300}
    traced = {"stats_after": after, "family": family, "config": cfg,
              "device": {"device_kind": "TPU v5 lite"}, "records": [one] * 20,
              "trace": {"t0": 2.0, "t1": 70.0, "window_s": 5.0, "programs_ms": {
                  "jit__lambda(1)": [12.0, 12.0, 13.0],
                  "jit__lambda(2)": [1.0], "jit_state_prefill(3)": [30.0]}}}
    assert decode_roofline_ssm.read(traced, {}) == pytest.approx(
        100 * family.decode_step_bytes(cfg, 20 * 302, 20) / 819e9 / 12e-3)
    assert 100 * family.decode_step_bytes(cfg, 20 * 302, 20) / 819e9 \
        == pytest.approx(1.158, abs=1e-3)           # ms: 6.38 GB + 20 x 153 MB


def test_a_waves_first_tokens_are_sampled_in_power_of_two_programs(monkeypatch):
    """The eager programs that sample an admission wave's first tokens are
    compiled a wave SIZE: the wave is filled to a power of two, so 32 slots
    warm six sizes and not thirty-two."""
    import ray_tpu.llm.engine as engine_mod
    _, pc = tiny()
    eng = engine(pc, 12, max_batch=4)
    sizes, stack = [], jnp.stack
    monkeypatch.setattr(engine_mod.jnp, "stack",
                        lambda rows: sizes.append(len(rows)) or stack(rows))
    rng = np.random.default_rng(0)
    for n in range(1, 10):
        rows = [jnp.asarray(rng.standard_normal(pc.vocab_size), jnp.float32)
                for _ in range(n)]
        got = eng._sample_batch(rows, [SamplingParams()] * n)
        assert got == [int(np.argmax(r)) for r in rows]
        hot = eng._sample_batch(rows, [SamplingParams(temperature=0.7)] * n)
        assert len(hot) == n
    assert sizes == [w for w in (1, 2, 4, 4, 8, 8, 8, 8, 16) for _ in (0, 1)]
