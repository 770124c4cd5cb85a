"""A stack whose layer is TWO residual halves, an operator and a feed-forward
(models/transformer.py: `C` gated short convolution, models/shortconv.py;
`F` the SwiGLU feed-forward alone; `*` attention with a q/k head norm and
rotation; `E` in its gated form, models/routed.py), its state in the engine
(llm/engine.py: the convolution tails as the slot's recurrent state and as
checkpoints in the prefix cache, with no word of Mamba), and the benchmark
family that holds it to a plain float32 reference
(benchmark/families/lfm2_moe.py, whose own cases run here too).  CPU, tiny
sizes, seeded weights, float32.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.tests.test_lfm2_moe import *                 # noqa: F401,F403
from benchmark.tests.test_lfm2_moe import (TOL, engine, prompt_of, tiny)
from ray_tpu.llm.engine import SamplingParams
from ray_tpu.models import routed, shortconv
from ray_tpu.models.transformer import (PRESETS, TransformerConfig, block_out,
                                        block_qkv, ffn_block, init_params,
                                        rope_angles, state_bytes, state_chunk)

CONV = shortconv.ShortConvDims(kernel=3, chunk=8)
GATED = routed.RoutedDims(experts=16, held=4, held_from=4, top_k=3, latent=0,
                          width=24, shared_width=0, scale=1.0, gated=True)


# ---- the short convolution -------------------------------------------------

def _plain_conv(lp, h, K=3):
    """The operator in numpy over whole rows h (S, E) from nothing read."""
    lp = jax.tree.map(lambda a: np.asarray(a, np.float64), lp)
    b, c, u = np.split(np.asarray(h, np.float64) @ lp["w_in"], 3, -1)
    z = np.concatenate([np.zeros((K - 1, h.shape[1])), b * u])
    mixed = sum(z[j:j + len(h)] * lp["conv_w"][j] for j in range(K))
    return (c * mixed) @ lp["w_out"], z


@pytest.mark.parametrize("length", [16, 37])
def test_conv_over_a_prompt_is_two_pieces_and_decode_steps(length):
    lp = shortconv.init_layer(jax.random.key(0), 32, CONV, jnp.float32)
    h = jax.random.normal(jax.random.key(1), (1, length, 32))
    zero = shortconv.zero_state(CONV, 32, 1, jnp.float32)
    y, end, kept = shortconv.mixer(lp, h, zero, CONV, every=16)
    want, z = _plain_conv(lp, h[0])
    np.testing.assert_allclose(y[0], want, **TOL)
    np.testing.assert_allclose(end["tail"][0], z[-2:], **TOL)  # z_{t-2}, z_{t-1}
    assert kept["tail"].shape == (1, length // 16, 2, 32)
    # two pieces, then one row at a time, through the carried tail
    cut = length // 3
    first, state, _ = shortconv.mixer(lp, h[:, :cut], zero, CONV)
    second, state, _ = shortconv.mixer(lp, h[:, cut:length - 4], state, CONV)
    rows = [first, second]
    for t in range(length - 4, length):
        row, state, _ = shortconv.mixer(lp, h[:, t:t + 1], state, CONV)
        rows.append(row)
    np.testing.assert_allclose(jnp.concatenate(rows, 1), y, **TOL)
    np.testing.assert_allclose(state["tail"], end["tail"], **TOL)
    # from the tail kept at the boundary: the same rows after it
    rest, _, _ = shortconv.mixer(lp, h[:, 16:], {"tail": kept["tail"][:, 0]},
                                 CONV) if length > 16 else (y[:, 16:], 0, 0)
    np.testing.assert_allclose(rest, y[:, 16:], **TOL)


def test_conv_rows_past_length_and_dead_slots_move_no_state():
    lp = shortconv.init_layer(jax.random.key(0), 32, CONV, jnp.float32)
    h = jax.random.normal(jax.random.key(2), (1, 37, 32))
    zero = shortconv.zero_state(CONV, 32, 1, jnp.float32)
    y, end, kept = shortconv.mixer(lp, h, zero, CONV, every=16)
    padded = jnp.pad(h, ((0, 0), (0, 27), (0, 0)), constant_values=3.0)
    yp, endp, keptp = shortconv.mixer(lp, padded, zero, CONV, length=37,
                                      every=16)
    np.testing.assert_allclose(yp[:, :37], y, **TOL)
    np.testing.assert_allclose(endp["tail"], end["tail"], **TOL)
    np.testing.assert_allclose(keptp["tail"][:, :2], kept["tail"], **TOL)
    both = jnp.concatenate([h[:, :1], h[:, 1:2]])           # two slots
    state = {"tail": jnp.concatenate([end["tail"], end["tail"]])}
    _, after, _ = shortconv.mixer(lp, both, state, CONV,
                                  live=jnp.asarray([True, False]))
    np.testing.assert_array_equal(after["tail"][1], end["tail"][0])
    assert not np.array_equal(after["tail"][0], end["tail"][0])


# ---- the gated routed layer ------------------------------------------------

def test_one_held_gated_expert_is_the_plain_swiglu_feed_forward():
    dims = dataclasses.replace(GATED, experts=1, held=1, held_from=0, top_k=1)
    lp = routed.init_layer(jax.random.key(0), 32, dims, jnp.float32)
    assert sorted(lp) == ["router", "router_bias", "w1", "w2"]
    x = jax.random.normal(jax.random.key(3), (2, 5, 32))
    y, counts, _ = routed.mixer(lp, x, dims)
    assert counts.tolist() == [1, 10]
    cfg = dataclasses.replace(PRESETS["tiny"], hidden_size=32,
                              intermediate_size=24)
    ones = {"ln_mlp": jnp.ones(32), "mlp": {
        "w_gate": lp["w1"][0][:, :24], "w_up": lp["w1"][0][:, 24:],
        "w_down": lp["w2"][0]}}
    # `ffn_block` norms its input and adds the residual: undo both
    from ray_tpu.models.transformer import rms_norm
    h = rms_norm(x, ones["ln_mlp"], cfg.rms_norm_eps)
    y_norm, _, _ = routed.mixer(lp, h, dims)
    np.testing.assert_allclose(ffn_block(ones, x, cfg) - x, y_norm, **TOL)
    xf = x.reshape(10, 32)
    plain = (jax.nn.silu(xf @ lp["w1"][0][:, :24]) * (xf @ lp["w1"][0][:, 24:])) \
        @ lp["w2"][0]
    np.testing.assert_allclose(y.reshape(10, 32), plain, **TOL)


@pytest.mark.parametrize("width", [1024, 1536, 2048, 2688, 3072, 64, 896])
def test_tile_divides_every_width_both_families_use(width):
    tile = routed._tile(width)
    assert width % tile == 0 and tile <= 1024
    assert tile == width or tile % 128 == 0
    assert {1024: 1024, 2688: 896}.get(width, tile) == tile    # the hybrid's


# ---- attention in a pattern: head norm, then rotation ----------------------

def test_qk_head_norm_and_rotation_in_a_pattern():
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=64, dtype=jnp.float32,
        pattern="*F *F", rope_theta=1e6, qk_norm=True, tie_embeddings=True)
    params = init_params(cfg, jax.random.key(0))
    assert "lm_head" not in params and len(params["layers"]) == 4
    lp = params["layers"][0]
    lp["attn"]["q_norm"] = 1 + 0.1 * jax.random.normal(jax.random.key(5), (8,))
    lp["attn"]["k_norm"] = 1 + 0.1 * jax.random.normal(jax.random.key(6), (8,))
    x = jax.random.normal(jax.random.key(1), (1, 6, 32))
    cos, sin = rope_angles(jnp.arange(3, 9), cfg)
    q, k, v = block_qkv(lp, x, cos, sin, cfg)

    def plain(w, scale):
        h = np.asarray(x[0], np.float64)
        h = h / np.sqrt((h * h).mean(-1, keepdims=True) + cfg.rms_norm_eps)
        p = np.einsum("se,ehd->shd", h, np.asarray(w, np.float64))
        p = p / np.sqrt((p * p).mean(-1, keepdims=True) + cfg.rms_norm_eps) \
            * np.asarray(scale, np.float64)
        freqs = 1.0 / 1e6 ** (np.arange(0, 8, 2) / 8)
        ang = np.arange(3, 9)[:, None] * freqs[None]
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([p[..., :4] * c - p[..., 4:] * s,
                               p[..., 4:] * c + p[..., :4] * s], -1)
    np.testing.assert_allclose(q[0], plain(lp["attn"]["wq"],
                                           lp["attn"]["q_norm"]), **TOL)
    np.testing.assert_allclose(k[0], plain(lp["attn"]["wk"],
                                           lp["attn"]["k_norm"]), **TOL)
    # the dense block without the norm is what it was
    dense = PRESETS["tiny"]
    assert not dense.qk_norm and not dense.tie_embeddings
    dp = jax.tree.map(lambda a: a[0], init_params(
        dense, jax.random.key(0))["layers"])
    assert "q_norm" not in dp["attn"]
    xd = jax.random.normal(jax.random.key(2), (1, 4, dense.hidden_size))
    o = jnp.zeros((1, 4, dense.num_heads, dense.head_dim_))
    np.testing.assert_array_equal(block_out(dp, xd, o, dense),
                                  ffn_block(dp, xd, dense))


def test_a_layer_of_two_letters_counts_once():
    _, pc = tiny()
    assert pc.pattern == "CF *E CE CE" and pc.num_layers == 4
    assert pc.kinds == "CF*ECECE" and pc.pattern_layers == 4
    assert pc.count("*") == 1 and pc.count("C") == 3 and pc.count("E") == 3
    assert state_chunk(pc) == 16 and state_bytes(pc) == 3 * 2 * 128 * 4
    params = init_params(pc, jax.random.key(0))
    assert len(params["layers"]) == 8
    assert sum(a.size for a in jax.tree.leaves(params)) == pc.param_count()
    with pytest.raises(ValueError, match="layers"):
        init_params(dataclasses.replace(pc, num_layers=8), jax.random.key(0))
    hybrid = dataclasses.replace(pc, pattern="CECE", num_layers=4)
    assert hybrid.pattern_layers == 4 and hybrid.kinds == "CECE"


# ---- the engine ------------------------------------------------------------

def test_the_state_is_the_convolutions_tails_and_the_counters_are_fed():
    cfg, pc = tiny()
    eng = engine(pc, 3)
    assert eng._every == 64 and len(eng._dev["rec"]) == 3
    assert [sorted(r) for r in eng._dev["rec"]] == [["tail"]] * 3
    assert eng._pk.shape[0] == 1                # one attention layer's pool
    assert eng.decode_stats()["pool_row"] == "heads"            # 4 x 16
    doc = prompt_of(cfg, 3, 150)                # checkpoints every 64 tokens
    first, second = doc + prompt_of(cfg, 4, 9), doc + prompt_of(cfg, 5, 12)
    eng.generate([first], SamplingParams(max_tokens=4))
    st = eng.state_stats()
    assert st["enabled"] and st["every"] == 64 and st["rows_in_use"] == 2 \
        and st["checkpoints_kept"] == 2 and st["rows_total"] == 16
    assert st["row_bytes"] == 3 * 2 * 128 * 4   # 3 layers x 2 rows, float32
    warm = eng.generate([second], SamplingParams(max_tokens=4))[0]
    st, pc_st = eng.state_stats(), eng.prefix_cache_stats()
    assert pc_st["hits"] == 1 and pc_st["hit_pages"] == 8       # 128 tokens
    assert st["tokens_recomputed"] == 144 - 128 \
        and st["hit_prompt_tokens"] == len(second)
    cold = engine(pc, 3, prefix_cache=False)
    assert cold.generate([second], SamplingParams(max_tokens=4))[0] == warm
    # A hit that starts from a convolution checkpoint: the whole prompt's
    # logits, to rounding.
    hit = eng._run_suffix(second, 128, np.r_[
        eng._cache.lookup(second)[1], np.zeros(24, np.int32)],
        from_row=eng._cache.lookup(second)[2])[0]
    np.testing.assert_allclose(hit, cold._run_prefill(second)[0], **TOL)
    rt = eng.routed_stats()
    assert rt["enabled"] and rt["steps"] == 6 and rt["held"] == 8 \
        and rt["experts"] == 8 and rt["top_k"] == 2
    assert len(rt["touched"]) == len(rt["rows"]) == 3           # E layers
    assert rt["rows"] == [12, 12, 12] and max(rt["step_touched"]) <= 2


# ---- 64-wide heads: the pool's row is lanes (ops/paged_attention.py) -------

def _narrow(kv):
    """`tiny` with 8 query heads of 64 over `kv` KV heads: KV x D = 128 or
    512 lanes, as the published widths have it (8 x 64)."""
    from benchmark.families import lfm2_moe as family
    cfg, _ = tiny()
    cfg = dict(cfg, head_dim=64, num_key_value_heads=kv)
    return cfg, family.program_config(cfg, max_seq_len=512)


@pytest.mark.parametrize("kv", [2, 8])
def test_narrow_heads_decode_through_the_cache_is_the_full_forward(kv):
    """Prefill, then 2 x page + 3 decode steps through a pool of rows of
    lanes and the convolution tails: every step's logits are the float32
    reference's over the whole sequence."""
    from benchmark.families import lfm2_moe as family
    cfg, pc = _narrow(kv)
    eng = engine(pc, 1)
    assert eng._pk.shape == (1, 65, 16, kv * 64)
    assert eng.decode_stats()["pool_row"] == "lanes"
    assert eng.decode_stats()["path"] == "reference"
    prompt, steps = prompt_of(cfg, 1), 2 * 16 + 3
    out = eng.generate([prompt], SamplingParams(max_tokens=steps + 1))[0]
    got = eng.trace_logits(prompt, out[:-1])
    toks = jnp.asarray([prompt + out[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0, len(prompt) - 1:]
    assert got["logits"].shape == (steps + 1, cfg["vocab_size"])
    np.testing.assert_allclose(got["logits"], ref, **TOL)
    assert np.asarray(ref).argmax(-1).tolist() == out       # greedy, served


@pytest.mark.parametrize("kv", [2, 8])
def test_narrow_heads_a_hit_reads_the_rows_a_prefill_installed(kv):
    """A prefix-cache hit on a pool of rows of lanes: the suffix prefill's
    XLA arm gathers the cached pages as they lie, and the greedy tokens are
    the whole prompt's; `trace_logits(cached=True)` traces that path."""
    from benchmark.families import lfm2_moe as family
    cfg, pc = _narrow(kv)
    eng = engine(pc, 3)
    doc = prompt_of(cfg, 3, 150)
    first, second = doc + prompt_of(cfg, 4, 9), doc + prompt_of(cfg, 5, 12)
    eng.generate([first], SamplingParams(max_tokens=4))
    warm = eng.generate([second], SamplingParams(max_tokens=20))[0]
    assert eng.prefix_cache_stats()["hit_pages"] == 8           # 128 tokens
    cold = engine(pc, 3, prefix_cache=False)
    assert cold.generate([second], SamplingParams(max_tokens=20))[0] == warm
    got = eng.trace_logits(second, warm[:-1], cached=True)
    assert got["from"] == 128
    toks = jnp.asarray([second + warm[:-1]], jnp.int32)
    ref = family.reference_logits(eng.params, toks, cfg)[0, len(second) - 1:]
    np.testing.assert_allclose(got["logits"], ref, **TOL)


def test_eviction_frees_pages_and_conv_checkpoint_rows_together():
    cfg, pc = tiny()
    eng = engine(pc, 6, kv_pages=24)       # 384 tokens: 6 checkpoint rows
    assert eng.state_stats()["rows_total"] == 6
    for seed in range(4):                   # 4 x 150 tokens do not fit
        eng.generate([prompt_of(cfg, 10 + seed, 150)],
                     SamplingParams(max_tokens=2))
    st, pc_st = eng.state_stats(), eng.prefix_cache_stats()
    assert pc_st["evictions"] > 0 and st["checkpoints_evicted"] > 0
    assert st["rows_in_use"] == st["checkpoints_kept"] \
        - st["checkpoints_evicted"]
    cache = eng._cache
    while cache.evict_lru(eng._decref):
        pass
    assert eng.state_stats()["rows_in_use"] == 0 \
        and sorted(cache.free_rows) == list(range(2, 8))
    assert eng.kv_pages_free() == 24 and not cache._rows


def test_what_the_engine_refuses_for_a_pattern_it_keeps_refusing():
    _, pc = tiny()
    with pytest.raises(ValueError, match="pattern"):
        engine(pc, 0, prefill_chunk=64)
    with pytest.raises(ValueError, match="pattern"):
        engine(pc, 0).prefill_only([1, 2, 3])
    from ray_tpu.models.transformer import forward, param_logical_axes
    with pytest.raises(ValueError, match="pattern"):
        forward(init_params(pc, jax.random.key(0)), jnp.zeros((1, 4), int), pc)
    with pytest.raises(ValueError, match="pattern"):
        param_logical_axes(pc)
