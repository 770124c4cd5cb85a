"""The seam between the scheduler (llm/engine.py) and what it compiles
(llm/programs.py): the engine names no kind of layer, and what it holds is
what the table's entry for its configuration gives.  The numbers themselves
are held by each family's float32 reference (tests/test_llm.py,
test_hybrid_model.py, test_lfm2_model.py, test_moonlight_model.py,
test_brumby_model.py), through prefill, hit and decode."""

import inspect
import re

import jax
import pytest

from benchmark import selftest
from benchmark.families import brumby, deepseek_v3, lfm2_moe, nemotron_h
from benchmark.run import load_cell
from ray_tpu.llm import engine, programs
from ray_tpu.models import PRESETS
from ray_tpu.ops.paged_attention import pool_row, pool_shape

KINDS = [r"cfg\.latent", r"cfg\.retention", r"cfg\.mamba", r"cfg\.conv",
         r"cfg\.routed", r"cfg\.count\(\s*[\"'][A-Z*]", r"LATENT_FORMS",
         r"paged_latent_attention", r"retention\."]


def test_the_engine_names_no_kind():
    text = inspect.getsource(engine)
    named = [k for k in KINDS if re.search(k, text)]
    assert not named, named
    # The one lambda the engine jits is the decode step (the benchmark's
    # readers find it as the most-run `jit__lambda`).
    assert len(re.findall(r"jax\.jit\(\s*lambda", text)) == 1


def _family(cell, family):
    cell = load_cell(cell)
    selftest.shrink(cell)
    return family.program_config(cell["config"], max_seq_len=512)


def _mesh():
    from ray_tpu.parallel import MeshSpec, build_mesh
    return build_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])


# configuration, mesh, the pools there are (of pool_k, pool_v), the row form
CONFIGS = {
    "dense": (lambda: PRESETS["tiny"], None, (True, True), "heads"),
    "dense_tp2": (lambda: PRESETS["tiny"], _mesh, (True, True), "heads"),
    "nemotron_h": (lambda: _family("serve_doc_reask_hybrid", nemotron_h),
                   None, (True, True), "heads"),
    "lfm2_moe": (lambda: _family("serve_doc_reask_moe", lfm2_moe), None,
                 (True, True), "heads"),
    "deepseek_v3": (lambda: _family("serve_doc_reask_mla", deepseek_v3),
                    None, (True, False), "latent"),
    "brumby": (lambda: _family("serve_doc_reask_retention", brumby), None,
               (False, False), "none"),
}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_engine_holds_what_its_entry_gives(name):
    make, mesh, there, row = CONFIGS[name]
    cfg = make()
    mesh = mesh and mesh()
    eng = engine.LLMEngine(
        cfg, None if mesh else {"weights": None}, mesh=mesh, max_batch=2,
        max_len=128, page_size=16, kv_pages=24, prefix_cache=True)
    entry = programs.cache_of(cfg)
    assert entry is eng._cache_form and entry in programs.CACHES.values()
    assert (eng._pk is not None, eng._pv is not None) == there
    assert eng.decode_stats()["pool_row"] == row
    assert entry.pools == sum(there)
    if not there[0]:
        assert eng.n_pages == 1 and eng.decode_stats()["path"] == "none"
        return
    assert pool_row(*cfg.cache_row) == row and eng.n_pages == 25
    layers = sum(cfg.count(k) for k in "D*L")
    shape = pool_shape(layers, eng.n_pages, eng.page, *cfg.cache_row)
    again = programs.make_pools(cfg, eng.n_pages, eng.page, eng._kv_shd)
    for held, made in zip((eng._pk, eng._pv), again):
        assert (held is None) == (made is None)
        if held is not None:
            assert held.shape == made.shape == shape
            assert held.dtype == made.dtype == cfg.dtype
            assert held.sharding == made.sharding
