"""Checks of the yardstick itself, none of which needs a chip:

    python -m benchmark.selftest            # everything
    python -m benchmark.selftest unit       # seconds: files, traffic, stats,
                                            # the trace reduction
    python -m benchmark.selftest rehearse   # minutes: the whole command per
                                            # cell on CPU workers

`unit` holds BENCHMARK.json and the files it names to the rules a later PR
leans on (every metric has its reader, every per-layer metric moves an
end-to-end metric its cells report), shows that two seeds offer the same
multiset of requests on the same schedule (a closed list with `stratum` 1:
the same list in the same order), checks the trace reduction on a hand-made
trace with known answers and on the recorded one under `fixtures/`, and
holds the reference check to its own rules on a toy routed family
(`tests/`: the plain check fails it on some seeds, the check it owns passes
every seed and refuses fp8 weights) and on the dense family (the report is
the parent's).  `rehearse` runs `python -m benchmark.run --rehearse` for
every cell: tiny widths, every token length cut eightfold, CPU workers
(four virtual devices for a four-chip cell) — control flow, not speed —
and then shows that without `--rehearse` the command gives no result here.
"""

from __future__ import annotations

import functools
import json
import os
import re
import subprocess
import sys
from typing import Any, Dict, List

from . import client, freeze, stats, trace, traffic
from .run import HERE, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
TINY = {"hidden_size": 128, "intermediate_size": 256, "vocab_size": 512,
        "num_attention_heads": 8, "num_key_value_heads": 4,
        "num_hidden_layers": 2, "torch_dtype": "float32"}
CUT = 8
ROUTED_SEEDS = range(14, 24)      # ten seeds; the plain check fails three


def shrink(cell: Dict[str, Any]) -> None:
    """The rehearsal's cell: tiny widths, lengths cut by CUT, short
    phases.  In place.  TINY's keys are the dense decoder's; a family
    whose configuration has other widths (latent ranks, expert counts)
    names their rehearsal sizes in its own `TINY`, applied after."""
    cell["config"].update(TINY)
    cell["config"].update(getattr(cell["family"], "TINY", {}))
    cell["config"].pop("head_dim", None)
    t = cell["traffic"]
    if "engine" in t:
        t["engine"].update(max_len=t["engine"]["max_len"] // CUT,
                           kv_pages=t["engine"]["kv_pages"] // CUT)
    for key in ("ramp_s", "trace_s"):
        if key in t:
            t[key] = max(1, t[key] // 4)
    if t["kind"] == "closed":
        d = t["documents"]
        d.update(len_min=d["len_min"] // CUT, len_max=d["len_max"] // CUT)
        t["output_tokens"] = max(2, t["output_tokens"] // CUT)
    elif t["kind"] == "open_grid":
        for key, floor in (("prompt_len", 4), ("output_len", 2)):
            for q in ("median", "min", "max"):
                t[key][q] = max(floor, t[key][q] // CUT)
    else:
        t["seq"] //= CUT
    if "check_prompt_len" in t:
        t["check_prompt_len"] //= CUT


# ---------------------------------------------------------------- unit ----

def check_files() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        with open(os.path.join(HERE, "metrics", f"{m['name']}.json")) as f:
            how = json.load(f)
        assert how["name"] == m["name"]
        assert os.path.exists(os.path.join(
            HERE, "readers", f"{how['reader']}.py")), how
        for w in m.get("workloads", []):
            assert w in cells, (m["name"], w)
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in moved.get("workloads", cells), \
                f"{m['name']} moves {m['moves']}, which {w} does not report"
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"], w["name"]
        assert cell["config"]["family"]
        for key in cell["config"]["reduced"]:
            assert key in next(c for c in bench["configs"]
                               if c["name"] == w["config"])["reduced"]
    print(f"files: {len(cells)} cells, {len(e2e)} end-to-end and "
          f"{len(bench['per_layer'])} per-layer metrics, each with its "
          "reader; every `moves` is reported where its metric is")


def check_traffic_file(name: str) -> None:
    """Two seeds: the same multiset of request shapes, on the same
    schedule.  closed: the same list but for the order inside strata, and
    with `stratum` 1 the same list in the same order (which request misses,
    and which misses meet in one admission, is then the layout's); the
    token ids differ.  open_grid: one request per slot, the same shapes per
    stratum."""
    seeds = (7, 2 ** 31 + 11)
    spec = traffic.load(name)
    if spec["kind"] == "train_steps":
        return
    a, b = (traffic.requests(spec, s, 45.0) for s in seeds)
    assert sorted(r.shape() for r in a) == sorted(r.shape() for r in b)
    size = spec.get("stratum") or spec["stratum_slots"]
    same = [(r.shape(), r.doc) for r in a] == [(r.shape(), r.doc) for r in b]
    assert same == (size == 1), \
        f"{name}: the seed {'permutes' if size == 1 else 'leaves'} the order"
    if spec["kind"] == "closed":
        for i in range(0, len(a), size):
            assert sorted(r.shape() for r in a[i:i + size]) == \
                sorted(r.shape() for r in b[i:i + size])
        assert [r.doc for r in a if r.doc < 0] == []
    else:
        rate = spec["rate_hz"]
        starts = {"ramp": 0.0, "window": spec["ramp_s"],
                  "tail": spec["ramp_s"] + 45.0}
        for reqs in (a, b):
            for phase, start in starts.items():
                slots = [int((r.due - start) * rate + 1e-9)
                         for r in reqs if r.phase == phase]
                assert slots == list(range(len(slots))), \
                    f"{name}: not one request per slot in the {phase}"
        for phase in ("ramp", "window", "tail"):
            pa = [r for r in a if r.phase == phase]
            pb = [r for r in b if r.phase == phase]
            for i in range(0, len(pa), size):
                sa = pa[i:i + size], pb[i:i + size]
                assert sorted(r.prompt_len for r in sa[0]) == \
                    sorted(r.prompt_len for r in sa[1])
                assert sorted(r.output_len for r in sa[0]) == \
                    sorted(r.output_len for r in sa[1])
    traffic.fill_tokens(a[:8], seeds[0], 512)
    traffic.fill_tokens(b[:8], seeds[1], 512)
    assert all(len(r.tokens) == r.prompt_len for r in a[:8] + b[:8])
    assert a[0].tokens != b[0].tokens
    print(f"traffic {name}: {len(a)} requests, same multiset and schedule "
          f"under two seeds; contents differ, order "
          f"{'the same' if size == 1 else 'differs inside strata'}")


def check_traffic() -> None:
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        check_traffic_file(name[:-5])


def check_stats() -> None:
    v = list(range(1, 101))
    assert stats.band_mean(v, 90, 99) == sum(range(91, 100)) / 9
    assert stats.band_mean(v, 75, 95) == sum(range(76, 96)) / 20
    assert stats.band_mean([5.0], 90, 99) is None
    assert stats.percentile(v, 50) == 50 and stats.median([1, 3]) == 2
    assert stats.gaps_ms([1.0, 1.5, 1.75]) == [500.0, 250.0]
    assert freeze.seconds([(1.0, 3.0), (5.0, 9.0)], 2.0, 6.0) == 2.0
    # Replies: due -> last token; failed or not whole = the window's length.
    rec = dict(error=None, cut=False, finish={"n_tokens": 2})
    ctx = {"window": [10.0, 20.0], "records": [
        dict(rec, due=11.0, token_times=[11.5, 12.25]),
        dict(rec, due=19.0, token_times=[19.5], finish=None, cut=True),
        dict(rec, due=12.0, token_times=[], error="x"),
        dict(rec, due=9.0, token_times=[9.5, 10.5])]}
    assert client.replies_ms(ctx) == [1250.0, 10000.0, 10000.0]
    print("stats: band means, percentiles, gaps, replies and stall seconds as "
          "defined")


def check_trace() -> None:
    # Hand-made: one device, a `while` of 100 ns holding two ops, a gap of
    # 50 ns between two programs, then an all-reduce of 30 ns.
    made = {"devices": [{"id": 0, "modules": [
        ["jit_a(1)", 0, 100], ["jit_b(2)", 150, 60], ["jit_b(2)", 210, 10]],
        "ops": [["while.1", 0, 100], ["fusion.1", 10, 40],
                ["copy.2", 50, 20], ["fusion.3", 150, 30],
                ["all-reduce.4", 180, 30], ["fusion.3", 210, 10]]}]}
    r = trace.reduce(made)
    ns = 1e-9
    assert abs(r["busy_s"] - 170 * ns) < 1e-15, r
    assert abs(r["window_s"] - 220 * ns) < 1e-15, r
    assert abs(r["collective_s"] - 30 * ns) < 1e-15, r
    ops = dict(map(tuple, r["device_ops"]))
    assert abs(ops["while.1"] - 40 * ns) < 1e-15 \
        and abs(ops["fusion.3"] - 40 * ns) < 1e-15, ops
    assert r["idle_gaps"] == [["jit_a->jit_b", 50 * ns]] or \
        abs(dict(map(tuple, r["idle_gaps"]))["jit_a->jit_b"] - 50 * ns) \
        < 1e-15, r["idle_gaps"]
    assert trace.most_run_program(r, "jit_")[0] == "jit_b(2)"
    assert trace.op_name("%fusion.368 = bf16[8]{0} fusion(...)") \
        == "fusion.368"
    seen = ["hand-made"]
    fix = os.path.join(HERE, "fixtures")
    for name in sorted(os.listdir(fix)):
        if not name.endswith(".trace.json.gz"):
            continue
        got = trace.reduce(trace.load(os.path.join(fix, name)))
        with open(os.path.join(fix, name.replace(
                ".trace.json.gz", ".expected.json"))) as f:
            want = json.load(f)
        for key, value in want.items():
            have = got[key] if key in got else \
                dict(map(tuple, got["device_ops"]))[key]
            assert abs(have - value) <= 1e-9 * max(1.0, abs(value)), \
                (name, key, have, value)
        # Two readings the reduction does not share code with: the union
        # of the intervals by a plain merge, and (events nest properly)
        # the self times adding up to exactly that union.
        ex = trace.load(os.path.join(fix, name))
        merged = 0.0
        for dev in ex["devices"]:
            end = None
            for _, s0, d0 in sorted(dev["ops"], key=lambda e: e[1]):
                if end is None or s0 > end:
                    merged += d0
                    end = s0 + d0
                elif s0 + d0 > end:
                    merged += s0 + d0 - end
                    end = s0 + d0
        merged /= 1e9 * len(ex["devices"])
        assert abs(merged - got["busy_s"]) < 1e-9, (merged, got["busy_s"])
        selfs = sum(sum(trace._self_times(dev["ops"])[0].values())
                    for dev in ex["devices"]) / 1e9 / len(ex["devices"])
        assert abs(selfs - got["busy_s"]) < 1e-9, (selfs, got["busy_s"])
        assert got["busy_s"] <= got["window_s"]
        seen.append(name)
    print(f"trace: reduction agrees on {', '.join(seen)}")


@functools.lru_cache(maxsize=None)
def routed_report(seed: int, weights: str = "bfloat16", flip=None,
                  family=None) -> Dict[str, Any]:
    """`refcheck.report` on the toy routed family (`tests/toy_routed.py`):
    seeded weights, a seeded prompt, eight greedy tokens served twice."""
    import numpy as np

    from . import refcheck
    from .tests import toy_routed as toy
    engine = toy.ToyEngine(toy.init_params(seed), weights=weights, flip=flip)
    prompt = np.random.default_rng([seed, 5]).integers(
        1, toy.CONFIG["vocab_size"], 40).tolist()
    out = engine.generate(prompt, 8)
    return refcheck.report(engine, family or toy, toy.CONFIG, prompt,
                           [out, out])


def check_routed(seeds=ROUTED_SEEDS) -> None:
    sound = [routed_report(s) for s in seeds]
    assert all(r["ok"] for r in sound), [r for r in sound if not r["ok"]]
    plain_fails = [s for s, r in zip(seeds, sound) if not r["plain"]["ok"]]
    assert plain_fails, "the plain check held a routed family on every seed"
    control = [routed_report(s, weights="float8_e4m3fn") for s in seeds]
    assert not any(r["ok"] for r in control), control
    print(f"reference check, toy routed family, {len(sound)} seeds: the "
          f"plain check fails seeds {plain_fails}; the family's own passes "
          f"all (logit max <= {max(r['logit_max'] for r in sound):.3f}, rms "
          f"<= {max(r['logit_rms'] for r in sound):.4f}, forced share <= "
          f"{max(r['forced_share'] for r in sound):.3f}) and refuses fp8 "
          f"weights on all (rms >= "
          f"{min(r['logit_rms'] for r in control):.3f})")


def check_dense_report() -> None:
    """A family without `check`: every key and value of the report is what
    the parent's `_bench_check` wrote (but the seconds it took)."""
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine, SamplingParams

    from . import refcheck
    from .tests import parent_check
    cell = load_cell("serve_chat")
    shrink(cell)
    family, config = cell["family"], cell["config"]
    engine = LLMEngine(family.program_config(config, max_seq_len=128),
                       max_batch=2, max_len=128, seed=2 ** 31 + 5,
                       page_size=16)
    rng = np.random.default_rng([2 ** 31 + 5, 5])
    prompt = rng.integers(1, config["vocab_size"], 75).tolist()
    served = engine.generate([prompt], SamplingParams(max_tokens=8)) * 2
    want = parent_check.bench_check(engine, family, config, prompt, served)
    got = refcheck.report(engine, family, config, prompt, served)
    want.pop("seconds"), got.pop("seconds")
    assert got == want and list(got) == list(want) and got["ok"], (got, want)

    # The same report says no when the path under it is broken: a served
    # token altered where it is produced, a prefill logit moved.
    worst = int(np.argmin(np.asarray(engine._run_prefill(prompt)[0])))
    altered = [[worst] + served[0][1:], served[1]]
    assert not refcheck.report(engine, family, config, prompt, altered)["ok"]

    class Moved:
        params = engine.params

        def _run_prefill(self, p):
            logits, *rest = engine._run_prefill(p)
            return (logits.at[7].add(1.0), *rest)
    assert not refcheck.report(Moved(), family, config, prompt, served)["ok"]
    print(f"reference check, dense family: the report is the parent's "
          f"({sorted(got)}), and fails on an altered token or a moved logit")


def check_shrink(tiny=None) -> None:
    """`shrink` applies the family's `TINY` after its own, if it has one."""
    import types
    cell = load_cell("serve_chat")
    if tiny is not None:
        cell["family"] = types.SimpleNamespace(TINY=tiny)
    shrink(cell)
    for key, value in {**TINY, **(tiny or {})}.items():
        assert cell["config"][key] == value, (key, cell["config"][key])
    assert cell["traffic"]["engine"]["max_len"] == 2048 // CUT


def check_refcheck() -> None:
    check_routed()
    check_dense_report()
    from .tests import toy_routed
    check_shrink()
    check_shrink(toy_routed.TINY)
    print("shrink: the family's TINY is applied after the dense keys")


def unit() -> None:
    check_files()
    check_traffic()
    check_stats()
    check_trace()
    check_refcheck()


# ------------------------------------------------------------ rehearse ----

def _run(workload: str, chips: int, extra: List[str]
         ) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", str(2 ** 31 + 5), "--seconds", "4", "--trace", "1",
         "--out", os.path.join(ROOT, "chiprun_out", "selftest", workload)]
        + extra, cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)


def rehearse() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    for w in cells:
        p = _run(w["name"], w["chips"], ["--rehearse"])
        assert p.returncode == 0, (w["name"], p.stderr[-3000:])
        line = json.loads(p.stdout.strip().splitlines()[-1])
        for key in ("correct", "attempted", "failed", "metrics", "device"):
            assert key in line, (w["name"], key)
        assert line["device"]["platform"] == "cpu"
        assert line["device"]["count"] == w["chips"], line["device"]
        assert line["correct"] and line["attempted"] > 0 \
            and line["failed"] == 0, (line, p.stderr[-2000:])
        print(f"rehearsal {w['name']}: correct, {line['attempted']} "
              f"attempted, metrics {sorted(line['metrics'])}")
    p = _run(cells[0]["name"], cells[0]["chips"], [])
    assert p.returncode != 0 and not p.stdout.strip().startswith("{"), \
        (p.returncode, p.stdout[-500:])
    print(f"no chip: exit {p.returncode} and no result line")


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    if what in ("unit", "all"):
        unit()
    if what in ("rehearse", "all"):
        rehearse()
    print("selftest: ok")
