"""Reduction of a `jax.profiler` trace to the numbers the per-layer
metrics and the `breakdown` are made of.

`traced` records a stretch of work.  The reduction is two steps, kept
apart so that the second can be checked on a small recorded trace
(benchmark/fixtures/, `python -m benchmark.selftest`):

  extract(xplane_path) -> {"devices": [{"id", "modules": [[name, start_ns,
      dur_ns]...], "ops": [[name, start_ns, dur_ns]...]}]}
      reads the `/device:TPU:<n>` planes: the "XLA Modules" line (one event
      per run of a compiled program, named `jit_<fn>(<fingerprint>)`) and
      the "XLA Ops" line (every HLO operation the core ran, containers such
      as `while` included, nested by time).
  reduce(extracted) -> busy and window seconds, self time per operation,
      idle gaps named by the programs around them, time in collectives,
      and the durations of each program's runs.

Everything on the "XLA Ops" line ran on the one TensorCore of a v5e chip,
in order; so an operation's SELF time is its duration minus the events
nested inside it, busy time is the union of the line's intervals, and a
collective operation on that line (an `all-reduce`, or the `-start` /
`-done` halves of an asynchronous one) is time the core spent issuing or
waiting for communication — exposed, not hidden behind compute.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import time
from collections import defaultdict
from typing import Any, Callable, Dict, List, Tuple

_OP_NAME = re.compile(r"%?([\w.\-]+)")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|async-collective|ragged-all-to-all)")


def op_name(hlo_text: str) -> str:
    """`%fusion.368 = bf16[...] fusion(...)` -> `fusion.368`."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text


def program_name(module_event: str) -> str:
    """`jit__step(17724276471279642650)` -> `jit__step`."""
    return module_event.split("(", 1)[0]


def extract(xplane_path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if not m:
            continue
        dev: Dict[str, Any] = {"id": int(m.group(1)), "modules": [],
                               "ops": []}
        for line in plane.lines:
            if line.name == "XLA Modules":
                dev["modules"] = [[e.name, int(e.start_ns),
                                   int(e.duration_ns)] for e in line.events]
            elif line.name == "XLA Ops":
                dev["ops"] = [[op_name(e.name), int(e.start_ns),
                               int(e.duration_ns)] for e in line.events]
        devices.append(dev)
    devices.sort(key=lambda d: d["id"])
    return {"devices": devices}


def cut(extracted: Dict[str, Any], max_ops: int) -> Dict[str, Any]:
    """The first `max_ops` operations of each device and the program runs
    that end before the last of them: a trace small enough to keep."""
    out = []
    for dev in extracted["devices"]:
        ops = sorted(dev["ops"], key=lambda e: e[1])[:max_ops]
        end = max((s + d for _, s, d in ops), default=0)
        out.append({"id": dev["id"], "ops": ops,
                    "modules": [m for m in dev["modules"]
                                if m[1] + m[2] <= end]})
    return {"devices": out}


def save(extracted: Dict[str, Any], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(extracted, f, separators=(",", ":"))


def load(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _self_times(ops: List[List[Any]]) -> Tuple[Dict[str, int], int,
                                               List[Tuple[int, int]]]:
    """Self time per operation name, busy ns (union of intervals), and the
    top-level intervals, from events nested by time."""
    self_ns: Dict[str, int] = defaultdict(int)
    top: List[Tuple[int, int]] = []
    stack: List[List[Any]] = []        # [name, end, child_ns]
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            self_ns[done[0]] += done[3] - done[2]
        if stack:
            stack[-1][2] += min(end, stack[-1][1]) - start
        elif top and start < top[-1][1]:    # overlaps without nesting
            top[-1] = (top[-1][0], max(top[-1][1], end))
        else:
            top.append((start, end))
        stack.append([name, end, 0, dur])
    while stack:
        done = stack.pop()
        self_ns[done[0]] += done[3] - done[2]
    return self_ns, sum(e - s for s, e in top), top


def _gap_names(top: List[Tuple[int, int]], modules: List[List[Any]]
               ) -> Dict[str, int]:
    """Idle ns between consecutive busy intervals, keyed by the programs
    before and after the gap (`inside_<program>` when one program's run
    spans it)."""
    mods = sorted(modules, key=lambda m: m[1])
    gaps: Dict[str, int] = defaultdict(int)
    mi = 0
    for (s0, e0), (s1, _) in zip(top, top[1:]):
        if s1 <= e0:
            continue
        while mi + 1 < len(mods) and mods[mi + 1][1] <= e0:
            mi += 1
        before = mods[mi] if mods and mods[mi][1] <= e0 else None
        if before and before[1] + before[2] >= s1:
            key = f"inside_{program_name(before[0])}"
        else:
            after = next((m for m in mods[mi:] if m[1] >= e0), None)
            key = (f"{program_name(before[0]) if before else 'start'}"
                   f"->{program_name(after[0]) if after else 'end'}")
        gaps[key] += s1 - e0
    return gaps


def reduce(extracted: Dict[str, Any]) -> Dict[str, Any]:
    """Averages over the devices of the trace (seconds), plus per-program
    run durations (ms) of device 0."""
    devs = [d for d in extracted["devices"] if d["ops"]]
    if not devs:
        return {"devices": 0}
    n = len(devs)
    busy = window = collective = 0.0
    ops_s: Dict[str, float] = defaultdict(float)
    gaps_s: Dict[str, float] = defaultdict(float)
    for dev in devs:
        self_ns, busy_ns, top = _self_times(dev["ops"])
        busy += busy_ns / 1e9 / n
        window += (top[-1][1] - top[0][0]) / 1e9 / n
        for name, ns in self_ns.items():
            ops_s[name] += ns / 1e9 / n
            if _COLLECTIVE.match(name):
                collective += ns / 1e9 / n
        for name, ns in _gap_names(top, dev["modules"]).items():
            gaps_s[name] += ns / 1e9 / n
    programs: Dict[str, List[float]] = defaultdict(list)
    for name, _, dur in devs[0]["modules"]:
        programs[name].append(dur / 1e6)
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {"devices": n, "busy_s": busy, "window_s": window,
            "collective_s": collective,
            "device_ops": rank(ops_s), "idle_gaps": rank(gaps_s),
            "programs_ms": dict(programs)}


def most_run_program(reduced: Dict[str, Any], prefix: str
                     ) -> Tuple[str, List[float]]:
    """Among the programs whose name starts with `prefix`, the one that ran
    most often, with its runs' durations (ms).  The engine's steps all
    trace as `jit__lambda(<fingerprint>)`; its decode step is the one it
    runs every tick, so it is the most-run of them."""
    runs = {k: v for k, v in reduced.get("programs_ms", {}).items()
            if program_name(k).startswith(prefix)}
    if not runs:
        return "", []
    name = max(runs, key=lambda k: len(runs[k]))
    return name, runs[name]


def traced(work: Callable[[], Any], out_dir: str, keep_ops: int
           ) -> Dict[str, Any]:
    """Run `work()` under jax.profiler in this process; reduce; keep a cut
    (`trace_cut.json.gz`); delete the raw `.xplane.pb` (it can exceed the
    file-size limit the driver's harness sets, and nobody reads it)."""
    import jax
    raw = os.path.join(out_dir, "trace_raw")
    os.makedirs(raw, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host frames are not read, and
    opts.host_tracer_level = 1          # tracing them slows the host
    t0 = time.time()
    jax.profiler.start_trace(raw, profiler_options=opts)
    try:
        result = work()
    finally:
        jax.profiler.stop_trace()
    t1 = time.time()
    paths = glob.glob(os.path.join(raw, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return {"devices": 0, "t0": t0, "t1": t1, "result": result}
    extracted = extract(paths[0])
    raw_bytes = os.path.getsize(paths[0])
    reduced = reduce(extracted)
    save(cut(extracted, keep_ops),
               os.path.join(out_dir, "trace_cut.json.gz"))
    shutil.rmtree(raw, ignore_errors=True)
    return {**reduced, "t0": t0, "t1": t1, "raw_bytes": raw_bytes,
            "result": result}
