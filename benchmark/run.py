"""One run of one benchmark cell:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--out <dir>]

from the root of a checkout, on a machine that holds the chips the cell
asks for.  Everything about the cell is data, found by the names in
BENCHMARK.json: the configuration file, `traffic/<traffic>.json`, and for
each metric `metrics/<name>.json`, which names a reader under `readers/`.
The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, and with `--trace 1`
`breakdown`): with `--trace 0` the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.  Detail goes to `--out`.

This process never initialises a JAX backend: the chip belongs to the
worker the runtime starts.  Without the chips the cell asks for it exits
non-zero and prints no result.  `--rehearse` (used by `python -m
benchmark.selftest`) is the CPU rehearsal: tiny widths, lengths cut, CPU
workers; its line says platform `cpu` and is no measurement.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

T_PROC = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_cell(workload: str) -> Dict[str, Any]:
    """The cell, its configuration and traffic, and the metrics it reports,
    all by name from BENCHMARK.json."""
    from . import families, traffic
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cell["config"] = json.load(f)
    cell["traffic"] = traffic.load(cell["traffic"])
    cell["family"] = families.load(cell["config"]["family"])
    for group in ("end_to_end", "per_layer"):
        cell[group] = [m for m in bench[group]
                       if workload in m.get("workloads", [workload])]
    return cell


def read_metrics(metrics: List[dict], ctx: Dict[str, Any]
                 ) -> Dict[str, Dict[str, Any]]:
    """Each metric through its own reader; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in metrics:
        with open(os.path.join(HERE, "metrics", f"{m['name']}.json")) as f:
            how = json.load(f)
        reader = importlib.import_module(
            f"{__package__}.readers.{how['reader']}")
        value = reader.read(ctx, how.get("args", {}))
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def verdict(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """correct / attempted / failed, with the reasons kept in the detail."""
    from . import client
    why: List[str] = []
    if not ctx["check"]["ok"]:
        why.append(f"reference check failed: {ctx['check']}")
    if ctx["compiles_in_window"]:
        why.append(f"{ctx['compiles_in_window']} compilation(s) inside "
                   "the window")
    if ctx["kind"] == "train_steps":
        losses = ctx["losses"]
        fifth = max(1, len(losses) // 5)
        first = sum(losses[:fifth]) / fifth
        last = sum(losses[-fifth:]) / fifth
        if not all(math.isfinite(x) for x in losses) or not last < first:
            why.append(f"loss did not fall over the window: {first} -> "
                       f"{last}")
        attempted = len(losses) + ctx["raised"]
        failed = ctx["raised"]
    else:
        due = client.due_in_window(ctx)
        attempted = len(due)
        failed = sum(1 for r in due if client.is_failed(r))
        wrong = client.wrong_streams(ctx)
        if wrong:
            why.append(f"{len(wrong)} stream(s) did not end as asked, "
                       f"first: {wrong[0]['finish']}")
        if ctx["list_exhausted"]:
            why.append("the fixed list of requests ran out: the window's "
                       "work was not the cell's")
    if attempted == 0:
        why.append("nothing was attempted in the window")
    return {"correct": not why, "attempted": attempted, "failed": failed,
            "why_not": why}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="open_grid only: offer this rate, not the cell's "
                         "(finding the knee again; never a measurement)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from . import cluster, freeze, serve_cell, train_cell
    cell = load_cell(args.workload)
    if args.rehearse:
        from . import selftest
        selftest.shrink(cell)
    out = os.path.abspath(args.out or os.path.join(
        ROOT, "chiprun_out", "benchmark", args.workload,
        f"seed{args.seed}_trace{args.trace}"))
    os.makedirs(out, exist_ok=True)
    kw = dict(seed=args.seed, seconds=args.seconds, traced=bool(args.trace),
              out_dir=out, t_proc=T_PROC, require_tpu=not args.rehearse)

    cluster.adopt_orphans()
    watch = freeze.FreezeWatch()
    run_cell = train_cell.run if cell["traffic"]["kind"] == "train_steps" \
        else functools.partial(serve_cell.run, rate_hz=args.rate)
    try:
        ctx = run_cell(cell, **kw)
    except cluster.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    finally:
        watch.close()
        killed = cluster.stop_descendants()
        if killed:
            print(f"benchmark: killed {len(killed)} process(es) that "
                  f"outlived shutdown: {killed}", file=sys.stderr)
    t0, t1 = ctx["window"]
    ctx["stalls"] = {
        "count": len(watch.stalls),
        "setup_s": freeze.seconds(watch.stalls, T_PROC, t0),
        "window_s": freeze.seconds(watch.stalls, t0, t1),
        "late_setup_s": freeze.seconds(watch.late, T_PROC, t0),
        "late_window_s": freeze.seconds(watch.late, t0, t1),
        "each": watch.stalls, "late": watch.late}
    if ctx["stalls"]["window_s"]:
        print(f"benchmark: this process stood still for "
              f"{ctx['stalls']['window_s']:.1f} s inside the window; the "
              "window is reported as measured", file=sys.stderr)

    ctx.update(config=cell["config"], traffic=cell["traffic"],
               family=cell["family"])
    result = verdict(ctx)
    result["metrics"] = read_metrics(
        cell["per_layer" if args.trace else "end_to_end"], ctx)
    dev, tr = ctx["device"], ctx.get("trace") or {}
    result["device"] = {"platform": dev["platform"],
                        "kind": dev["device_kind"],
                        "count": dev["device_count"],
                        "memory_peak_bytes": cluster.peak_bytes(
                            ctx["memory"])}
    if args.trace and tr.get("window_s"):
        result["device"].update(busy_s=tr["busy_s"],
                                window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    ctx["anomalies"] = cluster.anomalies(out)
    detail = {k: v for k, v in ctx.items()
              if k not in ("family", "spans", "config", "traffic")}
    detail.update(result=result, spans=len(ctx.get("spans", ())),
                  args=vars(args))
    with open(os.path.join(out, "run.json"), "w") as f:
        json.dump(detail, f, default=str)
    if result["why_not"]:
        print(f"benchmark: not correct: {result['why_not']}",
              file=sys.stderr)
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    line["stalls"] = {k: ctx["stalls"][k]
                      for k in ("count", "setup_s", "window_s")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
