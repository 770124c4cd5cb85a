"""The decode step's latent attention kernel's share of its roofline.

The kernel runs once a layer and traces as `paged_latent_attention.N`, one
name a layer, none of which need be among the ten operations the trace's
reduction keeps.  So this reads the CUT of the trace that `trace.traced`
left beside `run.json` (`trace_cut.json.gz`: the first operations of the
traced stretch, a dozen decode steps): the kernels' self time over the
decode steps that lie whole inside the cut, a step; against the bytes the
family says the kernel must move (`latent_decode_bytes`: the live tokens'
rows, each once, in every layer) over the chip's published HBM bandwidth.
The live tokens are the client's records' over the stretch's first second,
where the cut's steps lie, and not over the whole stretch, which a closed
loop that replaces its documents leaves several percent away.

`ctx` does not say where `run.main` wrote this run's detail, so the cut is
looked for where this process's own command line puts it (`--out`, or the
default that `run.main` builds from the other arguments), and taken only if
it was written after this run's trace began: a cut an earlier run left in
the directory, and a `main(argv)` called from another program, read
nothing.  (With `out_dir` in `ctx`, which is `run.py`'s to add, the command
line would not be read: PERF.md §7.)  None where there is no such cut, no
such kernel in it, or no such function in the family."""

import argparse
import os
import sys

from .. import client, peaks, trace

KERNEL = "paged_latent_attention"


def _cut_path():
    """Where `run.main` wrote this run's detail: its `--out`, or its
    default from the other arguments."""
    ap = argparse.ArgumentParser(add_help=False)
    for name in ("--workload", "--seed", "--trace", "--out"):
        ap.add_argument(name, default=None)
    a, _ = ap.parse_known_args(sys.argv[1:])
    if a.out is None and None in (a.workload, a.seed, a.trace):
        return None
    from ..run import ROOT
    out = a.out or os.path.join(ROOT, "chiprun_out", "benchmark", a.workload,
                                f"seed{a.seed}_trace{a.trace}")
    return os.path.join(os.path.abspath(out), "trace_cut.json.gz")


def kernel_ms_per_step(cut):
    """(the kernels' self time a decode step in ms, steps) over the decode
    steps that lie whole inside device 0's part of a cut."""
    devs = [d for d in cut.get("devices", ()) if d["ops"]]
    if not devs:
        return None, 0
    dev = devs[0]
    runs = {}
    for name, start, dur in dev["modules"]:
        if trace.program_name(name).startswith("jit__lambda"):
            runs.setdefault(name, []).append((start, start + dur))
    if not runs:
        return None, 0
    steps = max(runs.values(), key=len)         # the most-run: the step
    inside = [op for op in dev["ops"]
              if any(s <= op[1] and op[1] + op[2] <= e for s, e in steps)]
    self_ns, _, _ = trace._self_times(inside)
    ns = sum(v for k, v in self_ns.items() if k.startswith(KERNEL))
    if not ns:
        return None, len(steps)
    return ns / 1e6 / len(steps), len(steps)


def read(ctx, args):
    tr = ctx.get("trace") or {}
    bytes_of = getattr(ctx.get("family"), "latent_decode_bytes", None)
    path = _cut_path()
    if not tr.get("window_s") or bytes_of is None or path is None \
            or not os.path.exists(path) \
            or os.path.getmtime(path) < tr["t0"]:
        return None
    ms, _ = kernel_ms_per_step(trace.load(path))
    if ms is None:
        return None
    live = client.live_kv_tokens(ctx, tr["t0"], tr["t0"] + 1.0)
    least_s = bytes_of(ctx["config"], live) \
        / peaks.peak(ctx["device"]["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (ms / 1e3)
