"""The decode step's state update and query's share of their roofline.

The kernel runs once a layer and traces as `retention_step.N`; as
`latent_decode_roofline` reads its kernels, this reads the CUT of the trace
that `trace.traced` left beside `run.json` (found as that reader finds it):
the kernels' self time over the decode steps that lie whole inside the cut,
a step; against the bytes the family says they must move
(`retention_step_bytes`: the live sequences' published state, read and
written, in every layer) over the chip's published HBM bandwidth.  The live
sequences are the client's records' over the stretch's first second, where
the cut's steps lie.  None where there is no such cut, no such kernel in it
(a program that keeps XLA's form has no name to select by), or no such
function in the family."""

import os

from .. import peaks, trace
from .decode_roofline_hybrid import _live_seqs
from .latent_decode_roofline import _cut_path

KERNEL = "retention_step"


def kernel_ms_per_step(cut):
    """The kernels' self time a decode step in ms, over the decode steps
    (the most-run `jit__lambda`) that lie whole inside device 0's part of a
    cut; None where it holds no step or no such kernel."""
    devs = [d for d in cut.get("devices", ()) if d["ops"]]
    if not devs:
        return None
    runs = {}
    for name, start, dur in devs[0]["modules"]:
        if trace.program_name(name).startswith("jit__lambda"):
            runs.setdefault(name, []).append((start, start + dur))
    if not runs:
        return None
    steps = max(runs.values(), key=len)
    inside = [op for op in devs[0]["ops"]
              if any(s <= op[1] and op[1] + op[2] <= e for s, e in steps)]
    self_ns, _, _ = trace._self_times(inside)
    ns = sum(v for k, v in self_ns.items() if k.startswith(KERNEL))
    return ns / 1e6 / len(steps) if ns else None


def read(ctx, args):
    tr = ctx.get("trace") or {}
    bytes_of = getattr(ctx.get("family"), "retention_step_bytes", None)
    path = _cut_path()
    if not tr.get("window_s") or bytes_of is None or path is None \
            or not os.path.exists(path) \
            or os.path.getmtime(path) < tr["t0"]:
        return None
    ms = kernel_ms_per_step(trace.load(path))
    if ms is None:
        return None
    seqs = _live_seqs(ctx, tr["t0"], tr["t0"] + 1.0)
    least_s = bytes_of(ctx["config"], seqs) \
        / peaks.peak(ctx["device"]["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (ms / 1e3)
