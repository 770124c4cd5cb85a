"""The runtime's lifecycle around one benchmark run: start it from a driver
that never initialises a JAX backend, find the chips, keep what a failed run
leaves for diagnosis, and leave no process behind."""

from __future__ import annotations

import contextlib
import ctypes
import os
import shutil
import signal
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


def adopt_orphans() -> None:
    """Make this process the reaper of all its descendants, so that a
    worker whose agent died is re-parented here, where stop_descendants
    finds it, and not to init."""
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(36, 1, 0, 0, 0) != 0:         # PR_SET_CHILD_SUBREAPER
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_CHILD_SUBREAPER)")


def stop_descendants(grace_s: float = 10.0) -> List[int]:
    """After the runtime's own shutdown: wait until every process this one
    started, directly or not, has gone; kill what outlives the grace.
    Returns the pids killed."""
    me, killed = os.getpid(), []
    deadline = time.monotonic() + grace_s
    while True:
        children = []
        for entry in os.listdir("/proc"):
            if entry.isdigit():
                try:
                    with open(f"/proc/{entry}/stat") as f:
                        ppid = int(f.read().rsplit(")", 1)[1].split()[1])
                except (OSError, IndexError):       # exited under us
                    continue
                if ppid == me:
                    children.append(int(entry))
        if not children:
            return killed
        for pid in children:
            if time.monotonic() > deadline and pid not in killed:
                os.kill(pid, signal.SIGKILL)
                killed.append(pid)
            try:
                os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                pass
        time.sleep(0.05)


@contextlib.contextmanager
def runtime(chips: int, out_dir: str, require_tpu: bool = True,
            runtime_config: Optional[Dict[str, Any]] = None
            ) -> Iterator[Any]:
    """`ray_tpu.init()` ... `shutdown()`.  Raises NoChip unless the node
    advertises exactly `chips` TPU chips (the rehearsal passes
    require_tpu=False and runs on CPU workers).  On the way out: copies the
    session's diagnosis bundles (and, after a failure, its logs) under
    `out_dir`, shuts Serve and the runtime down, and checks that the driver
    itself never initialised a JAX backend.  `runtime_config` (the
    configuration file's `deployment.runtime_config`) lists the program
    defaults this deployment departs from, each as `{"value", "why"}`: an
    entry without its own reason is refused, and every one is named on
    stderr in every run.  It reaches the daemons and workers the way the
    program reads it: `RAY_TPU_<name>` in the environment they inherit."""
    import ray_tpu
    from ray_tpu import serve

    for name, entry in (runtime_config or {}).items():
        if not isinstance(entry, dict) or set(entry) != {"value", "why"} \
                or len(str(entry["why"])) < 40:
            raise ValueError(
                f"runtime_config.{name}: a departure from the program's "
                "default is {\"value\": ..., \"why\": <its own reason>}")
        print(f"benchmark: program default overridden: {name} = "
              f"{entry['value']!r} ({entry['why']})", file=sys.stderr)
        os.environ[f"RAY_TPU_{name}"] = str(entry["value"])
    rt = ray_tpu.init()
    failed = True
    try:
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if require_tpu and found != chips:
            raise NoChip(f"this cell needs {chips} TPU chip(s) and the node "
                         f"advertises {found}")
        yield rt
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            raise RuntimeError("the benchmark's driver initialised a JAX "
                               "backend: it would hold the chip")
        failed = False
    finally:
        for sub in ("diagnosis",) + (("logs",) if failed else ()):
            src = os.path.join(rt.session_dir, sub)
            if os.path.isdir(src):
                shutil.copytree(src, os.path.join(out_dir, sub),
                                dirs_exist_ok=True)
        serve.shutdown()
        ray_tpu.shutdown()


def anomalies(out_dir: str) -> List[str]:
    d = os.path.join(out_dir, "diagnosis")
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


def replicas(app: str) -> List[Any]:
    """The deployment's replica actors, from the controller's routing table
    (what serve.run itself waits on)."""
    import ray_tpu
    from ray_tpu.serve._private.controller import CONTROLLER_NAME
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.get_routing_table.remote(app, -1, 0.0),
                       timeout=60)["replicas"]


def ask(replica: Any, method: str, *args: Any, timeout_s: float = 900.0
        ) -> Any:
    import ray_tpu
    return ray_tpu.get(replica.handle_request.remote(method, args, {}),
                       timeout=timeout_s)


def request_spans(since_wall: float, until_wall: float) -> List[dict]:
    """The flight recorder's `request` spans that started in the interval,
    as the GCS sink holds them (workers flush every second)."""
    import ray_tpu
    rows = ray_tpu._core().gcs_call("get_task_events", {"limit": 100_000})
    return [r for r in rows
            if r.get("event") == "SPAN" and r.get("cat") == "request"
            and since_wall <= r.get("ts", 0.0) < until_wall]


def require_tpu(where: str, device: dict, count: int) -> None:
    if device["platform"] != "tpu" or device["device_count"] != count \
            or len(device["leased_chips"]) != count:
        raise NoChip(f"the {where} did not run on {count} leased TPU "
                     f"chip(s); it reports {device}")


def peak_bytes(memory: Sequence[Dict[str, Any]]) -> int:
    """Peak bytes held on the fullest chip, from each device's
    `memory_stats()`: what the allocator had in use at its peak plus what
    the runtime reserved for the loaded programs' scratch, which
    `peak_bytes_in_use` leaves out (a training step keeps 8.5 GB there)."""
    return max((int(m.get("peak_bytes_in_use") or 0)
                + int(m.get("peak_bytes_reserved") or 0)
                for m in memory), default=0)
