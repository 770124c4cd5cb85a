"""Node/process bootstrap: spawns the GCS and node agents.

Equivalent of the reference's Node + services (reference:
python/ray/_private/node.py start_head_processes :1357,
python/ray/_private/services.py start_gcs_server :1434 / start_raylet :1518).
Daemons are plain subprocesses signalling readiness via a ready-file, with
logs under <session_dir>/logs.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Dict, Optional, Tuple

from .ids import NodeID


def install_daemon_profiler(tag: str):
    """Live + post-mortem profiling for a daemon process.

    Always returns the live-introspection RPC handlers
    (``{"stacks", "cpu_profile"}`` from `diagnosis.profile_handlers`) so
    the caller can register them on its existing server conns — this is
    how `cluster_profile` reaches daemons, not just workers (reference:
    dashboard reporter's py-spy profiling fills this role for live
    processes).  Additionally, when RAY_TPU_PROFILE_WORKER_DIR is set,
    arms the whole-process cProfile dumped on SIGTERM/exit.  Shared by
    the worker, GCS and agent mains."""
    from . import diagnosis
    handlers = diagnosis.profile_handlers(tag)
    prof_dir = os.environ.get("RAY_TPU_PROFILE_WORKER_DIR")
    if not prof_dir:
        return handlers
    import atexit
    import cProfile
    import signal
    prof = cProfile.Profile()
    prof.enable()
    path = os.path.join(prof_dir, f"{tag}_{os.getpid()}.pstats")

    def _dump(*_a):
        prof.disable()
        prof.dump_stats(path)

    # Daemons that install their own SIGTERM handling and leave via
    # os._exit (the agent's bounded graceful drain) never reach atexit —
    # dump_profile() lets their exit path flush the profile explicitly.
    global dump_profile
    dump_profile = _dump
    atexit.register(_dump)
    signal.signal(signal.SIGTERM, lambda *a: (_dump(), os._exit(0)))
    return handlers


def dump_profile(*_a) -> None:
    """No-op unless install_daemon_profiler armed it (see above)."""


def _wait_ready(path: str, proc: subprocess.Popen, timeout: float = 30.0) -> dict:
    """The daemon's ready record.  A daemon that exits first, or is not
    ready in time (it is then killed: a failed start leaves no process),
    raises with the end of its stderr log — the caller may be on a machine
    nobody can open the log on."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        if proc.poll() is not None:
            raise RuntimeError(
                f"daemon exited with code {proc.returncode} before ready; "
                + _err_tail(proc))
        time.sleep(0.02)
    proc.kill()
    proc.wait()
    raise TimeoutError(f"daemon not ready after {timeout:.0f}s ({path}); "
                       + _err_tail(proc))


def _err_tail(proc: subprocess.Popen, nbytes: int = 3000) -> str:
    log = proc.err_log               # set by _spawn
    with open(log, "rb") as f:
        f.seek(max(0, os.path.getsize(log) - nbytes))
        tail = f.read().decode(errors="replace").strip()
    return f"its stderr ({log}) ends:\n{tail or '(empty)'}"


def new_session_dir() -> str:
    base = os.path.join(tempfile.gettempdir(), "ray_tpu")
    os.makedirs(base, exist_ok=True)
    session = os.path.join(
        base, f"session_{time.strftime('%Y%m%d_%H%M%S')}_{os.getpid()}_"
              f"{uuid.uuid4().hex[:6]}")
    os.makedirs(os.path.join(session, "logs"), exist_ok=True)
    return session


def _pkg_root() -> str:
    """Directory containing the ray_tpu package, for child PYTHONPATH."""
    import ray_tpu
    return os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))


def child_env(extra: Optional[Dict[str, str]] = None) -> dict:
    """Environment for spawned daemons/workers: guarantees ray_tpu is
    importable even when the driver added it to sys.path manually, and
    names the JAX compilation cache every child shares (compile_cache.py:
    the variable if set, else the fixed in-checkout directory)."""
    from .compile_cache import ENV as cache_env, compile_cache_dir
    env = dict(os.environ)
    root = _pkg_root()
    pp = env.get("PYTHONPATH", "")
    if root not in pp.split(os.pathsep):
        env["PYTHONPATH"] = root + (os.pathsep + pp if pp else "")
    env[cache_env] = compile_cache_dir()
    env.update(extra or {})
    return env


def _spawn(args, session_dir: str, tag: str) -> subprocess.Popen:
    log_dir = os.path.join(session_dir, "logs")
    os.makedirs(log_dir, exist_ok=True)
    out = open(os.path.join(log_dir, f"{tag}.out"), "ab")
    err = open(os.path.join(log_dir, f"{tag}.err"), "ab")
    proc = subprocess.Popen(args, stdout=out, stderr=err,
                            start_new_session=True, env=child_env())
    proc.err_log = err.name          # read back by _wait_ready on failure
    return proc


def start_gcs(session_dir: str, port: int = 0,
              system_config: Optional[dict] = None,
              ha: bool = False
              ) -> Tuple[subprocess.Popen, tuple]:
    """Spawn the GCS with its journal in the session dir; restarting it
    with the same session_dir + port replays the journal (reference:
    Redis-backed GCS restart, gcs_init_data.cc).

    ``ha=True`` arms the high-availability plane (docs/control_plane.md
    §8): the primary claims a disk lease under the session dir, renews
    it while it holds agent-heartbeat majority, and advertises its
    address through the session address file so a warm standby (see
    `start_gcs_standby`) can take over after a crash."""
    ready = os.path.join(session_dir, f"gcs_ready_{uuid.uuid4().hex[:6]}.json")
    args = [sys.executable, "-m", "ray_tpu._private.gcs",
            "--port", str(port), "--ready-file", ready,
            "--journal", os.path.join(session_dir, "gcs_journal.msgpack"),
            "--system-config",
            json.dumps(system_config) if system_config else ""]
    if ha:
        args += ["--ha-dir", session_dir]
    proc = _spawn(args, session_dir, "gcs")
    info = _wait_ready(ready, proc)
    return proc, tuple(info["address"])


def start_gcs_standby(session_dir: str, port: int = 0,
                      system_config: Optional[dict] = None
                      ) -> subprocess.Popen:
    """Spawn a warm-standby GCS: it tails the primary's journal from the
    shared session dir, keeps hot table replicas, and promotes itself —
    bumping the cluster epoch — once the primary's lease goes a full TTL
    without renewal.  Returns as soon as the standby confirms it is
    tailing (its promotion, if ever, is autonomous)."""
    ready = os.path.join(session_dir,
                         f"gcs_standby_ready_{uuid.uuid4().hex[:6]}.json")
    proc = _spawn(
        [sys.executable, "-m", "ray_tpu._private.gcs",
         "--standby", "--port", str(port), "--ready-file", ready,
         "--journal", os.path.join(session_dir, "gcs_journal.msgpack"),
         "--ha-dir", session_dir,
         "--system-config",
         json.dumps(system_config) if system_config else ""],
        session_dir, "gcs_standby")
    _wait_ready(ready, proc)
    return proc


def start_agent(session_dir: str, gcs_address: tuple,
                resources: Dict[str, float],
                labels: Optional[Dict[str, str]] = None,
                store_capacity: int = 1 << 30,
                system_config: Optional[dict] = None,
                node_id: Optional[bytes] = None,
                ) -> Tuple[subprocess.Popen, tuple, str, bytes]:
    """Spawn a node agent.  When `resources` holds TPU and this host
    exposes real chips, the agent gets their ids so it can confine each
    TPU worker to the chips of its lease; injected TPU counts on a host
    without chips (tests) get none and workers' environments are left
    alone."""
    node_id = node_id or NodeID.from_random().binary()
    chips = []
    if resources.get("TPU"):
        from ..tpu.accelerator import TPUAcceleratorManager
        chips = TPUAcceleratorManager.chip_ids()
    ready = os.path.join(session_dir,
                         f"agent_ready_{node_id.hex()[:8]}.json")
    proc = _spawn(
        [sys.executable, "-m", "ray_tpu._private.agent",
         "--gcs-address", json.dumps(list(gcs_address)),
         "--session-dir", session_dir,
         "--node-id", node_id.hex(),
         "--resources", json.dumps(resources),
         "--labels", json.dumps(labels or {}),
         "--tpu-chips", json.dumps(chips),
         "--store-capacity", str(store_capacity),
         "--system-config", json.dumps(system_config) if system_config else "",
         "--ready-file", ready],
        session_dir, f"agent_{node_id.hex()[:8]}")
    info = _wait_ready(ready, proc)
    return proc, tuple(info["address"]), info["store_path"], node_id


def default_resources(num_cpus: Optional[int] = None,
                      num_tpus: Optional[int] = None,
                      resources: Optional[Dict[str, float]] = None
                      ) -> Dict[str, float]:
    """Detect node resources (reference: _private/resource_spec.py +
    accelerator managers). TPU chips are detected via the accelerator
    manager (ray_tpu/tpu/accelerator.py), which never initialises JAX; a
    discovery error propagates rather than reading as "no chips"."""
    out: Dict[str, float] = dict(resources or {})
    out.setdefault("CPU", float(num_cpus if num_cpus is not None
                                else os.cpu_count() or 1))
    if num_tpus is None:
        from ..tpu.accelerator import TPUAcceleratorManager
        num_tpus = TPUAcceleratorManager.num_chips()
    if num_tpus:
        out.setdefault("TPU", float(num_tpus))
    out.setdefault("memory", float(_available_memory()))
    return out


def _available_memory() -> int:
    try:
        import psutil
        return psutil.virtual_memory().total
    except Exception:
        return 8 * 1024**3
