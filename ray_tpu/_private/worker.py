"""Global driver runtime: init/shutdown and the module-level API state.

Equivalent of the reference's driver layer (reference:
python/ray/_private/worker.py — global Worker at :438, init at :1432,
connect at :2460, shutdown at :2082).
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import subprocess
from typing import Any, Dict, List, Optional

from . import node as node_mod
from .config import Config, get_config, set_config
from .core_worker import CoreWorker

logger = logging.getLogger("ray_tpu.worker")

# Well-known head-node address drop (reference: /tmp/ray/ray_current_cluster).
CLUSTER_ADDRESS_FILE = "/tmp/ray_tpu/ray_current_cluster"


def write_cluster_address_file(address: tuple):
    os.makedirs(os.path.dirname(CLUSTER_ADDRESS_FILE), exist_ok=True)
    with open(CLUSTER_ADDRESS_FILE, "w") as f:
        f.write(f"{address[0]}:{address[1]}")


def read_cluster_address_file():
    try:
        with open(CLUSTER_ADDRESS_FILE) as f:
            return f.read().strip() or None
    except OSError:
        return None


class Runtime:
    def __init__(self):
        self.core: Optional[CoreWorker] = None
        self.session_dir: Optional[str] = None
        self.procs: List[subprocess.Popen] = []
        self.gcs_address: Optional[tuple] = None
        self.is_external_cluster = False
        self.mode = "driver"


_runtime: Optional[Runtime] = None


def global_runtime() -> Runtime:
    if _runtime is None:
        raise RuntimeError(
            "ray_tpu.init() must be called before using the API")
    return _runtime


def is_initialized() -> bool:
    return _runtime is not None


def _set_global_from_existing(core: CoreWorker):
    """Install a Runtime for an already-connected core (worker processes)."""
    global _runtime
    rt = Runtime()
    rt.core = core
    rt.session_dir = core.session_dir
    rt.gcs_address = core.gcs_address
    rt.mode = "worker"
    _runtime = rt


def init(address: Optional[str] = None, *,
         num_cpus: Optional[int] = None,
         num_tpus: Optional[int] = None,
         resources: Optional[Dict[str, float]] = None,
         object_store_memory: Optional[int] = None,
         labels: Optional[Dict[str, str]] = None,
         _system_config: Optional[dict] = None,
         log_level: str = "WARNING") -> "Runtime":
    """Start (or connect to) a cluster.

    - no address: start a head node in this process's session — GCS + one
      node agent as subprocesses (reference: ray.init starting
      start_head_processes, node.py:1357)
    - address='host:port': connect to an existing GCS; the driver attaches
      to the agent on this machine (reference: ray.init(address=...)).
    """
    global _runtime
    if _runtime is not None:
        return _runtime
    logging.basicConfig(level=log_level)
    set_config(Config(_system_config))
    cfg = get_config()
    rt = Runtime()
    if address is None and os.environ.get("RAY_TPU_ADDRESS"):
        # Driver spawned under a submitted job (or user exported the
        # address): join that cluster (reference: RAY_ADDRESS).
        address = os.environ["RAY_TPU_ADDRESS"]
    address_was_auto = address == "auto"
    if address == "auto":
        address = read_cluster_address_file()
        if address is None:
            raise ConnectionError(
                "address='auto' but no running cluster was found "
                f"({CLUSTER_ADDRESS_FILE} missing); start one with "
                "`python -m ray_tpu start --head`")
    from . import auth
    if address is None:
        rt.session_dir = node_mod.new_session_dir()
        # Session token BEFORE any daemon spawns: children inherit it via
        # child_env() and their servers require it from birth.
        # write_wellknown=False: only `ray_tpu start --head` writes the
        # cluster address file, so only it may write the paired token drop.
        auth.ensure_cluster_token(rt.session_dir, write_wellknown=False)
        gcs_proc, gcs_addr = node_mod.start_gcs(
            rt.session_dir, system_config=_system_config)
        rt.procs.append(gcs_proc)
        try:
            store_cap = object_store_memory or _auto_store_bytes(cfg)
            res = node_mod.default_resources(num_cpus, num_tpus, resources)
            agent_proc, agent_addr, store_path, node_id = \
                node_mod.start_agent(
                    rt.session_dir, gcs_addr, res, labels=labels,
                    store_capacity=store_cap, system_config=_system_config)
        except BaseException:
            _stop_procs(rt.procs)       # a failed init leaves no daemon
            raise
        rt.procs.append(agent_proc)
        rt.gcs_address = gcs_addr
    else:
        # Attaching driver: the cluster's token comes from the env, a
        # token file, or the well-known local drop — install it before
        # the first connect.  The drop is only trusted when the target
        # IS the local cluster it was written for: address='auto', or an
        # explicit address equal to the one in the cluster address file
        # (head start writes the pair together).  Any other explicit
        # address skips it — a stale token from an older local cluster
        # would produce opaque ConnectionLost failures instead of a
        # clear auth error.
        local_attach = address_was_auto or \
            address == read_cluster_address_file()
        tok = auth.install_process_token(
            allow_cluster_file=local_attach)
        if tok is None and not auth.auth_disabled():
            logger.warning(
                "no cluster auth token resolved for %s; connection will "
                "fail if the cluster requires one (set %s)", address,
                auth.TOKEN_ENV)
        host, port = address.rsplit(":", 1)
        rt.gcs_address = (host, int(port))
        rt.is_external_cluster = True
        # Find this machine's agent via the GCS node table.
        import asyncio
        from . import rpc as rpc_mod

        async def _find():
            conn = await rpc_mod.connect(rt.gcs_address)
            nodes = await conn.call("get_nodes", {})
            await conn.close()
            return nodes

        nodes = asyncio.run(_find())
        alive = [n for n in nodes if n["alive"]]
        if not alive:
            raise RuntimeError("no alive nodes in cluster")
        # Prefer a node that isn't mid-drain as the driver's home agent.
        n0 = ([n for n in alive if not n.get("draining")] or alive)[0]
        agent_addr = tuple(n0["address"])
        store_path = n0["store_path"]
        node_id = bytes(n0["node_id"])
        rt.session_dir = n0.get("session_dir") or node_mod.new_session_dir()

    try:
        core = CoreWorker(
            mode="driver", gcs_address=rt.gcs_address,
            agent_address=agent_addr, store_path=store_path,
            node_id=node_id, session_dir=rt.session_dir)
        core.start_driver()
    except BaseException:
        _stop_procs(rt.procs)
        raise
    rt.core = core
    _runtime = rt
    from .usage import record_session
    record_session(core)
    atexit.register(shutdown)
    return rt


def _auto_store_bytes(cfg) -> int:
    if cfg.object_store_memory_bytes:
        return cfg.object_store_memory_bytes
    try:
        import psutil
        avail = psutil.virtual_memory().available
    except Exception:
        avail = 8 * 1024**3
    cap = int(min(avail * cfg.object_store_auto_fraction,
                  cfg.object_store_max_auto_bytes))
    # The arena is one /dev/shm file: it must also fit the file-size limit
    # the process runs under (a harness's `ulimit -f`), or the agent's
    # ftruncate fails with EFBIG and the node never starts.
    from .shm_store import arena_bytes_limit
    limit = arena_bytes_limit()
    if limit is not None and limit < cap:
        logger.warning(
            "object store arena sized to %d bytes instead of %d to fit "
            "this process's file-size limit (RLIMIT_FSIZE)", limit, cap)
        cap = limit
    return cap


def shutdown():
    global _runtime
    rt = _runtime
    if rt is None:
        return
    _runtime = None
    if rt.core is not None:
        try:
            rt.core.shutdown()
        except Exception:
            pass
    _stop_procs(rt.procs)


def _stop_procs(procs) -> None:
    """Terminate the daemons this process started, newest first."""
    for proc in reversed(procs):
        try:
            proc.terminate()
        except ProcessLookupError:
            pass
    for proc in reversed(procs):
        try:
            proc.wait(timeout=3)
        except subprocess.TimeoutExpired:
            proc.kill()
