"""TPU accelerator manager: chip discovery, topology, slice metadata, and the
environment that confines a worker process to the chips its lease holds.

Equivalent of the reference's TPUAcceleratorManager (reference:
python/ray/_private/accelerators/tpu.py — chip counting per host :294,
TPU_VISIBLE_CHIPS :377, pod type via GCE metadata :420, worker-id/topology
env+metadata :479,:514, synthetic `TPU-{pod_type}-head` resource :576,
accelerator labels :642).

Discovery never initialises JAX: a driver or agent that opened the TPU
client would hold the chips its workers need (one process per chip).  Chips
come from ``TPU_VISIBLE_CHIPS`` or from the device files a TPU VM exposes
(``/dev/accel<N>`` up to v4, ``/dev/vfio/<N>`` from v5e on); a host with
neither has no chips.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, List, Optional, Sequence

_GCE_TPU_ENV = "TPU_ACCELERATOR_TYPE"     # e.g. "v5litepod-16"
_TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
_TPU_TOPOLOGY_ENV = "TPU_TOPOLOGY"        # e.g. "4x4"
_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# Set by the agent in a worker whose lease holds real chips: the ids, for
# the worker to report and to tell "this process must be on a TPU".
LEASED_CHIPS_ENV = "RAY_TPU_LEASED_CHIPS"

# libtpu 0.0.34 reads the per-process chip grid from
# TPU_CHIPS_PER_PROCESS_BOUNDS / TPU_PROCESS_BOUNDS and still honours the
# older *_HOST_BOUNDS names, which a TPU VM image may export for the whole
# host — so a sub-host lease sets both families.  A non-default
# per-process grid also makes libtpu skip its whole-host lock file, which
# is what lets several one-chip processes share a host.
_SUBHOST_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


class TPUAcceleratorManager:
    """Static methods mirroring the reference's AcceleratorManager ABC
    (reference: _private/accelerators/accelerator.py:18)."""

    _cached_chip_ids: Optional[List[int]] = None

    @staticmethod
    def accelerator_name() -> str:
        return "TPU"

    @classmethod
    def chip_ids(cls) -> List[int]:
        """Ids of the chips this host exposes, as TPU_VISIBLE_CHIPS counts
        them.  A malformed TPU_VISIBLE_CHIPS raises; it is never read as
        "no chips"."""
        if cls._cached_chip_ids is None:
            visible = os.environ.get(_VISIBLE_CHIPS_ENV)
            if visible:
                ids = [int(c) for c in visible.split(",") if c.strip()]
            else:
                accel = [int(m.group(1)) for p in glob.glob("/dev/accel*")
                         if (m := re.fullmatch(r"/dev/accel(\d+)", p))]
                # vfio names are IOMMU groups, not chip indices: count them.
                ids = sorted(accel) or list(
                    range(len(glob.glob("/dev/vfio/[0-9]*"))))
            cls._cached_chip_ids = ids
        return list(cls._cached_chip_ids)

    @classmethod
    def num_chips(cls) -> int:
        """Chips visible to this host."""
        return len(cls.chip_ids())

    @staticmethod
    def pod_type() -> Optional[str]:
        """e.g. 'v5litepod-16'. Env first, then GCE metadata server."""
        env = os.environ.get(_GCE_TPU_ENV)
        if env:
            return env
        return _gce_metadata("instance/attributes/accelerator-type")

    @staticmethod
    def topology() -> Optional[str]:
        env = os.environ.get(_TPU_TOPOLOGY_ENV)
        if env:
            return env
        return _gce_metadata("instance/attributes/topology")

    @staticmethod
    def worker_id() -> Optional[int]:
        env = os.environ.get(_TPU_WORKER_ID_ENV)
        if env is not None:
            return int(env)
        v = _gce_metadata("instance/attributes/agent-worker-number")
        return int(v) if v is not None else None

    @staticmethod
    def slice_name() -> Optional[str]:
        return (os.environ.get("TPU_NAME")
                or _gce_metadata("instance/attributes/instance-id"))

    @classmethod
    def num_hosts_in_slice(cls) -> int:
        pod = cls.pod_type()
        if not pod:
            return 1
        try:
            total_chips = int(pod.rsplit("-", 1)[1])
        except (IndexError, ValueError):
            return 1
        per_host = cls.num_chips()
        if not per_host:
            raise RuntimeError(
                f"pod type {pod!r} is set but this host exposes no TPU "
                "chip (TPU_VISIBLE_CHIPS, /dev/accel<N>, /dev/vfio/<N>): "
                "cannot size the slice")
        return max(1, total_chips // per_host)

    @classmethod
    def node_resources(cls) -> Dict[str, float]:
        """Resources this host contributes, including the synthetic slice-head
        resource used for gang reservation of whole slices (reference:
        tpu.py:576 `TPU-{pod_type}-head` on worker 0)."""
        out: Dict[str, float] = {}
        n = cls.num_chips()
        if n:
            out["TPU"] = float(n)
            pod = cls.pod_type()
            if pod:
                out[f"TPU-{pod}"] = float(n)
                if cls.worker_id() == 0:
                    out[f"TPU-{pod}-head"] = 1.0
        return out

    @classmethod
    def node_labels(cls) -> Dict[str, str]:
        """Accelerator labels (reference: tpu.py:642)."""
        out: Dict[str, str] = {}
        if cls.num_chips():
            out["accelerator-type"] = "TPU"
            if cls.pod_type():
                out["tpu-pod-type"] = cls.pod_type()
            if cls.topology():
                out["tpu-topology"] = cls.topology()
            if cls.slice_name():
                out["tpu-slice-name"] = cls.slice_name()
            wid = cls.worker_id()
            if wid is not None:
                out["tpu-worker-id"] = str(wid)
        return out

    @staticmethod
    def worker_env(chip_ids: Sequence[int],
                   host_chip_ids: Sequence[int]) -> Dict[str, str]:
        """Env that confines a worker to `chip_ids` of a host exposing
        `host_chip_ids` (reference: tpu.py:377
        set_current_process_visible_accelerator_ids).  JAX_PLATFORMS=tpu
        makes losing the chip an error instead of a CPU fallback.  A lease
        over the whole host keeps libtpu's defaults (and its whole-host
        lock file); a sub-host lease names its chips and their grid."""
        ids = ",".join(str(c) for c in chip_ids)
        env = {"JAX_PLATFORMS": "tpu", LEASED_CHIPS_ENV: ids}
        if sorted(chip_ids) != sorted(host_chip_ids):
            bounds = _SUBHOST_BOUNDS.get(len(chip_ids))
            if bounds is None:
                raise ValueError(
                    f"{len(chip_ids)} of {len(host_chip_ids)} chips is not "
                    "a grid libtpu can give one process: lease 1, 2 or "
                    "all of a host's chips")
            env[_VISIBLE_CHIPS_ENV] = ids
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
            env["TPU_CHIPS_PER_HOST_BOUNDS"] = bounds
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
            env["TPU_HOST_BOUNDS"] = "1,1,1"
        return env

    @staticmethod
    def leased_chip_ids() -> List[int]:
        """Chips the agent leased to THIS worker process; [] in a driver,
        a CPU worker, or a worker on injected (fake) TPU resources."""
        v = os.environ.get(LEASED_CHIPS_ENV, "")
        return [int(c) for c in v.split(",") if c]


def require_tpu_backend(who: str):
    """Initialise this process's JAX backend and return its first device,
    raising unless it is a TPU — for code that was promised a chip
    (use_tpu=True, a lease holding chips) and must not run on a fallback."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"{who} was given TPU chips but JAX initialised "
            f"platform={dev.platform!r} ({dev.device_kind}); "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}, "
            f"leased chips={os.environ.get(LEASED_CHIPS_ENV)!r}")
    return dev


def device_report() -> Dict[str, object]:
    """What this process's JAX runs on, as JAX reports it — only the
    process that holds the chip can say.  `peak_bytes_in_use` is per
    local device (None where the backend keeps no memory stats)."""
    import jax
    from .._private.compile_cache import compile_cache_stats
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()],
        "leased_chips": TPUAcceleratorManager.leased_chip_ids(),
        "pid": os.getpid(),
        "compile_cache": compile_cache_stats(),
    }


def require_cluster_tpus(n: float, who: str) -> float:
    """The most TPU chips any one alive node advertises; raises when that
    is fewer than `n`.  Called by the driver before it queues a TPU
    request, so a request no node can ever hold fails as infeasible at
    once instead of waiting out a scheduling timeout."""
    import ray_tpu
    most = max((node["resources_total"].get("TPU", 0.0)
                for node in ray_tpu.nodes() if node["alive"]), default=0.0)
    if most < n:
        raise RuntimeError(
            f"{who} is infeasible: it needs {n:g} TPU chip(s) on one node "
            f"and the best alive node advertises {most:g} (discovery reads "
            "TPU_VISIBLE_CHIPS, /dev/accel<N>, /dev/vfio/<N>)")
    return most


@functools.lru_cache(maxsize=None)
def _gce_metadata(path: str, timeout: float = 0.35) -> Optional[str]:
    """GCE metadata lookup; None off-GCE.  Answers are cached for the life
    of the process (instance attributes do not change).  The whole lookup
    — name resolution included, which urlopen's timeout does not cover —
    is bounded by running it on a daemon thread that is abandoned at the
    deadline; once the server proved unreachable no later path is tried."""
    import threading
    import urllib.error
    import urllib.request

    if _metadata_unreachable:
        return None
    answer: List[Optional[str]] = []

    def _fetch() -> None:
        req = urllib.request.Request(
            f"http://metadata.google.internal/computeMetadata/v1/{path}",
            headers={"Metadata-Flavor": "Google"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                answer.append(r.read().decode())
        except urllib.error.HTTPError:
            answer.append(None)          # reachable; attribute not set
        except (urllib.error.URLError, OSError):
            pass                          # unreachable

    t = threading.Thread(target=_fetch, daemon=True, name="gce-metadata")
    t.start()
    t.join(4 * timeout)
    if not answer:
        _metadata_unreachable.append(path)
        return None
    return answer[0]


# Non-empty once a lookup found no metadata server (off-GCE, sealed host).
_metadata_unreachable: List[str] = []
