"""Whole-TPU-slice reservation via placement groups.

Equivalent of the reference's slice scheduling (reference:
python/ray/util/tpu.py reserve_tpu_slice + fetch_tpu_slice_name_from_pg and
_private/accelerators/tpu.py:213): a SPREAD placement group whose first
bundle claims the synthetic `TPU-{pod_type}-head` resource (only worker 0 of
a slice exposes it) and whose remaining bundles claim the per-host chips —
so one reservation gangs every host of one slice, the unit of SPMD execution.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .accelerator import TPUAcceleratorManager
from ..util.placement_group import PlacementGroup, placement_group


def slice_bundles(pod_type: str, num_hosts: int,
                  chips_per_host: int = 4) -> List[Dict[str, float]]:
    """Bundle list reserving one whole slice: head bundle + per-host chips."""
    head = {f"TPU-{pod_type}-head": 1.0, "TPU": float(chips_per_host)}
    rest = [{"TPU": float(chips_per_host), f"TPU-{pod_type}": float(chips_per_host)}
            for _ in range(num_hosts - 1)]
    return [head] + rest


def reserve_tpu_slice(pod_type: Optional[str] = None,
                      num_hosts: Optional[int] = None,
                      chips_per_host: Optional[int] = None,
                      timeout_seconds: float = 60.0) -> PlacementGroup:
    """Reserve one whole TPU slice; blocks until placed or raises.

    On a single-host dev box this degenerates to one bundle with the local
    chip count, so the same code path works from v5e-8 to a full pod.
    """
    mgr = TPUAcceleratorManager
    pod_type = pod_type or mgr.pod_type() or "local"
    chips = chips_per_host or mgr.num_chips()
    if not chips:
        raise RuntimeError(
            "reserve_tpu_slice: this host exposes no TPU chip and no "
            "chips_per_host was given")
    hosts = num_hosts or mgr.num_hosts_in_slice()
    if hosts <= 1:
        bundles = [{"TPU": float(chips)}]
    else:
        bundles = slice_bundles(pod_type, hosts, chips)
    pg = placement_group(bundles, strategy="STRICT_SPREAD",
                         name=f"tpu-slice-{pod_type}")
    if not pg.wait(timeout_seconds):
        from ..util.placement_group import remove_placement_group
        remove_placement_group(pg)
        raise TimeoutError(
            f"could not reserve a {pod_type} slice ({hosts} hosts x {chips} "
            f"chips) within {timeout_seconds}s")
    return pg


def fetch_tpu_slice_name_from_pg(pg: PlacementGroup) -> Optional[str]:
    """Slice name of the node holding bundle 0 (reference:
    util/tpu.py fetch_tpu_slice_name_from_pg)."""
    table = pg._table()
    if not table or table.get("state") != "CREATED":
        return None
    node_id = bytes(table["bundles"][0]["node_id"])
    from .._private.worker import global_runtime
    core = global_runtime().core
    for n in core.gcs_call("get_nodes", {}):
        if bytes(n["node_id"]) == node_id:
            return n.get("labels", {}).get("tpu-slice-name")
    return None
