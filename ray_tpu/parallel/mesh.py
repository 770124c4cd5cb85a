"""Device-mesh construction for SPMD parallelism on TPU pods.

TPU-native replacement for the reference's process-group scaffolding
(reference: python/ray/train/v2/jax/config.py:29-57 builds a jax.distributed
world; python/ray/util/collective/collective.py:76 GroupManager hands out NCCL
groups).  On TPU the unit of parallelism is a *named mesh axis*, not a
communicator: XLA compiles collectives (psum/all_gather/ppermute) over ICI
from sharding annotations, so the framework's job is to build the right Mesh
and hand out shardings.

Canonical axis order (outer→inner, DCN→ICI):
    pp   pipeline stages        (DCN or slice boundary)
    dp   pure data parallel     (DCN-friendly: only gradient psum)
    fsdp fully-sharded data parallel (ICI: all-gather weights per layer)
    sp   sequence/context parallel   (ICI: ring attention / all-to-all)
    tp   tensor parallel             (innermost ICI: activation collectives)
    ep   expert parallel             (shares devices with fsdp/sp in MoE)
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

AXES = ("pp", "dp", "fsdp", "sp", "tp")
# Expert parallelism reuses the fsdp×sp submesh in MoE layers (same devices,
# different logical view), matching the usual TPU MoE recipe.  Referenced by
# the "expert" rule in sharding.LogicalAxisRules.default().
EP_AXES: Tuple[str, str] = ("fsdp", "sp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape.  -1 at most once = "fill with what's left".

    Example: MeshSpec(dp=-1, tp=4) on 32 chips → pp=1 dp=8 fsdp=1 sp=1 tp=4.
    """
    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1

    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXES}

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = self.sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed}")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return MeshSpec(**sizes)

    @property
    def n_devices(self) -> int:
        return math.prod(self.sizes().values())


def build_mesh(spec: Optional[MeshSpec] = None,
               *,
               devices: Optional[Sequence] = None,
               allow_split_physical_axes: bool = True):
    """Create a jax.sharding.Mesh with the canonical axis names.

    Uses mesh_utils.create_device_mesh so the logical axes land on physical
    ICI topology contiguously (innermost logical axis = densest ICI links).
    Falls back to a simple reshape for host/CPU device sets (tests run on an
    8-device virtual CPU mesh, see tests/conftest.py).
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    spec = (spec or MeshSpec(dp=-1)).resolve(len(devices))
    shape = tuple(spec.sizes()[a] for a in AXES)

    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        try:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=devices,
                allow_split_physical_axes=allow_split_physical_axes)
        except (ValueError, NotImplementedError) as e:
            logger.warning(
                "build_mesh: no topology-aware layout for %s over %d %s "
                "devices (%s); using enumeration order — logical axes may "
                "not sit on neighbouring chips", dict(zip(AXES, shape)),
                len(devices), devices[0].device_kind, e)
            dev_array = np.asarray(devices).reshape(shape)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def single_device_mesh(device=None):
    """1-chip mesh: every axis size 1 — shardings become no-ops, the same
    model code runs unmodified (used by the driver's single-chip entry())."""
    import jax
    if device is None:
        device = jax.devices()[0]
    return build_mesh(MeshSpec(), devices=[device])


def host_local_mesh(spec: Optional[MeshSpec] = None):
    """Mesh over this host's addressable devices only (one worker of a
    multi-host job before jax.distributed is up, or a test process)."""
    import jax
    return build_mesh(spec, devices=jax.local_devices())


def mesh_info(mesh) -> Dict[str, int]:
    return {name: size for name, size in mesh.shape.items()}
