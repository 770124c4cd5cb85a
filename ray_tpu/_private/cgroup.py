"""ctypes binding to the native cgroup v2 manager (src/cgroup).

Reference: src/ray/common/cgroup2/cgroup_manager.h — workers live in a
framework cgroup so the kernel bounds their memory/cpu.  Disabled by
default (config `cgroup_enabled`); every operation degrades to a no-op
when cgroup2 is unavailable or read-only (the common container case).
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Optional

from . import native_build

logger = logging.getLogger("ray_tpu")

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "src", "cgroup", "cgroup_manager.cc")
_SO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_cgroup.so")

_build_lock = threading.Lock()
_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        native_build.build_so(_SRC, _SO)
        lib = ctypes.CDLL(_SO)
        lib.cg_available.restype = ctypes.c_int
        lib.cg_create.argtypes = [ctypes.c_char_p]
        lib.cg_create.restype = ctypes.c_int
        lib.cg_set_memory_max.argtypes = [ctypes.c_char_p,
                                          ctypes.c_longlong]
        lib.cg_set_memory_max.restype = ctypes.c_int
        lib.cg_set_cpu_weight.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.cg_set_cpu_weight.restype = ctypes.c_int
        lib.cg_add_pid.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.cg_add_pid.restype = ctypes.c_int
        lib.cg_remove.argtypes = [ctypes.c_char_p]
        lib.cg_remove.restype = ctypes.c_int
        _lib = lib
    return _lib


def available() -> bool:
    try:
        return bool(_load().cg_available())
    except Exception:
        return False


class WorkerCgroup:
    """One cgroup holding a node's worker processes (reference: the
    'application' half of the system/application split)."""

    def __init__(self, name: str = "ray_tpu_workers",
                 memory_max: Optional[int] = None,
                 cpu_weight: Optional[int] = None):
        self.name = name.encode()
        self.active = False
        if not available():
            return
        lib = _load()
        if lib.cg_create(self.name) != 0:
            return
        self.active = True
        if memory_max is not None:
            lib.cg_set_memory_max(self.name, memory_max)
        if cpu_weight is not None:
            lib.cg_set_cpu_weight(self.name, cpu_weight)
        logger.info("worker cgroup %s active", name)

    def add(self, pid: int) -> bool:
        if not self.active:
            return False
        return _load().cg_add_pid(self.name, pid) == 0

    def close(self):
        if self.active:
            _load().cg_remove(self.name)
            self.active = False
