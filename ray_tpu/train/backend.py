"""Training backends: per-worker process-group setup.

Reference: python/ray/train/backend.py:32 (Backend/BackendConfig with
on_start/on_shutdown hooks) and the TPU-native primary backend
python/ray/train/v2/jax/config.py:21,74 (_JaxBackend running
jax.distributed.initialize(master_addr, num_workers, index) on every
worker).  No NCCL/torch path: JAX's coordination service + XLA collectives
over ICI/DCN are the only distributed substrate.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional


class BackendConfig:
    def backend_cls(self):
        return Backend


class Backend:
    """Hooks run inside each worker actor around the training function."""

    def __init__(self, config: Optional[BackendConfig] = None):
        self.config = config

    def on_start(self, worker_ctx: Dict[str, Any]) -> None:
        """worker_ctx: {world_rank, world_size, master_addr, master_port,
        local_rank, num_workers}."""

    def on_shutdown(self) -> None:
        pass


class JaxConfig(BackendConfig):
    """reference: train/v2/jax/config.py:21 JaxConfig — TPU-SPMD backend.

    cpu_devices_per_process: when use_tpu=False each worker process is
    pinned to this many virtual CPU devices BEFORE the jax backend
    initializes.  Without the pin every worker inherits the driver's
    --xla_force_host_platform_device_count (e.g. 8) and an N-process world
    sees N*8 devices instead of N*cpu_devices_per_process."""

    def __init__(self, use_tpu: bool = True,
                 coordinator_port: int = 0,
                 cpu_devices_per_process: int = 1):
        self.use_tpu = use_tpu
        self.coordinator_port = coordinator_port
        self.cpu_devices_per_process = cpu_devices_per_process

    def backend_cls(self):
        return _JaxBackend


class _JaxBackend(Backend):
    """Forms the jax.distributed world (reference:
    train/v2/jax/config.py:29-57 _setup_jax_environment): every worker calls
    jax.distributed.initialize(coordinator, num_processes, process_id); XLA
    then sees the full multi-host device set and pjit shards over it."""

    def __init__(self, config: JaxConfig):
        self.config = config
        self._initialized = False

    def _pin_local_devices(self, strict: bool) -> None:
        """Pin this worker's local device count before backend init
        (reference: config.py:29-57 sets JAX_PLATFORMS per worker).  With
        use_tpu the agent already confined this process to its lease's
        chips (JAX_PLATFORMS=tpu + TPU_VISIBLE_CHIPS); on CPU we must fix
        the per-process virtual device count explicitly."""
        import jax
        if self.config.use_tpu:
            return
        n = self.config.cpu_devices_per_process
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split()
                         if "xla_force_host_platform_device_count" not in f)
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()
        try:
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", n)
        except RuntimeError as e:
            # Backend already initialized in this process — device count
            # can no longer change.  Only fatal if the count is wrong AND
            # we are forming a multi-process world (which would silently
            # mis-size otherwise); a solo worker just keeps its devices.
            if strict and len(jax.local_devices()) != n:
                raise RuntimeError(
                    "jax backend already initialized with "
                    f"{len(jax.local_devices())} local devices before "
                    f"_JaxBackend could pin it to {n}; TrainWorker "
                    "processes must not touch jax before setup_backend()"
                ) from e

    def on_start(self, worker_ctx: Dict[str, Any]) -> None:
        self._pin_local_devices(strict=worker_ctx["world_size"] > 1)
        if worker_ctx["world_size"] > 1:
            # (A single worker runs standalone: no coordinator.)
            import jax
            coordinator = (f"{worker_ctx['master_addr']}:"
                           f"{worker_ctx['master_port']}")
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=worker_ctx["world_size"],
                process_id=worker_ctx["world_rank"])
            self._initialized = True
        if self.config.use_tpu:
            # use_tpu is a promise: a worker that lost its chip must fail
            # here, not train on whatever platform JAX fell back to.
            from ..tpu.accelerator import require_tpu_backend
            require_tpu_backend("JaxTrainer(use_tpu=True) worker "
                                f"rank {worker_ctx['world_rank']}")

    def on_shutdown(self) -> None:
        if self._initialized:
            import jax
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            self._initialized = False


class TorchConfig(BackendConfig):
    """reference: train/torch/config.py:43 TorchConfig — CPU/gloo process
    groups (the reference's nccl path has no TPU analogue; torch models
    on this runtime train with gloo across hosts, or convert to JAX for
    the accelerator path)."""

    def __init__(self, backend: str = "gloo",
                 init_timeout_s: float = 120.0):
        if backend not in ("gloo",):
            raise ValueError(
                f"torch backend {backend!r} not supported here: no "
                "CUDA/NCCL on TPU hosts — use 'gloo' (reference: "
                "train/torch/config.py nccl/gloo selection)")
        self.backend = backend
        self.init_timeout_s = init_timeout_s

    def backend_cls(self):
        return _TorchBackend


class _TorchBackend(Backend):
    """Forms the torch.distributed world on every worker (reference:
    train/torch/config.py:73-119 _setup_torch_process_group:
    init_process_group(backend, init_method='tcp://master:port',
    rank, world_size))."""

    def __init__(self, config: TorchConfig):
        self.config = config
        self._initialized = False

    def on_start(self, worker_ctx: Dict[str, Any]) -> None:
        if worker_ctx["world_size"] <= 1:
            return
        import datetime

        import torch.distributed as dist
        dist.init_process_group(
            backend=self.config.backend,
            init_method=(f"tcp://{worker_ctx['master_addr']}:"
                         f"{worker_ctx['master_port']}"),
            rank=worker_ctx["world_rank"],
            world_size=worker_ctx["world_size"],
            timeout=datetime.timedelta(
                seconds=self.config.init_timeout_s))
        self._initialized = True

    def on_shutdown(self) -> None:
        if self._initialized:
            import torch.distributed as dist
            try:
                dist.destroy_process_group()
            except Exception:
                pass
            self._initialized = False
