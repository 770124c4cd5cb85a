"""Trainer API: DataParallelTrainer + the TPU-primary JaxTrainer.

Reference: python/ray/train/v2/api/data_parallel_trainer.py and the TPU
entry point python/ray/train/v2/jax/jax_trainer.py:19 (JaxTrainer — SPMD,
num_workers = number of TPU hosts, SPREAD placement; drivers must not
import/initialize the TPU client themselves, jax_trainer.py:92-94).
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Any, Callable, Dict, List, Optional

from ._checkpoint import Checkpoint
from .backend import BackendConfig, JaxConfig
from .controller import TrainController


@dataclasses.dataclass
class ScalingConfig:
    """reference: ray.air.ScalingConfig (air/config.py)."""
    num_workers: int = 1
    resources_per_worker: Optional[Dict[str, float]] = None
    use_tpu: bool = False
    topology: Optional[str] = None
    placement_strategy: str = "SPREAD"
    # Elastic training (reference: v2 scaling policy): when set, a failed
    # group restarts at the largest feasible world size in
    # [min_workers, num_workers] and upsizes again when capacity returns.
    min_workers: Optional[int] = None

    def __post_init__(self):
        if self.min_workers is not None and not (
                1 <= self.min_workers <= self.num_workers):
            raise ValueError(
                f"min_workers={self.min_workers} must be in "
                f"[1, num_workers={self.num_workers}]")

    def _resources(self) -> Dict[str, float]:
        if self.resources_per_worker:
            return dict(self.resources_per_worker)
        if self.use_tpu:
            # One worker per TPU host, holding all of that host's chips.
            # The count comes from what the cluster's nodes advertise —
            # never from a guess, and never by touching JAX in the driver.
            from ..tpu.accelerator import require_cluster_tpus
            return {"TPU": require_cluster_tpus(1, "use_tpu=True")}
        return {"CPU": 1.0}


@dataclasses.dataclass
class FailureConfig:
    max_failures: int = 0


@dataclasses.dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None
    callbacks: Optional[List["UserCallback"]] = None


@dataclasses.dataclass
class Result:
    """reference: ray.train.Result."""
    metrics: Dict[str, Any]
    metrics_history: List[Dict[str, Any]]
    checkpoint: Optional[Checkpoint]
    best_checkpoint: Optional[Checkpoint]
    error: Optional[str]


class DataParallelTrainer:
    """reference: v2 DataParallelTrainer — controller + worker group."""

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 backend_config: Optional[BackendConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.backend_config = backend_config or BackendConfig()
        self.datasets = datasets or {}

    def fit(self) -> Result:
        from .._private.usage import record_library_usage
        record_library_usage("train")
        run_name = self.run_config.name or "train_run"
        storage = self.run_config.storage_path or os.path.join(
            tempfile.gettempdir(), "ray_tpu_results")
        storage_path = os.path.join(storage, run_name)
        fail = self.run_config.failure_config or FailureConfig()
        ckpt = self.run_config.checkpoint_config or CheckpointConfig()
        config = dict(self.train_loop_config)
        if self.datasets:
            # Per-worker dataset shards (reference: Train dataset_shard);
            # round 1: streaming_split by world size at run time.
            config["_datasets"] = self.datasets
        controller = TrainController(
            train_fn=self.train_loop_per_worker,
            config=config,
            num_workers=self.scaling_config.num_workers,
            resources_per_worker=self.scaling_config._resources(),
            backend_config=self.backend_config,
            storage_path=storage_path,
            max_failures=fail.max_failures,
            placement_strategy=self.scaling_config.placement_strategy,
            checkpoint_num_to_keep=ckpt.num_to_keep,
            checkpoint_score_attribute=ckpt.checkpoint_score_attribute,
            checkpoint_score_order=ckpt.checkpoint_score_order,
            min_workers=self.scaling_config.min_workers,
            callbacks=self.run_config.callbacks)
        return controller.run()


class JaxTrainer(DataParallelTrainer):
    """SPMD training on TPU slices (reference: train/v2/jax/
    jax_trainer.py:19).  num_workers = number of TPU hosts; each worker
    holds the host's chips and joins one jax.distributed world; pjit/
    shard_map inside train_loop_per_worker spans the whole slice."""

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 jax_config: Optional[JaxConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None):
        scaling_config = scaling_config or ScalingConfig(use_tpu=True)
        super().__init__(
            train_loop_per_worker,
            train_loop_config=train_loop_config,
            scaling_config=scaling_config,
            run_config=run_config,
            backend_config=jax_config or JaxConfig(
                use_tpu=scaling_config.use_tpu),
            datasets=datasets)


class TorchTrainer(DataParallelTrainer):
    """Data-parallel torch training over gloo process groups (reference:
    python/ray/train/torch/torch_trainer.py TorchTrainer; the v2
    controller architecture is shared with JaxTrainer).  Workers call
    torch.distributed collectives / DistributedDataParallel as usual;
    there is no CUDA on TPU hosts, so this is the CPU/gloo path — models
    that need the accelerator should use JaxTrainer."""

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 torch_config: Optional["TorchConfig"] = None,
                 datasets: Optional[Dict[str, Any]] = None):
        from .backend import TorchConfig
        scaling_config = scaling_config or ScalingConfig(use_tpu=False)
        super().__init__(
            train_loop_per_worker,
            train_loop_config=train_loop_config,
            scaling_config=scaling_config,
            run_config=run_config,
            backend_config=torch_config or TorchConfig(),
            datasets=datasets)
