"""Elastic launch configuration.

Reference: dlrover/python/elastic_agent/torch/training.py:169,216
(``ElasticLaunchConfig`` = torchrun LaunchConfig + DLRover flags with
``auto_configure_params``). TPU-native: ``nproc_per_node`` defaults to one
worker process per host (the PJRT model — one process drives all local
chips); accelerator topology comes from the TPU environment, not flags.
"""

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def with_package_on_pythonpath(env: Dict[str, str]) -> Dict[str, str]:
    """``env`` with the directory holding this ``dlrover_tpu`` on
    PYTHONPATH, so every child the agent starts (workers, warm spares,
    the node-check probe) resolves the package the agent runs."""
    import dlrover_tpu

    pkg_root = os.path.dirname(os.path.dirname(dlrover_tpu.__file__))
    pythonpath = env.get("PYTHONPATH", "")
    if pkg_root not in pythonpath.split(os.pathsep):
        env["PYTHONPATH"] = (
            pkg_root + (os.pathsep + pythonpath if pythonpath else "")
        )
    return env


@dataclass
class ElasticLaunchConfig:
    min_nodes: int = 1
    max_nodes: int = 1
    nproc_per_node: int = 1
    node_rank: int = 0
    node_id: int = 0
    job_name: str = "local"
    master_addr: str = ""
    rdzv_timeout_s: float = 600.0
    monitor_interval_s: float = 0.2
    max_restarts: int = 3
    # run the node-health check rendezvous before training
    # (reference flag --network-check)
    network_check: bool = False
    # also benchmark collective bandwidth during the check (--comm-perf-test)
    comm_perf_test: bool = False
    # exclude stragglers found by the check (--exclude-straggler)
    exclude_straggler: bool = False
    # world size must stay a multiple of this many nodes (TPU slice shape)
    node_unit: int = 1
    # save a breakpoint checkpoint from shm when a worker fails
    # (reference --save-at-breakpoint)
    save_at_breakpoint: bool = True
    # auto-tuning of dataloader/grad-accum knobs
    auto_tunning: bool = False
    # training entrypoint
    entrypoint: str = ""
    args: List[str] = field(default_factory=list)
    # extra env for workers
    worker_env: Dict[str, str] = field(default_factory=dict)
    # checkpoint dir the agent persists breakpoint saves into
    ckpt_dir: str = ""
    # cross-host in-memory checkpoint redundancy: backup-group size
    # (reference flash_checkpoint/replica.py; 0/1 disables)
    ckpt_replica: int = 0
    # start the tpu_timer observability plane: workers patch the PJRT table
    # and serve per-rank metrics; the agent runs the per-host aggregation
    # daemon on :18889 (reference xpu_timer_launch LD_PRELOAD + daemon)
    tpu_timer: bool = False
    # start this node's unified-runtime actor-host daemon and register it
    # with the master, so a unified job submitted with
    # submit(master_addr=...) can place actors on every node without a
    # hand-built hosts map (unified/remote.py; reference: Ray supplies
    # this placement layer, unified/master/scheduler.py:161)
    actor_host: bool = False
    # keep pre-imported spare interpreters so worker (re)spawns skip the
    # numpy/jax import cost — the largest fixed term of restart-to-training
    # after the persistent compilation cache (agent/warm_spawn.py). Any
    # pool failure falls back to a cold spawn.
    warm_spawn: bool = True

    def base_worker_env(self) -> Dict[str, str]:
        """Job-static environment of every child that may touch the
        device: the agent's own (``JAX_COMPILATION_CACHE_DIR``,
        ``JAX_PLATFORMS``, ... pass through) plus ``worker_env``."""
        return with_package_on_pythonpath({**os.environ, **self.worker_env})

    def auto_configure_params(self) -> None:
        """Fill topology-dependent defaults from the environment
        (reference training.py:216)."""
        if self.nproc_per_node <= 0:
            self.nproc_per_node = 1
        if self.max_nodes < self.min_nodes:
            self.max_nodes = self.min_nodes
        env_rank = os.getenv("NODE_RANK") or os.getenv("TPU_WORKER_ID")
        if env_rank is not None and self.node_rank == 0:
            self.node_rank = int(env_rank)
        self.node_id = self.node_rank
