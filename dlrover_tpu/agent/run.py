"""``dtpu-run`` — the elastic launcher CLI.

Reference: dlrover/trainer/torch/elastic_run.py:516–568 (``dlrover-run``):
a superset of ``torchrun``. TPU translation: a superset of a plain
``jax.distributed`` bootstrap — rendezvous via the job master, node health
checks, elastic restarts, flash checkpoint.

Usage:
    python -m dlrover_tpu.agent.run --standalone --nproc_per_node=2 train.py
    python -m dlrover_tpu.agent.run --master-addr=$MASTER --nnodes=2:4 \
        --network-check train.py -- --model-arg=1
"""

import argparse
import os
import sys
import time
from typing import List, Optional

from dlrover_tpu.agent.config import ElasticLaunchConfig
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training import ElasticTrainingAgent
from dlrover_tpu.common.constants import NodeStatus, RendezvousName
from dlrover_tpu.common.log import logger


def parse_nnodes(value: str):
    if ":" in value:
        lo, hi = value.split(":", 1)
        return int(lo), int(hi)
    n = int(value)
    return n, n


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "dtpu-run", description="TPU-native elastic training launcher"
    )
    p.add_argument("--standalone", action="store_true",
                   help="run a local in-process master (single node)")
    p.add_argument("--nnodes", default="1",
                   help="number of nodes, or MIN:MAX for elastic jobs")
    p.add_argument("--nproc_per_node", "--nproc-per-node", dest="nproc_per_node",
                   type=int, default=1)
    p.add_argument("--node_rank", "--node-rank", dest="node_rank",
                   type=int, default=0)
    p.add_argument("--master_addr", "--master-addr", dest="master_addr",
                   default=os.getenv("DLROVER_TPU_MASTER_ADDR", ""))
    p.add_argument("--job_name", "--job-name", dest="job_name",
                   default=os.getenv("DLROVER_TPU_JOB_NAME", "local"))
    p.add_argument("--max_restarts", "--max-restarts", dest="max_restarts",
                   type=int, default=3)
    p.add_argument("--monitor_interval", dest="monitor_interval",
                   type=float, default=0.2)
    p.add_argument("--network-check", dest="network_check",
                   action="store_true",
                   help="run node health checks before training")
    p.add_argument("--comm-perf-test", dest="comm_perf_test",
                   action="store_true")
    p.add_argument("--exclude-straggler", dest="exclude_straggler",
                   action="store_true")
    p.add_argument("--node_unit", "--node-unit", dest="node_unit",
                   type=int, default=1)
    p.add_argument("--ckpt_dir", "--ckpt-dir", dest="ckpt_dir", default="")
    p.add_argument("--ckpt_replica", "--ckpt-replica", dest="ckpt_replica",
                   type=int, default=0,
                   help="cross-host checkpoint backup-group size (0=off)")
    p.add_argument("--auto-tunning", "--auto-tuning", dest="auto_tunning",
                   action="store_true",
                   help="poll master-tuned dataloader/grad-accum config")
    p.add_argument("--no-save-at-breakpoint", dest="save_at_breakpoint",
                   action="store_false")
    p.add_argument("--actor-host", dest="actor_host", action="store_true",
                   help="start this node's unified-runtime actor-host "
                   "daemon and register it with the master (multi-host "
                   "unified jobs; needs $DTPU_ACTOR_HOST_SECRET for a "
                   "non-loopback bind)")
    p.add_argument("--tpu-timer", dest="tpu_timer", action="store_true",
                   help="enable the native profiler plane: workers patch "
                        "the PJRT table, agent aggregates on :18889")
    p.add_argument("--no-warm-spawn", dest="warm_spawn",
                   action="store_false",
                   help="disable the pre-imported spare-interpreter pool "
                        "(workers then pay the full numpy/jax import on "
                        "every spawn/restart)")
    p.add_argument("entrypoint", help="training script")
    p.add_argument("args", nargs=argparse.REMAINDER)
    return p


def config_from_args(args) -> ElasticLaunchConfig:
    min_nodes, max_nodes = parse_nnodes(args.nnodes)
    config = ElasticLaunchConfig(
        min_nodes=min_nodes,
        max_nodes=max_nodes,
        nproc_per_node=args.nproc_per_node,
        node_rank=args.node_rank,
        job_name=args.job_name,
        master_addr=args.master_addr,
        max_restarts=args.max_restarts,
        monitor_interval_s=args.monitor_interval,
        network_check=args.network_check,
        comm_perf_test=args.comm_perf_test,
        exclude_straggler=args.exclude_straggler,
        node_unit=args.node_unit,
        save_at_breakpoint=args.save_at_breakpoint,
        ckpt_dir=args.ckpt_dir,
        ckpt_replica=args.ckpt_replica,
        auto_tunning=args.auto_tunning,
        tpu_timer=args.tpu_timer,
        actor_host=args.actor_host,
        warm_spawn=args.warm_spawn,
        entrypoint=args.entrypoint,
        args=args.args[1:] if args.args[:1] == ["--"] else list(args.args),
    )
    config.auto_configure_params()
    return config


def _launch_local_master(config: ElasticLaunchConfig):
    """In-process master for standalone mode (reference
    elastic_run.py:296 ``_launch_dlrover_local_master`` — the reference uses
    a subprocess; in-process keeps standalone single-PID)."""
    from dlrover_tpu.master.master import LocalJobMaster

    master = LocalJobMaster(
        job_name=config.job_name,
        node_num=config.min_nodes,
        min_nodes=config.min_nodes,
        max_nodes=config.max_nodes,
        node_unit=config.node_unit,
    )
    master.prepare()
    config.master_addr = master.addr
    return master


def wait_pre_check(client: MasterClient, timeout_s: float = 600.0) -> None:
    """Poll the master pre-check gate (reference elastic_run.py:265)."""
    start = time.time()
    while time.time() - start < timeout_s:
        status, reason = client.get_pre_check_result()
        if status == "pass":
            return
        if status == "fail":
            raise RuntimeError(f"pre-check failed: {reason}")
        time.sleep(1.0)
    raise TimeoutError("pre-check did not finish in time")


def _run_network_check(config: ElasticLaunchConfig,
                       client: MasterClient) -> bool:
    from dlrover_tpu.diagnosis.node_check_agent import run_node_check

    return run_node_check(config, client)


def _apply_master_run_config(client: MasterClient,
                             config: ElasticLaunchConfig) -> None:
    """Merge master-pushed launcher overrides (reference merges the
    master's ElasticRunConfig into the torchrun args, elastic_run.py:
    404–443) — the platform's central switch for e.g. forcing
    --network-check on every agent of a job. Unknown keys are ignored."""
    try:
        resp = client.get_run_config()
    except (ConnectionError, OSError, RuntimeError):
        # RuntimeError covers RPCError from an older master without this
        # method — version skew must not stop the agent
        return
    if not resp:
        return
    for key, value in resp.items():
        if hasattr(config, key):
            setattr(config, key, value)
            logger.info("master-pushed run config: %s=%r", key, value)
        else:
            logger.warning("master-pushed run config key %r unknown — "
                           "ignored (version skew?)", key)


def _launch_actor_host(config: ElasticLaunchConfig):
    """Per-node unified-runtime daemon, registered with the master
    (reference: Ray's node-level raylet gives the unified scheduler its
    placement layer for free; here the agent owns that daemon). Binds
    all interfaces only when a spawn-auth secret is present — otherwise
    loopback (the single-host dev shape)."""
    import subprocess

    secure = bool(os.environ.get("DTPU_ACTOR_HOST_SECRET"))
    host = "0.0.0.0" if secure else "127.0.0.1"
    cmd = [
        sys.executable, "-m", "dlrover_tpu.unified.remote",
        "--port", "0", "--host", host,
    ]
    if secure:
        cmd += [
            "--master-addr", config.master_addr,
            "--job-name", config.job_name,
            "--node-rank", str(config.node_rank),
        ]
    else:
        # loopback daemon: do NOT register it with the master — a
        # 127.0.0.1 address in the placement map would point remote
        # submitters at their own host (or a colliding local port); a
        # missing registration fails resolution loudly instead
        logger.warning(
            "--actor-host without $DTPU_ACTOR_HOST_SECRET: daemon binds "
            "loopback and is NOT registered with the master — remote "
            "nodes cannot place actors here"
        )
    proc = subprocess.Popen(cmd)
    return proc


def run(config: ElasticLaunchConfig) -> int:
    master = None
    actor_host_proc = None
    if config.master_addr == "":
        master = _launch_local_master(config)
        logger.info("standalone master at %s", config.master_addr)
    client = MasterClient(
        config.master_addr, config.node_id, config.node_rank
    )
    warm_pool = None
    try:
        if config.actor_host:
            actor_host_proc = _launch_actor_host(config)
        _apply_master_run_config(client, config)
        if config.warm_spawn and config.entrypoint:
            # start the spare interpreters NOW so their numpy/jax imports
            # overlap the pre-check and network-check phases — by the time
            # the training agent gates on readiness, the pool is warm and
            # every node leaves the gate together (a node whose gate runs
            # long would otherwise miss its peers' rendezvous cut window)
            from dlrover_tpu.agent.warm_spawn import WarmWorkerPool

            # spares must see config.worker_env at IMPORT time: env vars
            # jax reads on import (JAX_PLATFORMS, JAX_ENABLE_X64, ...)
            # are too late to merge at release — a bare-os.environ spare
            # would initialize a different backend than a cold spawn
            warm_pool = WarmWorkerPool(
                size=config.nproc_per_node,
                base_env=config.base_worker_env(),
            )
            warm_pool.prewarm()
        wait_pre_check(client)
        if config.network_check:
            ok = _run_network_check(config, client)
            if not ok:
                logger.error("node %s failed the network check — exiting "
                             "so the scheduler can replace it",
                             config.node_rank)
                client.update_node_status(
                    NodeStatus.FAILED, exit_reason="hardware_error"
                )
                return 1
        from dlrover_tpu.ckpt.ckpt_saver import AsyncCheckpointSaver

        saver = None
        if config.ckpt_dir or config.save_at_breakpoint:
            saver = AsyncCheckpointSaver(
                ckpt_dir=config.ckpt_dir,
                node_rank=config.node_rank,
                local_world_size=config.nproc_per_node,
                expected_frames=config.min_nodes * config.nproc_per_node,
                is_commit_leader=(config.node_rank == 0),
            )
        agent = ElasticTrainingAgent(
            config, client, ckpt_saver=saver, warm_pool=warm_pool
        )
        return agent.run()
    finally:
        if warm_pool is not None:
            warm_pool.stop()
        if actor_host_proc is not None:
            actor_host_proc.terminate()
            try:
                actor_host_proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — escalate, never hang exit
                logger.warning("actor host ignored terminate — killing")
                actor_host_proc.kill()
        if master is not None:
            master.stop()


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    config = config_from_args(args)
    if not args.standalone and not config.master_addr:
        print("error: --master-addr required unless --standalone",
              file=sys.stderr)
        return 2
    if args.standalone and config.master_addr:
        logger.info("--standalone ignored: master addr %s given",
                    config.master_addr)
    return run(config)


if __name__ == "__main__":
    raise SystemExit(main())
