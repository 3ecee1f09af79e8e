"""Agent-mode node check: rendezvous pairs, run the workload, get a verdict.

Reference: dlrover/python/elastic_agent/torch/training.py
``NodeCheckElasticAgent``:1503 (``run``:1554, ``_run_node_check``:1647) and
the entrypoints ``node_health_check``:1757 / ``comm_perf_check``:1776. Two
check rounds: round 1 pairs (i, i+1); nodes in failed pairs are re-paired
with healthy partners in round 2 so the master can tell a bad node from a
bad partner (rdzv_manager pair-grouping :598).
"""

import time
from typing import Tuple

from dlrover_tpu.agent.config import ElasticLaunchConfig
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training import MasterRendezvousHandler
from dlrover_tpu.common.constants import (
    NetworkFailureReason,
    RendezvousName,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.diagnosis.node_check import (
    DEVICE_CHECK_TIMEOUT_S,
    run_check_workload,
)

# a node whose device-check child hangs reports the fault only when that
# child's time is up: its partners must still be waiting for the verdict
_VERDICT_WAIT_S = DEVICE_CHECK_TIMEOUT_S + 30.0


def _one_check_round(
    config: ElasticLaunchConfig, client: MasterClient, round_idx: int,
    matmul_size: int, payload_mb: float,
) -> None:
    handler = MasterRendezvousHandler(
        RendezvousName.NODE_CHECK,
        client,
        config.node_rank,
        config.nproc_per_node,
        timeout_s=config.rdzv_timeout_s,
    )
    _, group, _ = handler.next_rendezvous()
    partners = [r for r in group if r != config.node_rank]
    poll_state = {"ts": float("-inf"), "failed": False}

    def partner_failed() -> bool:
        # a partner whose failure THIS ROUND is already on the books is
        # not coming — stop waiting for it (same failed-round outcome as
        # the timeout, seconds earlier). The benchmark's wait loops call
        # this every 0.2-1s; cap the master RPC at ~1/s so a large job's
        # check phase doesn't multiply master load
        now = time.monotonic()
        if now - poll_state["ts"] < 1.0:
            return poll_state["failed"]
        poll_state["ts"] = now
        try:
            failed = set(client.get_check_failures())
        except (ConnectionError, RuntimeError):
            return False  # version skew / blip: fall back to the timeout
        poll_state["failed"] = any(r in failed for r in partners)
        return poll_state["failed"]

    try:
        elapsed = run_check_workload(
            config.node_rank, group,
            matmul_size=matmul_size, payload_mb=payload_mb,
            partner_failed=partner_failed,
            env=config.base_worker_env(),
        )
        client.report_network_check(normal=True, elapsed=elapsed)
    except Exception as e:  # noqa: BLE001 — a failed check is a data point
        logger.warning(
            "node %s check round %s failed: %r", config.node_rank,
            round_idx, e,
        )
        client.report_network_check(normal=False, elapsed=0.0)


def _wait_verdict(
    client: MasterClient, timeout_s: float = _VERDICT_WAIT_S
) -> Tuple[list, str]:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        faults, reason = client.check_fault_node()
        if reason != NetworkFailureReason.WAITING_NODE:
            return faults, reason
        time.sleep(0.5)
    return [], NetworkFailureReason.WAITING_NODE


def run_node_check(
    config: ElasticLaunchConfig,
    client: MasterClient,
    matmul_size: int = 1024,
    payload_mb: float = 4.0,
) -> bool:
    """Run up to two check rounds; returns False if THIS node is deemed
    faulty (or an excluded straggler)."""
    try:
        # fresh session: this node's previous-session results must not
        # ride into the new verdict (a re-sickened host re-proves health)
        client.clear_node_check()
    except RuntimeError:
        pass  # older master without the RPC — verdicts still work
    _one_check_round(config, client, 1, matmul_size, payload_mb)
    faults, reason = _wait_verdict(client)
    if faults:
        logger.info("check round 1 fault nodes: %s — running round 2", faults)
        _one_check_round(config, client, 2, matmul_size, payload_mb)
        faults, reason = _wait_verdict(client)
    if config.node_rank in faults:
        return False
    if config.exclude_straggler:
        stragglers = client.check_straggler()
        if config.node_rank in stragglers:
            logger.warning("node %s excluded as straggler", config.node_rank)
            return False
    return True
