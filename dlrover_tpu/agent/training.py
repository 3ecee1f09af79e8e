"""The elastic agent: rendezvous, worker process management, fault recovery.

Reference: dlrover/python/elastic_agent/torch/training.py —
``ElasticTrainingAgent``:484 (``_rendezvous``:604, ``_assign_worker_ranks``:791,
``_initialize_workers``:856, ``_invoke_run``:969,
``_process_diagnosis_action``:1111, ``_restart_workers``:1225) and
``MasterRendezvousHandler``:272 (``next_rendezvous``:349).

TPU-native redesign: instead of wrapping torchrun's agent, this is a small
self-contained loop. Rendezvous hands out a **jax.distributed coordinator
address** (rank-0 host + free port) rather than a torch Store; workers
bootstrap PJRT with it. Elasticity = kill worker procs, re-join rendezvous,
respawn with the new world (XLA's world is static per-process, so every
membership change is a process restart — made cheap by the persistent JAX
compilation cache, SURVEY.md §7 hard-part (b)).
"""

import signal
import subprocess
import sys
import threading
import time
import traceback
from dataclasses import dataclass
from enum import Enum
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.agent.config import ElasticLaunchConfig
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.comm import NodeMeta
from dlrover_tpu.common.constants import (
    ConfigKey,
    DiagnosisActionType,
    EnvKey,
    MetricLabel,
    NodeStatus,
    RendezvousName,
    SharedResourceName,
    SpanName,
    TrainingExceptionLevel,
    env_flag,
    env_float,
    env_str,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.event import AgentEvent, get_emitter
from dlrover_tpu.common.multi_process import LocalIPCServer, ipc_socket_path
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.journal import JournalEvent
from dlrover_tpu.common.rpc import find_free_port
from dlrover_tpu.diagnosis.diagnosis_agent import DiagnosisAgent


class RendezvousOutSyncError(Exception):
    """Raised when the cut world went stale mid-poll (reference training.py:432)."""


class MasterRendezvousHandler:
    """Joins a named master rendezvous and polls for the cut world
    (reference training.py:272)."""

    def __init__(
        self,
        name: str,
        client: MasterClient,
        node_rank: int,
        local_world_size: int,
        timeout_s: float = 600.0,
        node_unit: int = 1,
    ):
        self._name = name
        self._client = client
        self._node_rank = node_rank
        self._local_world_size = local_world_size
        self._timeout_s = timeout_s
        self._node_unit = node_unit

    def next_rendezvous(
        self,
    ) -> Tuple[int, Dict[int, NodeMeta], str]:
        """Join, then poll until this node is in a cut world.

        Returns (round, world {node_rank: NodeMeta}, coordinator_addr).
        """
        free_port = find_free_port("127.0.0.1")
        self._client.join_rendezvous(
            self._name,
            self._node_rank,
            self._local_world_size,
            host=env_str(ConfigKey.HOST_IP, "127.0.0.1"),
            free_port=free_port,
            node_unit=self._node_unit,
        )
        start = time.monotonic()
        while True:
            rdzv_round, _, world, coordinator = self._client.get_comm_world(
                self._name, self._node_rank
            )
            if world and self._node_rank in world:
                return rdzv_round, world, coordinator
            if time.monotonic() - start > self._timeout_s:
                raise TimeoutError(
                    f"rendezvous {self._name} timed out after "
                    f"{self._timeout_s}s (node_rank={self._node_rank})"
                )
            time.sleep(0.1)  # noqa: DLR010 — deadline-bounded cross-process rendezvous poll (raises TimeoutError above); no Event spans the kv store


def assign_worker_ranks(
    world: Dict[int, NodeMeta], node_rank: int
) -> Tuple[int, int]:
    """Compute (base_global_rank, world_size) from the cut world
    (reference ``_assign_worker_ranks``:791). Rank order follows the
    master's topology-stamped ``comm_rank`` when present (slice-contiguous,
    torus order — master/net_topology.py), node-rank order otherwise."""
    world_size = sum(m.local_world_size for m in world.values())
    if all(m.comm_rank >= 0 for m in world.values()):
        order = sorted(world, key=lambda r: world[r].comm_rank)
    else:
        order = sorted(world)
    base_rank = 0
    for r in order:
        if r == node_rank:
            break
        base_rank += world[r].local_world_size
    return base_rank, world_size


class WorkerState(Enum):
    INIT = "init"
    RUNNING = "running"
    SUCCEEDED = "succeeded"
    FAILED = "failed"


@dataclass
class _Worker:
    local_rank: int
    global_rank: int
    proc: subprocess.Popen


class RunResult:
    def __init__(self, state: WorkerState, failures: Optional[Dict] = None):
        self.state = state
        self.failures = failures or {}


class ElasticTrainingAgent:
    """Per-host agent driving rendezvous → spawn → monitor → recover
    (reference training.py:484)."""

    def __init__(
        self,
        config: ElasticLaunchConfig,
        client: Optional[MasterClient] = None,
        ckpt_saver=None,
        warm_pool=None,
    ):
        import uuid

        self._config = config
        self._client = client or MasterClient(
            config.master_addr, config.node_id, config.node_rank
        )
        self._workers: List[_Worker] = []
        self._restart_count = 0
        self._remaining_restarts = config.max_restarts
        self._stop_flag = threading.Event()
        self._action_lock = threading.Lock()
        self._pending_action: Optional[Tuple[str, Dict]] = None
        # shm incarnation nonce: workers of THIS agent process name their
        # checkpoint segments with it, so a restarted agent never reattaches
        # to a dead predecessor's half-written segments (and can unlink
        # them — cleanup_orphan_segments at run() start)
        self._shm_incarnation = uuid.uuid4().hex[:8]
        # partition-degraded mode: on master unreachability keep training
        # on cached shard assignments for a bounded grace window, then
        # save + exit cleanly if the master never comes back
        self._partition_grace_s = env_float(EnvKey.PARTITION_GRACE_S, 120.0)
        self._partition_threshold = 3  # consecutive failed heartbeats
        self._hb_consec_failures = 0
        self._degraded_since: Optional[float] = None  # monotonic
        self._rdzv_handler = MasterRendezvousHandler(
            RendezvousName.TRAINING,
            self._client,
            config.node_rank,
            config.nproc_per_node,
            timeout_s=config.rdzv_timeout_s,
            node_unit=config.node_unit,
        )
        self._current_round = -1
        self._world: Dict[int, NodeMeta] = {}
        # agent-hosted IPC for flash checkpoint (SharedQueue/Lock/Dict + shm)
        self._ipc_server = LocalIPCServer(
            ipc_socket_path(config.job_name, config.node_rank)
        )
        self._ckpt_saver = ckpt_saver
        self._hb_thread: Optional[threading.Thread] = None
        # a caller-provided pool (dtpu-run creates it BEFORE the network
        # check so spares finish importing during the check phase) wins;
        # otherwise build one here
        self._warm_pool = warm_pool
        if (self._warm_pool is None and config.warm_spawn
                and config.entrypoint):
            from dlrover_tpu.agent.warm_spawn import WarmWorkerPool

            self._warm_pool = WarmWorkerPool(
                size=config.nproc_per_node,
                base_env=config.base_worker_env(),
            )
        self._last_global_step = 0
        self._last_step_ts = 0.0
        # node-side diagnosis: telemetry gauges for heartbeats + the
        # restart-vs-relaunch verdict on worker failure
        self._diagnosis = DiagnosisAgent(
            ipc_server=self._ipc_server,
            local_world_size=config.nproc_per_node,
        )
        # worker-published op-class histograms re-keyed by global rank for
        # the heartbeat uplink (master/skew_monitor.py consumes them)
        from dlrover_tpu.agent.monitor import (
            MemorySnapshotCollector,
            OpTelemetryCollector,
        )

        self._op_telemetry = OpTelemetryCollector(self._ipc_server)
        # worker-published device-memory ledger snapshots re-keyed by
        # global rank (master's FleetMemoryMonitor consumes them)
        self._mem_snapshots = MemorySnapshotCollector(self._ipc_server)
        self._events = get_emitter(f"agent_{config.node_rank}")
        self._training_monitor = None
        self._replica_service = None
        self._reshard_service = None
        # observability spine: local metrics (scraped via the optional
        # per-agent /metrics server) + journal events reported to master
        from dlrover_tpu.observability.registry import get_registry

        reg = get_registry()
        self._step_time_hist = reg.histogram(
            "dlrover_agent_step_seconds",
            "Wall time between consecutive observed global steps",
        )
        self._restarts_counter = reg.counter(
            "dlrover_agent_restarts_total", "Soft worker restarts, by reason",
            labelnames=("reason",),
        )
        self._worker_failures_counter = reg.counter(
            "dlrover_agent_worker_failures_total",
            "Worker process failures observed by the agent",
        )
        reg.gauge(
            "dlrover_agent_global_step", "Last global step this agent saw"
        ).set_function(lambda: self._last_global_step)
        # crash flight recorder: bundles on unhandled agent exceptions,
        # partition-degraded exits, injected chaos, or GET /debug/bundle
        from dlrover_tpu.observability.flight_recorder import FlightRecorder

        self._flight_recorder = FlightRecorder(
            source=f"agent_{config.node_rank}", registry=reg
        )
        self._metrics_server = self._maybe_start_metrics_server()

    def _maybe_start_metrics_server(self):
        """Per-agent scrape surface, gated on
        DLROVER_TPU_AGENT_METRICS_PORT (0 = pick a free port). The base
        port is offset by node_rank so multi-agent hosts don't collide."""
        port_env = env_str(ConfigKey.AGENT_METRICS_PORT)
        if not port_env:
            return None
        from dlrover_tpu.common.http_server import HTTPTransportServer
        from dlrover_tpu.observability.registry import get_registry

        try:
            base = int(port_env)
            port = base + self._config.node_rank if base else 0
            server = HTTPTransportServer(port=port)
        except (ValueError, OSError) as e:
            logger.warning("agent metrics server disabled: %r", e)
            return None
        server.add_get_route(
            "/metrics",
            lambda: (
                "text/plain; version=0.0.4; charset=utf-8",
                get_registry().render(),
            ),
        )
        server.add_get_route(
            "/debug/bundle", self._flight_recorder.http_handler()
        )
        server.start()
        logger.info("agent metrics on :%s/metrics", server.port)
        return server

    # -- rendezvous + spawn ------------------------------------------------

    def _rendezvous(self) -> Tuple[str, int, int]:
        """(reference ``_rendezvous``:604)"""
        # the causal root of a rendezvous round on this node: the join/
        # world-wait RPC spans (master_client.py) and the master-side
        # join/world-cut spans all nest under this trace
        with tracing.span(
            SpanName.RDZV_CLIENT_ROUND,
            source=f"agent_{self._config.node_rank}",
            node_rank=self._config.node_rank,
            restart_count=self._restart_count,
        ), self._events.span(AgentEvent.RENDEZVOUS):
            rdzv_round, world, coordinator = (
                self._rdzv_handler.next_rendezvous()
            )
        self._current_round = rdzv_round
        self._world = world
        base_rank, world_size = assign_worker_ranks(
            world, self._config.node_rank
        )
        logger.info(
            "node %s rendezvous round %s: %s nodes, world_size=%s, "
            "base_rank=%s, coordinator=%s",
            self._config.node_rank, rdzv_round, len(world), world_size,
            base_rank, coordinator,
        )
        if self._ckpt_saver is not None:
            # commit quorum is a property of the *current* world
            self._ckpt_saver.update_world(
                node_rank=self._config.node_rank,
                expected_frames=world_size,
                is_commit_leader=(self._config.node_rank == min(world)),
            )
        return coordinator, base_rank, world_size

    def _worker_env(
        self, local_rank: int, global_rank: int, world_size: int,
        coordinator: str,
    ) -> Dict[str, str]:
        env = self._config.base_worker_env()
        env.update({
            EnvKey.JOB_NAME: self._config.job_name,
            EnvKey.MASTER_ADDR: self._client.master_addr,
            EnvKey.NODE_ID: str(self._config.node_id),
            EnvKey.NODE_RANK: str(self._config.node_rank),
            EnvKey.NODE_NUM: str(len(self._world)),
            EnvKey.LOCAL_RANK: str(local_rank),
            EnvKey.LOCAL_WORLD_SIZE: str(self._config.nproc_per_node),
            EnvKey.RANK: str(global_rank),
            EnvKey.WORLD_SIZE: str(world_size),
            EnvKey.COORDINATOR_ADDR: coordinator,
            EnvKey.PROCESS_ID: str(global_rank),
            EnvKey.NUM_PROCESSES: str(world_size),
            EnvKey.RESTART_COUNT: str(self._restart_count),
            EnvKey.RDZV_ROUND: str(self._current_round),
            EnvKey.REPLICA_GROUP: str(self._config.ckpt_replica),
            EnvKey.SHM_INCARNATION: self._shm_incarnation,
            "DLROVER_TPU_IPC_SOCKET": self._ipc_server.path,
        })
        if self._config.tpu_timer:
            env["TPU_TIMER_ENABLE"] = "1"
        return env

    def _initialize_workers(self) -> None:
        """(reference ``_initialize_workers``:856)"""
        coordinator, base_rank, world_size = self._rendezvous()
        self._workers = []
        for local_rank in range(self._config.nproc_per_node):
            global_rank = base_rank + local_rank
            env = self._worker_env(
                local_rank, global_rank, world_size, coordinator
            )
            proc = None
            if self._warm_pool is not None:
                proc = self._warm_pool.take(
                    env, self._config.entrypoint, self._config.args
                )
            if proc is None:  # pool disabled/empty: cold spawn
                cmd = [
                    sys.executable, self._config.entrypoint,
                    *self._config.args,
                ]
                proc = subprocess.Popen(cmd, env=env)  # noqa: S603
            self._workers.append(_Worker(local_rank, global_rank, proc))
        logger.info(
            "node %s spawned %s worker(s): pids=%s",
            self._config.node_rank,
            len(self._workers),
            [w.proc.pid for w in self._workers],
        )

    # -- monitoring --------------------------------------------------------

    def _monitor_workers(self) -> RunResult:
        states = []
        failures = {}
        for w in self._workers:
            code = w.proc.poll()
            if code is None:
                states.append(WorkerState.RUNNING)
            elif code == 0:
                states.append(WorkerState.SUCCEEDED)
            else:
                states.append(WorkerState.FAILED)
                failures[w.global_rank] = code
        if failures:
            return RunResult(WorkerState.FAILED, failures)
        if all(s == WorkerState.SUCCEEDED for s in states):
            return RunResult(WorkerState.SUCCEEDED)
        return RunResult(WorkerState.RUNNING)

    def _membership_changed(self) -> bool:
        """A new rendezvous round is forming (reference
        ``_membership_changed``:1232)."""
        try:
            return self._client.num_nodes_waiting(RendezvousName.TRAINING) > 0
        except ConnectionError:
            return False

    def _stop_workers(self, sig: int = signal.SIGTERM,
                      grace_s: Optional[float] = None) -> None:
        if grace_s is None:
            from dlrover_tpu.common.config import get_context

            grace_s = get_context().worker_stop_grace_s
        for w in self._workers:
            if w.proc.poll() is None:
                try:
                    w.proc.send_signal(sig)
                except ProcessLookupError:
                    pass
        deadline = time.monotonic() + grace_s
        for w in self._workers:
            remaining = max(0.1, deadline - time.monotonic())
            try:
                w.proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                w.proc.kill()
                w.proc.wait()

    def _restart_workers(self, reason: str,
                         grace_s: Optional[float] = None) -> None:
        """Soft restart: same host, new rendezvous round
        (reference ``_restart_workers``:1225)."""
        logger.info("restarting workers on node %s: %s",
                    self._config.node_rank, reason)
        self._events.instant(AgentEvent.RESTART, reason=reason)
        self._restarts_counter.labels(reason=reason).inc()
        # stop first: shm survives the workers, and persisting after they
        # die removes any chance of reading a frame mid-write
        self._stop_workers(grace_s=grace_s)
        self._save_breakpoint_checkpoint(reason)
        # the dead workers' unacked shard leases go back to TODO now —
        # relaunched workers (or any survivor) re-pull them immediately
        # instead of waiting out shard_lease_timeout_s; acked shards stay
        # retired in the master ledger, so nothing double-trains
        try:
            self._client.recover_shard_tasks()
        except (ConnectionError, OSError) as e:
            # best-effort fast path: lease expiry remains the backstop
            logger.warning("shard-lease recovery skipped: %r", e)
        self._restart_count += 1
        # drop the stale step observation: heartbeats must not re-populate
        # the master's PerfMonitor with pre-restart timestamps (that would
        # immediately re-arm the hang detector after a hang restart), and
        # restored workers may legitimately resume from an earlier step
        self._last_global_step = 0
        self._last_step_ts = 0.0
        if getattr(self, "_training_monitor", None) is not None:
            self._training_monitor.reset()
        self._initialize_workers()

    def _save_breakpoint_checkpoint(self, reason: str) -> None:
        """Persist whatever checkpoint state is in shm before losing workers
        (reference agent ``_save_ckpt_to_storage`` training.py:1186)."""
        if self._ckpt_saver is not None and self._config.save_at_breakpoint:
            try:
                self._ckpt_saver.save_shm_to_storage(
                    reason=reason, workers_dead=True,
                    # never block a restart on the commit quorum: a dead
                    # peer's frame is not coming (the SIGTERM path in
                    # ckpt_saver keeps its synchronous commit)
                    async_commit=True,
                )
            except Exception:  # noqa: BLE001
                logger.exception("breakpoint checkpoint save failed")

    # -- heartbeat / diagnosis actions -------------------------------------

    def _heartbeat_loop(self) -> None:
        from dlrover_tpu.agent.fanin import HeartbeatRouter
        from dlrover_tpu.common import retry
        from dlrover_tpu.common.config import get_context

        interval = get_context().heartbeat_interval_s
        # fan-in routing: beats go to this node's assigned aggregator
        # when the master hands one out, straight to the master otherwise
        # (and on any aggregator failure) — see agent/fanin.py
        router = HeartbeatRouter(self._client)
        self._hb_router = router
        wait_s = interval
        try:
            while not self._stop_flag.wait(wait_s):
                wait_s = interval
                try:
                    resp = router.heartbeat(
                        global_step=self._last_global_step,
                        step_timestamp=self._last_step_ts,
                        gauges=self._diagnosis.collect_gauges(),
                        rdzv_round=self._current_round,
                        op_telemetry=self._op_telemetry.collect(),
                        memory=self._mem_snapshots.collect(),
                    )
                except ConnectionError:
                    self._note_heartbeat_failure()
                    continue
                self._note_heartbeat_success()
                if resp.backoff_hint_s > 0:
                    # explicit master backpressure: stretch the next beat,
                    # jittered so the fleet doesn't re-synchronize into
                    # the very burst the master is shedding
                    wait_s = interval + retry.jittered(resp.backoff_hint_s)
                self._handle_heartbeat_action(resp)
        finally:
            router.close()

    def _handle_heartbeat_action(self, resp) -> None:
        if resp.action_type == DiagnosisActionType.NONE:
            return
        with self._action_lock:
            self._pending_action = (
                resp.action_type, dict(resp.action_data or {})
            )
        logger.info(
            "received diagnosis action %s (%s)",
            resp.action_type, resp.action_data,
        )

    def _note_heartbeat_failure(self) -> None:
        """Consecutive heartbeat failures are THE partition signal: after
        the threshold the agent enters partition-degraded mode — workers
        keep training on their cached shard assignments (the membership
        poll already treats connection errors as "no change"), and the
        monitor loop bounds the degradation with a grace window."""
        self._hb_consec_failures += 1
        if (self._degraded_since is None
                and self._hb_consec_failures >= self._partition_threshold):
            self._degraded_since = time.monotonic()
            logger.warning(
                "master unreachable for %d consecutive heartbeats — "
                "entering partition-degraded mode: training continues on "
                "cached shard assignments for up to %.0fs",
                self._hb_consec_failures, self._partition_grace_s,
            )

    def _note_heartbeat_success(self) -> None:
        if self._degraded_since is not None:
            outage_s = time.monotonic() - self._degraded_since
            self._degraded_since = None
            logger.info(
                "master reachable again after %.1fs — resynced out of "
                "partition-degraded mode", outage_s,
            )
            # journal the whole degradation episode now that the master
            # can hear us (events during the partition could not land)
            self._client.report_event(
                JournalEvent.PARTITION_RESYNC,
                {"outage_s": outage_s,
                 "failed_heartbeats": self._hb_consec_failures},
            )
        self._hb_consec_failures = 0

    def _partition_grace_expired(self) -> bool:
        since = self._degraded_since
        return (since is not None
                and time.monotonic() - since > self._partition_grace_s)

    def _take_pending_action(self):
        """Returns (action_type, action_data) or (None, {})."""
        with self._action_lock:
            pending, self._pending_action = self._pending_action, None
            return pending if pending is not None else (None, {})

    def _capture_stack_dump(self, action_data: dict) -> None:
        """Serve a master-requested STACK_DUMP (RuntimeStragglerDiagnostician
        flagged one of this node's ranks): xprof requests to every local
        worker plus the daemon's stack RPC, then acknowledge via the journal
        so the operator can correlate verdict → evidence."""
        import threading as _threading

        # master-originated action: restore its trace context on the
        # capture thread so the evidence span joins the master's arc
        carried = tracing.extract_wire(action_data.get(tracing.WIRE_KEY))

        def _capture():
            try:
                with tracing.activate(carried), tracing.span(
                    SpanName.AGENT_STACK_DUMP,
                    source=f"agent_{self._config.node_rank}",
                    rank=action_data.get("rank", -1),
                ):
                    self._diagnosis._request_worker_profiles()
                    path = self._diagnosis.capture_worker_stacks()
                self._client.report_event(
                    JournalEvent.STACK_DUMP_CAPTURED,
                    {"rank": action_data.get("rank", -1),
                     "cause": action_data.get("cause", ""),
                     "path": path},
                )
            except Exception:  # noqa: BLE001 — evidence capture is
                # best-effort; the training plane must stay untouched
                logger.warning("stack-dump capture failed", exc_info=True)

        _threading.Thread(
            target=_capture, name="stack-dump", daemon=True
        ).start()

    def observe_global_step(self, step: int, ts: float) -> None:
        if self._last_step_ts == 0.0:
            # first completed step of this incarnation: training is live
            # again — the master closes its recompile/restore phase here
            self._client.report_event(
                JournalEvent.STEP_RESUMED, {"step": step}
            )
        elif ts > self._last_step_ts:
            self._step_time_hist.observe(ts - self._last_step_ts)
        self._last_global_step = step
        self._last_step_ts = ts

    def _local_shm_handlers(self):
        """Live handlers for the shm frames this host's workers registered
        in the IPC meta dict (same attach idiom as the saver) — the
        ReshardService reads shard byte-ranges through these."""
        from dlrover_tpu.ckpt.shm_handler import SharedMemoryHandler

        handlers = []
        meta = self._ipc_server.local_dict(SharedResourceName.SHM_META_DICT)
        for info in dict(meta).values():
            handlers.append(SharedMemoryHandler(info["shm"]))
        return handlers

    # -- main loop ---------------------------------------------------------

    def run(self) -> int:
        """(reference ``_invoke_run``:969)"""
        from dlrover_tpu.chaos import get_injector
        from dlrover_tpu.ckpt.shm_handler import cleanup_orphan_segments

        # a predecessor agent that died uncleanly leaves its incarnation's
        # segments in /dev/shm; unlink them before any worker maps memory
        removed = cleanup_orphan_segments(
            self._config.job_name, self._config.node_rank,
            self._shm_incarnation,
        )
        if removed:
            self._client.report_event(
                JournalEvent.SHM_ORPHANS_CLEANED, {"segments": removed}
            )
        if self._ckpt_saver is not None:
            # every tracker move this host leads lands in the master's
            # journal as ckpt_committed {step, trigger, frames} — the
            # incident stitcher scores pre-emptive saves from these
            self._ckpt_saver.set_reporter(
                lambda kind, data: self._client.report_event(kind, data)
            )
        inj = get_injector()
        if inj is not None:
            # injected faults land in the master's journal via the
            # best-effort telemetry path (never adds faults of its own);
            # the flight recorder then snapshots a local bundle so the
            # drill leaves an artifact even when recovery succeeds
            inj.set_reporter(self._flight_recorder.wrap_fault_reporter(
                lambda event: self._client.report_event(
                    JournalEvent.FAULT_INJECTED, event
                )
            ))
        self._ipc_server.start()
        if self._warm_pool is not None:
            # spares import numpy/jax before this node joins rendezvous:
            # a node joining a RUNNING job stops the world for every peer,
            # so a bounded wait here (peers train meanwhile) is cheaper
            # globally than joining cold and making everyone wait through
            # this host's imports during the cutover
            self._warm_pool.prewarm()
            self._warm_pool.wait_ready(
                n=self._config.nproc_per_node,
                timeout_s=env_float(ConfigKey.WARM_WAIT_S, 10.0),
            )
        if self._config.ckpt_replica > 1:
            # agent-hosted store for peers' shm frames; survives worker
            # crashes and serves a relaunched peer its frame back
            from dlrover_tpu.ckpt.replica import ReplicaService

            self._replica_service = ReplicaService()
            self._replica_service.start()
            # publish this agent's reachable address in the master KV;
            # workers (push) and relaunched peers (fetch) resolve it there
            self._replica_service.register(
                self._client, self._config.job_name, self._config.node_rank
            )
        if env_flag(ConfigKey.RESHARD, default=True):
            # live-reshard plane (ckpt/reshard.py): serve this host's
            # sealed shm frames by shard byte-range so survivors of a
            # world cut can feed relaunched peers without a storage read;
            # runs in the agent so the frames outlive the workers
            from dlrover_tpu.ckpt.reshard import ReshardService

            self._reshard_service = ReshardService(
                shm_provider=self._local_shm_handlers,
            )
            self._reshard_service.start()
            try:
                self._reshard_service.register(
                    self._client, self._config.job_name,
                    self._config.node_rank,
                )
            except ConnectionError as e:
                logger.warning(
                    "reshard service address publish failed: %r — peers "
                    "will fall back to replica/shm/storage restore", e,
                )
        if self._ckpt_saver is not None:
            self._ckpt_saver.start(self._ipc_server)
            try:
                # persist shm before dying on SIGTERM (pod preemption)
                self._ckpt_saver.install_signal_handlers()
            except ValueError:
                pass  # not the main thread (in-process test harness)
        self._client.update_node_status(NodeStatus.RUNNING)
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, name="agent-heartbeat", daemon=True
        )
        self._hb_thread.start()
        # periodic host-usage reports + worker-published step forwarding
        # (reference monitor/resource.py:86, monitor/training.py:40)
        from dlrover_tpu.agent.monitor import (
            ResourceMonitor,
            TrainingMonitor,
            device_stats_from_ipc,
        )
        from dlrover_tpu.common.config import get_context

        resource_monitor = ResourceMonitor(
            self._client, interval_s=get_context().resource_report_interval_s,
            # HBM telemetry the workers publish over the IPC dict — the
            # master's micro-batch tuner and stall diagnosis feed on it
            extra_device_stats=lambda: device_stats_from_ipc(
                self._ipc_server),
        )
        self._training_monitor = TrainingMonitor(
            self._ipc_server, self._client,
            on_step=self.observe_global_step,
            round_provider=lambda: self._current_round,
        )
        resource_monitor.start()
        self._training_monitor.start()
        timer_daemon = None
        if self._config.tpu_timer:
            # per-host metrics aggregator; the diagnosis TpuTimerCollector
            # scrapes it on :18889 (reference starts xpu_timer_daemon from
            # the launch wrapper)
            from dlrover_tpu.observability.timeline import start_daemon

            timer_daemon = start_daemon(
                n_workers=self._config.nproc_per_node
            )
        config_tuner = None
        if self._config.auto_tunning:
            from dlrover_tpu.agent.config_tuner import (
                ParalConfigTuner,
                default_config_path,
            )

            config_tuner = ParalConfigTuner(
                self._client, default_config_path(self._config.job_name)
            )
            config_tuner.start()
            self._config.worker_env.setdefault(
                "DLROVER_TPU_PARAL_CONFIG_FILE", config_tuner.config_path
            )
        try:
            self._initialize_workers()
            return self._monitor_loop()
        except Exception:
            # post-mortem artifact before the exception unwinds the agent
            from dlrover_tpu.observability.flight_recorder import (
                REASON_CRASH,
            )

            self._flight_recorder.capture(REASON_CRASH, extra={
                "error": traceback.format_exc(limit=20),
            })
            raise
        finally:
            self._stop_flag.set()
            resource_monitor.stop()
            self._training_monitor.stop()
            self._stop_workers()
            if config_tuner is not None:
                config_tuner.stop()
            if self._ckpt_saver is not None:
                self._ckpt_saver.stop()
            if self._replica_service is not None:
                self._replica_service.stop()
            if self._reshard_service is not None:
                self._reshard_service.stop()
            if timer_daemon is not None:
                timer_daemon.kill()
            if self._warm_pool is not None:
                self._warm_pool.stop()
            self._ipc_server.stop()

    def _monitor_loop(self) -> int:
        interval = self._config.monitor_interval_s
        membership_poll = 0.0
        while True:
            time.sleep(interval)  # noqa: DLR010 — the agent's FOREGROUND loop pacing subprocess polls; it exits via worker-state transitions, not a stop event
            result = self._monitor_workers()
            if result.state == WorkerState.SUCCEEDED:
                logger.info("node %s workers all succeeded",
                            self._config.node_rank)
                self._client.update_node_status(NodeStatus.SUCCEEDED)
                return 0
            if result.state == WorkerState.FAILED:
                if not self._handle_worker_failure(result):
                    return 1
                continue
            # healthy: check diagnosis actions and membership changes
            action, action_data = self._take_pending_action()
            if action == DiagnosisActionType.RESTART_WORKER:
                # a restart marked "wedged" (hang watchdog) means the
                # workers are blocked in a dead collective and will not
                # exit gracefully — waiting the full stop grace is pure
                # downtime, and SIGKILLing fast is safe because shm frames
                # are seal-written (a kill mid-write leaves an unreadable
                # frame, not a torn one) and the ipc lock server releases
                # a dead holder's locks. Unmarked restarts (e.g. the
                # peer-left broadcast, master.py) target HEALTHY workers
                # mid-cleanup: they keep the normal grace.
                grace = None
                if action_data.get("wedged"):
                    from dlrover_tpu.common.config import get_context

                    grace = get_context().wedged_kill_grace_s
                # a master-originated action carries the trace context of
                # the arc that caused it (e.g. fault.relaunch): restoring
                # it here joins this restart to that trace_id
                carried = tracing.extract_wire(
                    action_data.get(tracing.WIRE_KEY)
                )
                with tracing.activate(carried), tracing.span(
                    SpanName.AGENT_RESTART_WORKERS,
                    source=f"agent_{self._config.node_rank}",
                    reason=action_data.get("reason", ""),
                ):
                    self._restart_workers(
                        f"diagnosis action {action} "
                        f"({action_data.get('reason', '')})",
                        grace_s=grace,
                    )
                continue
            if action == DiagnosisActionType.STACK_DUMP:
                # skew monitor flagged one of this node's ranks as a
                # straggler: capture evidence (xprof + py/native stacks)
                # WITHOUT restarting anything — runs on a background
                # thread because gdb attach can take ~20s per worker
                self._capture_stack_dump(action_data)
                continue
            if action == DiagnosisActionType.CHECKPOINT:
                # brain-predicted failure on this node: flush the newest
                # shm frames to durable storage while the workers keep
                # training — if the prediction hits, lost work shrinks to
                # the steps since THIS save instead of the last cadence
                # save. workers_dead=False: peers are alive, so the
                # normal commit quorum applies.
                logger.info(
                    "preemptive checkpoint action (%s)",
                    action_data.get("reason", ""),
                )
                if self._ckpt_saver is not None:
                    try:
                        self._ckpt_saver.save_shm_to_storage(
                            reason="brain preemptive checkpoint",
                            workers_dead=False,
                            trigger=MetricLabel.CKPT_TRIGGER_PREEMPTIVE,
                        )
                    except Exception:  # noqa: BLE001 — advisory save
                        logger.exception("preemptive checkpoint failed")
                continue
            if action == DiagnosisActionType.RELAUNCH_WORKER:
                # pod-level: exit so the master's relaunch ladder replaces
                # this node (a wedged chip must not be soft-restarted onto)
                logger.warning("relaunch action — exiting for pod replacement")
                self._stop_workers()
                self._save_breakpoint_checkpoint("relaunch action")
                self._client.update_node_status(
                    NodeStatus.FAILED, exit_reason="relaunched",
                    restart_count=self._restart_count,
                )
                return 1
            if action == DiagnosisActionType.JOB_ABORT:
                logger.error("job abort action received")
                self._client.update_node_status(
                    NodeStatus.FAILED, exit_reason="job_abort"
                )
                return 1
            if self._partition_grace_expired():
                # the partition outlived the grace window: stop burning
                # compute on a world the master may already have recut —
                # persist state and exit cleanly so the relaunch ladder
                # (or the operator) replaces this node
                logger.error(
                    "partition-degraded grace window (%.0fs) expired with "
                    "master still unreachable — saving state and exiting",
                    self._partition_grace_s,
                )
                self._stop_workers()
                self._save_breakpoint_checkpoint("partition grace expired")
                # the bundle is the only evidence that survives this exit:
                # the master is unreachable, so nothing else gets reported
                from dlrover_tpu.observability.flight_recorder import (
                    REASON_PARTITION,
                )

                self._flight_recorder.capture(REASON_PARTITION, extra={
                    "grace_s": self._partition_grace_s,
                    "failed_heartbeats": self._hb_consec_failures,
                })
                try:
                    # best-effort: the open circuit breaker makes this fail
                    # fast if the master is still gone
                    self._client.update_node_status(
                        NodeStatus.FAILED,
                        exit_reason="partition_grace_expired",
                        restart_count=self._restart_count,
                    )
                except ConnectionError:
                    pass
                return 1
            now = time.monotonic()
            if now - membership_poll >= 1.0:
                membership_poll = now
                if self._membership_changed():
                    self._restart_workers("membership changed")

    def _handle_worker_failure(self, result: RunResult) -> bool:
        """Returns True to continue (restarted), False to give up.

        The DiagnosisAgent decides RESTART_WORKER (in place) vs
        RELAUNCH_WORKER (this agent exits non-zero; the master's relaunch
        ladder replaces the pod) — reference diagnose_training_failure:137."""
        logger.warning(
            "node %s worker failure(s): %s",
            self._config.node_rank, result.failures,
        )
        self._events.instant(
            AgentEvent.WORKER_FAIL, failures=result.failures,
            restart_count=self._restart_count,
        )
        self._worker_failures_counter.inc()
        try:
            self._client.report_failure(
                error_data=str(result.failures),
                level=TrainingExceptionLevel.PROCESS_ERROR,
                restart_count=self._restart_count,
            )
        except ConnectionError:
            pass
        # the budget counts only failure-driven restarts (_restart_count
        # also grows on membership changes); the verdict is the single
        # decision point for giving up in place
        verdict = self._diagnosis.diagnose_training_failure(
            result.failures, self._remaining_restarts
        )
        if verdict == DiagnosisActionType.RELAUNCH_WORKER:
            logger.error(
                "giving up in-place restarts on node %s (verdict=%s, "
                "remaining=%s)", self._config.node_rank, verdict,
                self._remaining_restarts,
            )
            self._save_breakpoint_checkpoint("relaunch")
            self._client.update_node_status(
                NodeStatus.FAILED, exit_reason="relaunched",
                restart_count=self._restart_count,
            )
            return False
        self._remaining_restarts -= 1
        self._restart_workers(f"worker failure {result.failures}")
        return True
