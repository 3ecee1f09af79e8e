"""Worker-process API: bootstrap + control-plane helpers.

What the reference achieves with torchrun env vars (RANK/WORLD_SIZE/...) plus
``init_process_group``, a TPU worker gets from :func:`init`: read the env the
agent set, bootstrap ``jax.distributed`` with the master-rendezvoused
coordinator, and hand back a :class:`WorkerContext` with the control-plane
client (steps, shards, kv) wired up.
"""

import os
import time
from dataclasses import dataclass
from typing import Optional

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.constants import ConfigKey, EnvKey, env_str
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability import compile_watch, tracing


@dataclass
class WorkerContext:
    rank: int
    world_size: int
    local_rank: int
    local_world_size: int
    node_rank: int
    node_num: int
    restart_count: int
    master: Optional[MasterClient]
    job_name: str = "local"

    @property
    def is_leader(self) -> bool:
        return self.rank == 0

    def report_step(self, step: int) -> None:
        if self.master is not None:
            try:
                self.master.report_global_step(step, time.time())
            except ConnectionError:
                pass

    @property
    def ipc_socket(self) -> str:
        return os.getenv("DLROVER_TPU_IPC_SOCKET", "")

    def training_span(self, **content):
        """The productive-time span offline goodput analysis counts
        (common/event.py compute_goodput). Use around the training loop:

            with ctx.training_span():
                for batch in data: ...

        Crashing inside the span leaves it unterminated — exactly the lost
        time a fault costs."""
        from dlrover_tpu.common.event import TrainEvent, get_emitter

        return get_emitter(f"worker_{self.rank}").span(
            TrainEvent.TRAINING, rank=self.rank, **content
        )

    def publish_step(self, step: int) -> None:
        """Publish progress to the local agent via the SharedDict IPC (the
        agent's TrainingMonitor forwards it to the master — reference
        monitor/training.py:40 reads a metrics file instead). Cheaper than
        :meth:`report_step` (unix socket, no cross-host RPC) and also feeds
        the agent's own hang bookkeeping.

        Every ~15 s the publish also carries this worker's device HBM
        stats (the agent process must not touch jax — the worker owns the
        chips); the agent's ResourceMonitor forwards them to the master,
        where they drive micro-batch auto-tuning and stall diagnosis."""
        if not self.ipc_socket:
            return
        from dlrover_tpu.agent.monitor import (
            HBM_KEY_PREFIX,
            MEM_KEY_PREFIX,
            OPTEL_KEY_PREFIX,
            TRAINING_METRICS_DICT,
        )
        from dlrover_tpu.common.multi_process import SharedDict
        from dlrover_tpu.observability.memory import get_accountant
        from dlrover_tpu.observability.op_telemetry import get_accumulator

        if not hasattr(self, "_metrics_dict"):
            self._metrics_dict = SharedDict(
                TRAINING_METRICS_DICT, self.ipc_socket
            )
            self._last_hbm_publish = 0.0
        payload = {"step": step, "ts": time.time()}
        now = time.time()
        mem_acc = get_accountant()
        mem_acc.step_mark(step)
        if now - self._last_hbm_publish > 15.0:
            self._last_hbm_publish = now
            hbm = self._collect_hbm()
            if hbm:
                payload[f"{HBM_KEY_PREFIX}{self.local_rank}"] = hbm
            # the accountant's ledger rides the same cadence; stamped
            # with the global rank the master attributes against
            snap = mem_acc.wire_snapshot()
            snap["rank"] = self.rank
            payload[f"{MEM_KEY_PREFIX}{self.local_rank}"] = snap
        acc = get_accumulator()
        if acc.seq:
            # cumulative op-class histograms for the master's skew monitor;
            # keyed by local rank in the dict, stamped with the global rank
            # the master attributes against
            snap = acc.snapshot()
            snap["rank"] = self.rank
            payload[f"{OPTEL_KEY_PREFIX}{self.local_rank}"] = snap
        try:
            self._metrics_dict.update(payload)
        except OSError:
            pass

    @staticmethod
    def _collect_hbm() -> dict:
        """Per-local-device {id: {hbm_used_mb, hbm_total_mb}}, via the
        process MemoryAccountant's reconciliation sweep — ONE collection
        path for device stats (observability/memory.py). A sweep that
        can't see the device journals ``memory_degraded`` once per
        episode instead of debug-swallowing here."""
        from dlrover_tpu.observability.memory import (
            get_accountant,
            per_device_stats,
        )

        get_accountant().reconcile()
        return per_device_stats()


def default_compile_cache_dir() -> str:
    """The one fixed cache directory, inside the checkout. The path is
    part of the cache key, so it must not move between incarnations:
    never ``~``, a temp name, a pid or a time."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".xla_cache",
    )


def enable_compilation_cache() -> None:
    """Turn on XLA's persistent compilation cache.

    Elastic restarts re-spawn worker processes, and under jit the first
    step would otherwise pay full recompilation (tens of seconds for a
    real model) every restart — the dominant term in restart-to-training
    time on TPU, where the reference's torch workers pay nothing. With the
    cache, a restarted worker (same world shape) deserializes the
    executable instead (SURVEY.md §7 hard part b).

    Placement is the caller's: where ``JAX_COMPILATION_CACHE_DIR`` is set
    jax already reads it and nothing here overrides it (the agent's
    workers and warm spares inherit the variable); unset, the cache lives
    in :func:`default_compile_cache_dir`. DLROVER_TPU_COMPILE_CACHE=off
    disables it.
    """
    import jax

    if env_str(ConfigKey.COMPILE_CACHE).lower() in ("off", "0", "disable"):
        jax.config.update("jax_enable_compilation_cache", False)
        return
    cache = env_str(ConfigKey.JAX_COMPILATION_CACHE_DIR)
    try:
        if not cache:
            cache = default_compile_cache_dir()
            os.makedirs(cache, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache)
        # cache everything that took meaningful XLA time (the threshold is
        # against compile time proper, not trace+lower wall time — keep it
        # low or real train steps get filtered); tiny probe computations
        # stay uncached to keep the directory lean
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.05)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        logger.info("XLA compilation cache at %s", cache)
    except Exception as e:  # noqa: BLE001 — cache is an optimization only
        logger.warning("compilation cache unavailable: %r", e)


def init(initialize_jax_distributed: bool = True) -> WorkerContext:
    """Bootstrap the worker from the agent-provided environment.

    With >1 process in the world, calls ``jax.distributed.initialize`` with
    the coordinator the master rendezvoused (rank-0 host + free port) — the
    analogue of the reference bootstrapping a torch Store from the master KV
    (master_kv_store.py:24).
    """
    rank = int(os.getenv(EnvKey.RANK, "0"))
    world_size = int(os.getenv(EnvKey.WORLD_SIZE, "1"))
    enable_compilation_cache()
    # the program's spans also sit in any profile taken of this process,
    # beside the device ops and on their clock
    import jax.profiler

    tracing.install_bridge(jax.profiler.TraceAnnotation)
    # the agent's spawn context, for the first restore to join (a resumed
    # worker's restore then sits in the fault arc that started it)
    tracing.get_tracer().hand_over(
        tracing.extract_env(env_str(EnvKey.TRACE_PARENT)))
    # and its compile requests are counted from here on, set-up's too
    compile_watch.get_watcher()
    coordinator = os.getenv(EnvKey.COORDINATOR_ADDR, "")
    if initialize_jax_distributed and world_size > 1 and coordinator:
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=world_size,
            process_id=rank,
            # elastic jobs reap crashed workers FAST: a worker whose
            # collective failed (peer died) otherwise blocks in the
            # distributed client's exit barrier for the full 300s default
            # — pointlessly, since its agent owns checkpoint persistence
            # and will re-rendezvous a fresh incarnation. The barrier
            # still coordinates healthy shutdowns within the timeout.
            # 60s (not jax's 300s): long enough for a healthy world's
            # ranks to reach the exit barrier skewed (rank 0 writing a
            # final checkpoint), short enough that a crashed worker whose
            # peer died doesn't pin the host — the agent's SIGKILL
            # escalation (worker_stop_grace_s) reaps faster anyway when
            # it wants the slot back
            shutdown_timeout_seconds=int(
                os.getenv("DLROVER_TPU_DIST_SHUTDOWN_S", "60")
            ),
            # detect a dead peer at the runtime level too (the master's
            # connection-drop detection is the primary signal)
            heartbeat_timeout_seconds=int(
                os.getenv("DLROVER_TPU_DIST_HEARTBEAT_S", "30")
            ),
        )
        logger.info(
            "jax.distributed initialized: rank=%s/%s coordinator=%s",
            rank, world_size, coordinator,
        )
    master_addr = os.getenv(EnvKey.MASTER_ADDR, "")
    master = None
    if master_addr:
        master = MasterClient(
            master_addr,
            int(os.getenv(EnvKey.NODE_ID, "0")),
            int(os.getenv(EnvKey.NODE_RANK, "0")),
        )
    ipc = os.getenv("DLROVER_TPU_IPC_SOCKET", "")
    if ipc and os.path.exists(ipc) and os.getenv(
        "DLROVER_TPU_PROFILE_LISTENER", "1"
    ) != "0":
        # on-demand xprof capture (observability/profiler.py): the agent's
        # hang diagnosis asks workers for an XLA trace over this channel
        from dlrover_tpu.observability.profiler import ProfileListener

        listener = ProfileListener(
            ipc, int(os.getenv(EnvKey.LOCAL_RANK, "0"))
        )
        listener.start()
    if os.getenv("TPU_TIMER_ENABLE"):
        # agent opted this job into the observability plane: start the
        # native engine, serve per-rank metrics, patch the live PJRT table
        # (tpu_timer/; the reference reaches this point via LD_PRELOAD)
        from dlrover_tpu.observability import TpuTimer

        timer = TpuTimer()
        timer.install(
            rank=rank,
            world_size=world_size,
            local_rank=int(os.getenv(EnvKey.LOCAL_RANK, "0")),
        )
        timer.enable_gc_hook()
        if os.getenv("DLROVER_TPU_TRACE_FUNCS"):
            # opt-in user-function tracepoints into the same trace plane
            # (observability/tpu_timer.py install_tracepoints)
            from dlrover_tpu.observability import install_tracepoints

            install_tracepoints()
    return WorkerContext(
        rank=rank,
        world_size=world_size,
        local_rank=int(os.getenv(EnvKey.LOCAL_RANK, "0")),
        local_world_size=int(os.getenv(EnvKey.LOCAL_WORLD_SIZE, "1")),
        node_rank=int(os.getenv(EnvKey.NODE_RANK, "0")),
        node_num=int(os.getenv(EnvKey.NODE_NUM, "1")),
        restart_count=int(os.getenv(EnvKey.RESTART_COUNT, "0")),
        master=master,
        job_name=os.getenv(EnvKey.JOB_NAME, "local"),
    )
