"""Constants and enums for the TPU-native elastic stack.

Reference surface: dlrover/python/common/constants.py (node types, statuses,
accelerators, rendezvous names, timeouts). Re-designed for TPU: accelerators
are TPU generations, node-check runs over ICI/DCN, HCCL/NCCL specifics dropped.

This module is also the **environment-variable registry**: every env name
the stack reads lives here (:class:`EnvKey` for the agent→worker fork
boundary, :class:`ConfigKey` for operator-facing knobs) and every read
goes through the ``env_*`` accessors below. The static analyzer enforces
this (rule DLR002): a raw ``os.environ``/``os.getenv`` read anywhere else
fails ``python -m dlrover_tpu.analysis --check`` — otherwise fault drills
and docs that enumerate the knobs from this registry silently go stale.
"""

import os


def get_env(name: str, default=None):
    """Raw accessor (``os.environ.get``). Prefer the typed variants."""
    return os.environ.get(name, default)


def env_str(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


def env_int(name: str, default: int = 0) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def env_float(name: str, default: float = 0.0) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def env_flag(name: str, default: bool = False) -> bool:
    """Truthiness of an env toggle: unset → ``default``; set → anything
    except 0/false/no/off/empty is True."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("", "0", "false", "no", "off")


class PlatformType:
    LOCAL = "local"
    KUBERNETES = "kubernetes"
    GKE_TPU = "gke_tpu"


class Accelerator:
    """Accelerator families (reference constants.py:434 Accelerators)."""

    TPU = "tpu"
    CPU = "cpu"  # JAX CPU backend — used by tests and local dev


class NodeType:
    MASTER = "master"
    WORKER = "worker"
    # PS/chief/evaluator exist in the reference for the TF stack; the TPU
    # build is SPMD-only, so WORKER is the only trainable role. SERVE is
    # the decode-serving replica role (dlrover_tpu/serving/): it shares
    # the worker's liveness plane (heartbeats, conn-drop detection) but a
    # SERVE death is absorbed by request re-routing + the serving
    # autoscaler instead of a training world re-formation.
    SERVE = "serve"


class NodeStatus:
    INITIAL = "initial"
    PENDING = "pending"
    RUNNING = "running"
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    DELETED = "deleted"
    BREAKDOWN = "breakdown"

    @classmethod
    def terminal(cls, status: str) -> bool:
        return status in (cls.SUCCEEDED, cls.FAILED, cls.DELETED)


class NodeEventType:
    ADDED = "added"
    MODIFIED = "modified"
    DELETED = "deleted"
    ERROR = "error"


class NodeExitReason:
    """Why a worker/node terminated (reference constants.py NodeExitReason)."""

    SUCCEEDED = "succeeded"
    KILLED = "killed"
    OOM = "oom"
    FATAL_ERROR = "fatal_error"
    HARDWARE_ERROR = "hardware_error"
    PREEMPTED = "preempted"
    RELAUNCHED = "relaunched"
    NO_HEARTBEAT = "no_heartbeat"
    UNKNOWN = "unknown"


class JobStage:
    INIT = "init"
    PENDING = "pending"
    RUNNING = "running"
    SUSPENDED = "suspended"
    SUCCEEDED = "succeeded"
    FAILED = "failed"


class RendezvousName:
    """Named rendezvous rounds served by the master
    (reference constants.py RendezvousName: elastic-training / network-check)."""

    TRAINING = "training"
    NODE_CHECK = "node-check"


class NetworkFailureReason:
    NO_INIT = "no_init"
    NODE_FAILURE = "node_failure"
    WAITING_NODE = "waiting_node"


class DiagnosisActionType:
    NONE = "no_action"
    # agent-level
    RESTART_WORKER = "restart_worker"
    RELAUNCH_WORKER = "relaunch_worker"
    # capture py-stacks / xprof from a straggling rank without restarting it
    STACK_DUMP = "stack_dump"
    # persist the newest shm checkpoint frames to storage NOW, without
    # touching the workers — the BrainAdvisor's pre-emptive breakpoint
    # checkpoint ahead of a predicted node failure (brain/advisor.py)
    CHECKPOINT = "checkpoint"
    # master-level
    MASTER_RELAUNCH_WORKER = "master_relaunch_worker"
    JOB_ABORT = "job_abort"
    EVENT = "event"


class DiagnosisConstant:
    MASTER_INSTANCE = -1
    ANY_INSTANCE = -2
    ACTION_EXPIRY_S = 60 * 5


class PreCheckStatus:
    """Master pre-check verdict polled by agents before training starts
    (reference constants.py PreCheckStatus)."""

    PASS = "pass"
    FAIL = "fail"
    CHECKING = "checking"


class TrainingExceptionLevel:
    RDZV_ERROR = "rdzv_error"
    PROCESS_ERROR = "process_error"
    NODE_ERROR = "node_error"
    WARNING = "warning"
    INFO = "info"


class CheckpointConstant:
    """Flash Checkpoint layout (reference:
    dlrover/python/common/constants.py CheckpointConstant + ckpt_saver.py)."""

    STATE_DICT_NAME = "state.dlrover"
    META_NAME = "meta.dlrover"
    TRACKER_FILE = "latest_step.txt"
    DONE_DIR = "._done"
    TEMP_DIR_PREFIX = "._tmp_"
    SAVE_TIMEOUT_S = 600
    # incremental-chain layout (ckpt/manifest.py): one manifest link per
    # frame per step, committed write-temp → fsync → atomic replace; delta
    # links reference unchanged shards in ancestor steps' payload files
    MANIFEST_PREFIX = "manifest_"
    MANIFEST_SUFFIX = ".mf"
    DELTA_PREFIX = "delta_"
    FRAME_SUFFIX = ".dlrover"


class SharedResourceName:
    """Names of agent-served IPC resources (reference ckpt_saver.py constants)."""

    SAVE_LOCK = "flash_ckpt_save_lock"
    SAVE_EVENT_QUEUE = "flash_ckpt_event_queue"
    SHM_META_DICT = "flash_ckpt_shm_meta"


class GoodputEvent:
    TRAINING_START = "training_start"
    FAULT = "fault"
    RECOVERY = "recovery"
    CKPT_SAVE = "ckpt_save"
    CKPT_RESTORE = "ckpt_restore"


class EnvKey:
    """Environment variables crossing the agent→worker fork boundary."""

    JOB_NAME = "DLROVER_TPU_JOB_NAME"
    MASTER_ADDR = "DLROVER_TPU_MASTER_ADDR"
    NODE_ID = "DLROVER_TPU_NODE_ID"
    NODE_RANK = "DLROVER_TPU_NODE_RANK"
    NODE_NUM = "DLROVER_TPU_NODE_NUM"
    LOCAL_RANK = "DLROVER_TPU_LOCAL_RANK"
    LOCAL_WORLD_SIZE = "DLROVER_TPU_LOCAL_WORLD_SIZE"
    RANK = "DLROVER_TPU_RANK"
    WORLD_SIZE = "DLROVER_TPU_WORLD_SIZE"
    # jax.distributed bootstrap (set by the agent from master rendezvous)
    COORDINATOR_ADDR = "DLROVER_TPU_COORDINATOR_ADDR"
    PROCESS_ID = "DLROVER_TPU_PROCESS_ID"
    NUM_PROCESSES = "DLROVER_TPU_NUM_PROCESSES"
    RESTART_COUNT = "DLROVER_TPU_RESTART_COUNT"
    RDZV_ROUND = "DLROVER_TPU_RDZV_ROUND"
    # checkpoint replica backup-group size (0/1 = off)
    REPLICA_GROUP = "DLROVER_TPU_REPLICA_GROUP"
    # fault injection for node-check benchmarks
    # (reference: trainer/torch/node_check/utils.py:52 MOCK_ERR_RANK)
    MOCK_ERR_RANK = "DLROVER_TPU_MOCK_ERR_RANK"
    # per-agent-incarnation nonce suffixing shm segment names: a restarted
    # agent never reattaches to a dead predecessor's half-written segments
    # (ckpt/shm_handler.py shm_name / cleanup_orphan_segments)
    SHM_INCARNATION = "DLROVER_TPU_SHM_INCARNATION"
    # grace window (seconds) the agent keeps training on cached shard
    # assignments while the master is unreachable (partition-degraded mode)
    PARTITION_GRACE_S = "DLROVER_TPU_PARTITION_GRACE_S"
    # "<trace_id>:<span_id>" of the agent's spawn (observability/tracing.py
    # inject_env): the worker's first ``ckpt.restore`` joins that trace
    TRACE_PARENT = "DLROVER_TPU_TRACE_PARENT"


class ConfigKey:
    """Operator-facing env knobs (everything that is not part of the
    agent→worker fork contract in :class:`EnvKey`). Grouped by the layer
    that reads them; reads go through the ``env_*`` accessors above."""

    # master
    MASTER_STATE_DIR = "DLROVER_TPU_MASTER_STATE_DIR"
    MASTER_SNAPSHOT_S = "DLROVER_TPU_MASTER_SNAPSHOT_S"
    HTTP_PORT = "DLROVER_TPU_HTTP_PORT"
    JOB_UID = "DLROVER_TPU_JOB_UID"
    RUN_CONFIG = "DLROVER_TPU_RUN_CONFIG"
    # ckpt
    IPC_SOCKET = "DLROVER_TPU_IPC_SOCKET"
    CKPT_CRC = "DLROVER_TPU_CKPT_CRC"
    CKPT_READY_TIMEOUT = "DLROVER_TPU_CKPT_READY_TIMEOUT"
    CKPT_READY_COOLDOWN = "DLROVER_TPU_CKPT_READY_COOLDOWN"
    CKPT_STORAGE_WAIT = "DLROVER_TPU_CKPT_STORAGE_WAIT"
    # incremental persistence plane (ckpt/manifest.py): dirty-shard delta
    # checkpoints on/off, max delta links before a full-rebase compaction,
    # and the stripe size (bytes) for parallel cold persists/restores
    CKPT_DELTA = "DLROVER_TPU_CKPT_DELTA"
    CKPT_CHAIN_MAX = "DLROVER_TPU_CKPT_CHAIN_MAX"
    CKPT_STRIPE_BYTES = "DLROVER_TPU_CKPT_STRIPE_BYTES"
    # live resharding (ckpt/reshard.py): enable flag (default on), per-peer
    # RPC timeout for shard-region fetches, and how long a worker waits for
    # survivor agents to publish their reshard service addresses
    RESHARD = "DLROVER_TPU_RESHARD"
    RESHARD_TIMEOUT_S = "DLROVER_TPU_RESHARD_TIMEOUT_S"
    # mesh re-decomposition (parallel/replan.py): enable flag for the
    # world-cut planner (default on; off = same-decomposition reshard,
    # the pre-replan behavior), the largest tensor-parallel degree the
    # planner may pick (model-shape bound), and how long a chosen
    # decomposition's step-time prediction stays open before it scores
    # itself a miss
    REPLAN = "DLROVER_TPU_REPLAN"
    REPLAN_MAX_TP = "DLROVER_TPU_REPLAN_MAX_TP"
    REPLAN_HORIZON_S = "DLROVER_TPU_REPLAN_HORIZON_S"
    # state-movement fabric (common/fabric.py): stripe size (bytes) a bulk
    # transfer is split into, connections a fetcher opens per source, and
    # the per-source concurrent-fetch admission cap (incast protection)
    FABRIC_STRIPE_BYTES = "DLROVER_TPU_FABRIC_STRIPE_BYTES"
    FABRIC_CONNS = "DLROVER_TPU_FABRIC_CONNS"
    FABRIC_ADMIT = "DLROVER_TPU_FABRIC_ADMIT"
    # ops/flash_attention.py backward-pass block overrides (tuned
    # independently of the forward blocks; read at trace time)
    FLASH_BWD_BLOCK_Q = "DLROVER_TPU_FLASH_BWD_BLOCK_Q"
    FLASH_BWD_BLOCK_K = "DLROVER_TPU_FLASH_BWD_BLOCK_K"
    # agent / worker
    HOST_IP = "DLROVER_TPU_HOST_IP"
    AGENT_METRICS_PORT = "DLROVER_TPU_AGENT_METRICS_PORT"
    WARM_WAIT_S = "DLROVER_TPU_WARM_WAIT_S"
    WARM_PREIMPORT = "DLROVER_TPU_WARM_PREIMPORT"
    COMPILE_CACHE = "DLROVER_TPU_COMPILE_CACHE"
    # jax's own variable, named here only so worker.py reads it through
    # the registry: where it is set, nothing in this package overrides it
    JAX_COMPILATION_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
    DIST_SHUTDOWN_S = "DLROVER_TPU_DIST_SHUTDOWN_S"
    DIST_HEARTBEAT_S = "DLROVER_TPU_DIST_HEARTBEAT_S"
    TRACE_FUNCS = "DLROVER_TPU_TRACE_FUNCS"
    # tpu_timer / profiler (observability/)
    TPU_TIMER_LIB = "TPU_TIMER_LIB"
    TPU_TIMER_PORT = "TPU_TIMER_PORT"
    TPU_TIMER_DAEMON_PATH = "TPU_TIMER_DAEMON_PATH"
    TPU_LIBRARY_PATH = "TPU_LIBRARY_PATH"
    PROFILE_DIR = "DLROVER_TPU_PROFILE_DIR"
    # diagnosis
    CHECK_TIMEOUT_S = "DLROVER_TPU_CHECK_TIMEOUT_S"
    # skew / hang attribution (master/skew_monitor.py)
    SKEW_THRESHOLD = "DLROVER_TPU_SKEW_THRESHOLD"
    SKEW_WINDOW = "DLROVER_TPU_SKEW_WINDOW"
    # hierarchical control-plane fan-in (master/fanin.py, agent/fanin.py):
    # aggregation-tree branching factor (0/1 = flat, every agent talks to
    # the master directly), aggregator flush cadence, the per-beat handler
    # latency (ms) above which the master starts shedding telemetry, the
    # KV store's internal shard count, and a test-only override forcing a
    # backpressure level regardless of measured load
    FANIN_DEGREE = "DLROVER_TPU_FANIN_DEGREE"
    FANIN_FLUSH_S = "DLROVER_TPU_FANIN_FLUSH_S"
    FANIN_SHED_MS = "DLROVER_TPU_FANIN_SHED_MS"
    FANIN_KV_SHARDS = "DLROVER_TPU_FANIN_KV_SHARDS"
    FANIN_FORCE_LEVEL = "DLROVER_TPU_FANIN_FORCE_LEVEL"
    # elastic decode-serving plane (dlrover_tpu/serving/): autoscaler
    # signal thresholds — TTFT p99 SLO (seconds) and the router queue
    # depth above which the serving optimizer grows the replica set —
    # plus the grow/shrink cooldowns bounding oscillation
    SERVE_TTFT_SLO_S = "DLROVER_TPU_SERVE_TTFT_SLO_S"
    SERVE_QUEUE_HI = "DLROVER_TPU_SERVE_QUEUE_HI"
    SERVE_GROW_COOLDOWN_S = "DLROVER_TPU_SERVE_GROW_COOLDOWN_S"
    SERVE_SHRINK_COOLDOWN_S = "DLROVER_TPU_SERVE_SHRINK_COOLDOWN_S"
    # serving performance plane (serving/engine.py, serving/prefix_cache.py,
    # serving/speculative.py): int8 KV cache in the batched engine (0/1,
    # default off), radix prefix-cache reuse on/off, its byte budget and
    # match-block quantum (reuse lengths are multiples of the block so the
    # chunked-prefill trace count stays bounded), and the speculative
    # draft length k
    SERVE_QUANT = "DLROVER_TPU_SERVE_QUANT"
    SERVE_PREFIX = "DLROVER_TPU_SERVE_PREFIX"
    SERVE_PREFIX_BYTES = "DLROVER_TPU_SERVE_PREFIX_BYTES"
    SERVE_PREFIX_BLOCK = "DLROVER_TPU_SERVE_PREFIX_BLOCK"
    SERVE_SPEC_K = "DLROVER_TPU_SERVE_SPEC_K"
    # models/decode.py fused-kernel routing: 1/0 force the pallas decode
    # kernel on/off; default "auto" follows the measured policy in
    # flash_decode_wanted
    FLASH_DECODE = "DLROVER_TPU_FLASH_DECODE"
    # agentic-RL rollout plane (dlrover_tpu/rl/): the on-policy staleness
    # bound (learner_version − generation_version a trajectory may carry
    # and still be trained), the trajectory-lease timeout after which an
    # unacked episode is requeued onto a survivor, and the per-call
    # timeout for learner→replica weight-sync fabric sessions
    RL_STALENESS_BOUND = "DLROVER_TPU_RL_STALENESS_BOUND"
    RL_LEASE_TIMEOUT_S = "DLROVER_TPU_RL_LEASE_TIMEOUT_S"
    RL_SYNC_TIMEOUT_S = "DLROVER_TPU_RL_SYNC_TIMEOUT_S"
    # brain predictive loop (brain/persister.py, brain/advisor.py): master-
    # side telemetry persistence + proactive advice on/off (default on),
    # the sqlite datastore path ("" = per-job in-memory), the persister/
    # advisor tick cadence, and the prediction horizon the failure prior
    # and traffic forecaster look ahead over
    BRAIN = "DLROVER_TPU_BRAIN"
    BRAIN_DB = "DLROVER_TPU_BRAIN_DB"
    BRAIN_TICK_S = "DLROVER_TPU_BRAIN_TICK_S"
    BRAIN_HORIZON_S = "DLROVER_TPU_BRAIN_HORIZON_S"
    # chaos / observability
    FAULT_SCHEDULE = "DLROVER_FAULT_SCHEDULE"
    FAULT_SEED = "DLROVER_FAULT_SEED"
    EVENT_DIR = "DLROVER_TPU_EVENT_DIR"
    LOG_LEVEL = "DLROVER_TPU_LOG_LEVEL"
    # tracing / flight recorder (observability/tracing.py,
    # observability/flight_recorder.py)
    TRACE = "DLROVER_TPU_TRACE"
    TRACE_RING = "DLROVER_TPU_TRACE_RING"
    TRACE_DIR = "DLROVER_TPU_TRACE_DIR"
    TRACE_BUNDLE_COOLDOWN_S = "DLROVER_TPU_TRACE_BUNDLE_COOLDOWN_S"
    # serving SLO plane (observability/slo.py): goodput floor (fraction of
    # requests that must complete OK), the fast/slow burn-rate evaluation
    # windows, the burn-rate threshold both windows must exceed before an
    # alert journals, and the alert re-fire cooldown
    SERVE_GOODPUT_SLO = "DLROVER_TPU_SERVE_GOODPUT_SLO"
    SERVE_SLO_BURN_FAST_S = "DLROVER_TPU_SERVE_SLO_BURN_FAST_S"
    SERVE_SLO_BURN_SLOW_S = "DLROVER_TPU_SERVE_SLO_BURN_SLOW_S"
    SERVE_SLO_BURN_RATE = "DLROVER_TPU_SERVE_SLO_BURN_RATE"
    SERVE_SLO_ALERT_COOLDOWN_S = "DLROVER_TPU_SERVE_SLO_ALERT_COOLDOWN_S"
    # tail-latency attribution (serving/tail.py): the slow percentile a
    # request must exceed to be attributed, the minimum completed-request
    # window before attribution starts, and how many worst request traces
    # a replica's flight-recorder bundle carries
    SERVE_TAIL_PCTL = "DLROVER_TPU_SERVE_TAIL_PCTL"
    SERVE_TAIL_MIN_WINDOW = "DLROVER_TPU_SERVE_TAIL_MIN_WINDOW"
    SERVE_TRACE_WORST = "DLROVER_TPU_SERVE_TRACE_WORST"
    # device-plane memory/compile observability (observability/memory.py,
    # observability/compile_watch.py): synthetic HBM limit for CPU CI
    # (bytes; 0 = use PJRT's reported limit), the headroom fraction below
    # which memory_pressure journals + a forensics bundle captures, and
    # the distinct-signature count per jit fn per window that counts as a
    # recompile storm
    HBM_LIMIT_BYTES = "DLROVER_TPU_HBM_LIMIT_BYTES"
    MEM_PRESSURE_FRAC = "DLROVER_TPU_MEM_PRESSURE_FRAC"
    COMPILE_STORM_N = "DLROVER_TPU_COMPILE_STORM_N"


class SpanName:
    """Span and span-event names for observability/tracing.py. Like
    journal kinds (JournalEvent) and metric names, span names are
    registry constants — rule DLR007 rejects ad-hoc string literals at
    ``.span(...)`` call sites so a typo can't fork a trace arc into two
    names that never correlate."""

    # rendezvous arc (agent/master_client.py client side,
    # master/rdzv_manager.py server side)
    RDZV_CLIENT_ROUND = "rdzv.client_round"
    RDZV_JOIN = "rdzv.join"
    RDZV_WORLD_WAIT = "rdzv.world_wait"
    RDZV_WORLD_CUT = "rdzv.world_cut"
    # flash-checkpoint arc (ckpt/engine.py worker side,
    # ckpt/ckpt_saver.py agent side)
    CKPT_SAVE_MEMORY = "ckpt.save_to_memory"
    CKPT_DRAIN = "ckpt.drain"
    CKPT_PERSIST_REQUEST = "ckpt.persist_request"
    CKPT_PERSIST = "ckpt.persist"
    CKPT_COMMIT = "ckpt.commit"
    CKPT_RESTORE = "ckpt.restore"
    # a frame's persist from inside (ckpt_saver._persist_one, both the
    # save-event and the breakpoint path): the frame lock held and the
    # frame read out of shm, the CRC pass over it, the stripes and the
    # manifest link written
    CKPT_PERSIST_READ = "ckpt.persist.read"
    CKPT_PERSIST_VERIFY = "ckpt.persist.verify"
    CKPT_PERSIST_WRITE = "ckpt.persist.write"
    # the same three from inside (ckpt/engine.py), one span a phase and
    # none a leaf on the save's blocking path. Save block: readiness
    # (drain-alive check, lock, peer exchange), the planning pass
    # (flatten, one on-device snapshot program a device), the meta-dict
    # write. Drain thread: the snapshot on the device-to-host link (first
    # block issued to last landed), the frame write (first piece to the
    # seal: copy + checksums, split by its ``copy_s``/``checksum_s``
    # attrs) -- the two overlap, both children of ``ckpt.drain`` -- then
    # the hand-off to replicas, agent and master.
    CKPT_SAVE_READY = "ckpt.save.ready"
    CKPT_SAVE_PLAN = "ckpt.save.plan"
    CKPT_SAVE_REGISTER = "ckpt.save.register"
    CKPT_DRAIN_D2H_WAIT = "ckpt.drain.d2h_wait"
    CKPT_DRAIN_SHM_WRITE = "ckpt.drain.shm_write"
    CKPT_DRAIN_PUBLISH = "ckpt.drain.publish"
    # restore: the wait for a drain in flight, then one span a rung of
    # the ladder that was tried (``ckpt.chain_restore`` below is the
    # chain rung's), and inside ``_assemble``, on its pool's threads, one
    # span a byte read (``staged`` says whether into the staging ring),
    # one a host-to-device put, one a chunk of the ring made and faulted,
    # one a pair of programs compiled to rebuild large leaves on a device
    CKPT_RESTORE_WAIT_DRAINED = "ckpt.restore.wait_drained"
    CKPT_RESTORE_RESHARD = "ckpt.restore.reshard"
    CKPT_RESTORE_REPLICA_PULL = "ckpt.restore.replica_pull"
    CKPT_RESTORE_VERIFY = "ckpt.restore.verify"
    CKPT_RESTORE_CONSISTENT = "ckpt.restore.consistent"
    CKPT_RESTORE_SHM = "ckpt.restore.shm"
    CKPT_RESTORE_PEER = "ckpt.restore.peer"
    CKPT_RESTORE_STORAGE = "ckpt.restore.storage"
    CKPT_RESTORE_READ = "ckpt.restore.read"
    CKPT_RESTORE_H2D = "ckpt.restore.h2d"
    CKPT_RESTORE_RING = "ckpt.restore.ring"
    CKPT_RESTORE_COMPILE = "ckpt.restore.compile"
    # one dispatch of the train step with every hook round it
    # (trainer/elastic.py ElasticTrainer.train_step)
    TRAIN_STEP = "train.step"
    # incremental-chain storage restore (engine._load_from_chain): the
    # newest-first candidate walk + striped frame reconstruction
    CKPT_CHAIN_RESTORE = "ckpt.chain_restore"
    # live-reshard arc (ckpt/reshard.py planner/executor, served by the
    # agent's ReshardService; one trace_id spans plan → transfers → apply)
    RESHARD_PLAN = "reshard.plan"
    RESHARD_XFER = "reshard.xfer"
    RESHARD_APPLY = "reshard.apply"
    # mesh re-decomposition (parallel/replan.py via ReshardCoordinator):
    # the master-side planner pass on a world cut — enumerate + score +
    # publish; shares the cut's journal round for correlation
    RESHARD_REPLAN = "reshard.replan"
    # state-movement fabric (common/fabric.py): one striped multi-source
    # transfer session, client side
    FABRIC_FETCH = "fabric.fetch"
    # scale-plan arc (master/auto_scaler.py → master/job_manager.py)
    SCALE_APPLY = "scale.apply"
    SCALE_RDZV_PARAMS = "scale.update_rdzv_params"
    # fan-in plane (agent/fanin.py aggregator forward hop,
    # master/fanin.py re-parenting of a dead aggregator's subtree)
    FANIN_FORWARD = "fanin.forward"
    FANIN_REPARENT = "fanin.reparent"
    # elastic decode-serving plane (dlrover_tpu/serving/): router-side
    # routing of one request, replica-side generate handling, a planned
    # drain, and an applied serve plan
    SERVE_ROUTE = "serve.route"
    SERVE_GENERATE = "serve.generate"
    SERVE_DRAIN = "serve.drain"
    SERVE_SCALE = "serve.scale"
    # per-request waterfall segments (serving/batcher.py): the TTFT
    # decomposition queue-wait → prefill-compute → first-step, then one
    # decode segment spanning t_first → t_done; spec_verify brackets one
    # speculative verify leg (serving/speculative.py)
    SERVE_QUEUE_WAIT = "serve.queue_wait"
    SERVE_PREFILL_COMPUTE = "serve.prefill_compute"
    SERVE_FIRST_STEP = "serve.first_step"
    SERVE_DECODE = "serve.decode"
    SERVE_SPEC_VERIFY = "serve.spec_verify"
    # agentic-RL rollout plane (dlrover_tpu/rl/): the learner-side
    # publish→fan-out of one weight version, the replica-side fabric
    # import of it (same trace: the sync version rides the wire context),
    # and one episode-generation call against a rollout replica
    RL_WEIGHT_SYNC = "rl.weight_sync"
    RL_WEIGHT_IMPORT = "rl.weight_import"
    RL_GENERATE = "rl.generate"
    RL_TRAIN_STEP = "rl.train_step"
    # failure-detect → relaunch arc (master/master.py → agent/training.py)
    FAULT_RELAUNCH = "fault.relaunch"
    AGENT_RESTART_WORKERS = "agent.restart_workers"
    AGENT_STACK_DUMP = "agent.stack_dump"
    # the same arc from inside the agent (agent/training.py): the root
    # opened at the poll that saw a worker die, the failure report and
    # verdict, then under ``agent.restart_workers`` the stop, the
    # breakpoint ``ckpt.persist``, ``rdzv.client_round`` and the spawn
    AGENT_WORKER_FAILURE = "agent.worker_failure"
    AGENT_FAILURE_REPORT = "agent.failure_report"
    AGENT_STOP_WORKERS = "agent.stop_workers"
    AGENT_SPAWN = "agent.spawn"
    # the launcher's node health check before training (agent/run.py)
    AGENT_NODE_CHECK = "agent.node_check"
    # span events (retry plane, chaos plane, serving reroutes)
    EVT_RPC_RETRY = "rpc.retry"
    EVT_BREAKER_OPEN = "rpc.breaker_open"
    EVT_FAULT_INJECTED = "chaos.fault_injected"
    EVT_SERVE_REROUTED = "serve.rerouted"


class ChaosSite:
    """Named fault-injection sites for chaos/injector.py. Sites are
    cross-artifact API surface: drill schedules name them, the
    ``docs/design/fault_injection.md`` catalog documents them, and
    chaos-marked tests exercise them — rule DLR016 certifies all four
    views against this registry bidirectionally (a fired-but-undeclared
    site, a dead declaration, a missing catalog row, a phantom row, or
    an undrilled site each fail --check)."""

    # rpc transport (common/rpc.py, common/http_server.py)
    RPC_SEND = "rpc.send"
    RPC_RECV = "rpc.recv"
    # flash-checkpoint shm frame writer (ckpt/shm_handler.py)
    SHM_WRITE = "shm.write"
    # master kv/rendezvous services
    KV_WAIT = "kv.wait"
    RDZV_JOIN = "rdzv.join"
    # live reshard planner + world-cut re-decomposition (ckpt/reshard.py)
    RESHARD_PLAN = "reshard.plan"
    RESHARD_REPLAN = "reshard.replan"
    # state-movement fabric (common/fabric.py)
    FABRIC_CONNECT = "fabric.connect"
    FABRIC_STRIPE = "fabric.stripe"
    # heartbeat fan-in plane (agent/fanin.py)
    HB_FANIN = "hb.fanin"
    AGG_FORWARD = "agg.forward"
    # persistent storage commit protocol (common/storage.py,
    # ckpt/manifest.py)
    STORAGE_PERSIST = "storage.persist"
    STORAGE_COMMIT = "storage.commit"
    # elastic decode-serving plane (dlrover_tpu/serving/)
    SERVE_REQUEST = "serve.request"
    SERVE_REPLICA = "serve.replica"
    SERVE_PREFIX = "serve.prefix"
    # elastic data plane (master/task_manager.py, trainer/data_plane.py)
    DATA_DISPATCH = "data.dispatch"
    DATA_REPORT = "data.report"
    # brain telemetry/advisory plane (dlrover_tpu/brain/)
    BRAIN_PERSIST = "brain.persist"
    BRAIN_QUERY = "brain.query"
    # device-plane memory accountant (observability/memory.py): forces
    # the pressure → journal → bundle path deterministically by shrinking
    # the reconciled headroom below the breach threshold
    MEM_PRESSURE = "mem.pressure"


class MetricLabel:
    """Bounded label-value vocabularies for metric families. Label values
    drawn from open sets (request ids, prompts, trace ids, addresses)
    explode scrape cardinality at fleet scale — rule DLR013 rejects
    ``.labels(...)`` call sites whose values look prompt- or id-derived,
    so per-request detail rides EXEMPLARS and traces instead of labels."""

    # dominant cause classes the TailAttributor (serving/tail.py) assigns
    # to a slow-percentile request from its span tree
    TAIL_QUEUE = "queue"
    TAIL_PREFILL = "prefill"
    TAIL_BATCH_INTERFERENCE = "batch_interference"
    TAIL_SPECULATIVE_MISS = "speculative_miss"
    TAIL_PREFIX_MISS = "prefix_miss"
    TAIL_REROUTE = "reroute"
    TAIL_CAUSES = (
        TAIL_QUEUE, TAIL_PREFILL, TAIL_BATCH_INTERFERENCE,
        TAIL_SPECULATIVE_MISS, TAIL_PREFIX_MISS, TAIL_REROUTE,
    )
    # SLO burn windows (observability/slo.py)
    WINDOW_FAST = "fast"
    WINDOW_SLOW = "slow"
    # restore-ladder rung attribution (observability/incidents.py): the
    # rung that won a fault→recovery episode, as journaled by
    # ckpt/engine.py's restore_complete {medium} — plus "unknown" for an
    # incident whose window never saw a restore land
    RUNG_RESHARD = "reshard"
    RUNG_SHM = "shm"
    RUNG_CHAIN = "chain"
    RUNG_REPLICA = "replica"
    RUNG_STORAGE = "storage"
    RUNG_UNKNOWN = "unknown"
    RESTORE_RUNGS = (
        RUNG_RESHARD, RUNG_SHM, RUNG_CHAIN, RUNG_REPLICA, RUNG_STORAGE,
        RUNG_UNKNOWN,
    )
    # how restored array bytes reached their device (ckpt/engine.py
    # dlrover_ckpt_restore_bytes_total): through the staging ring, or put
    # from a host buffer of their own
    RESTORE_PATH_STAGED = "staged"
    RESTORE_PATH_DIRECT = "direct"
    RESTORE_PATHS = (RESTORE_PATH_STAGED, RESTORE_PATH_DIRECT)
    # checkpoint-commit triggers (ckpt/ckpt_saver.py → ckpt_committed
    # journal events): the cadence save, a membership-change/SIGTERM
    # breakpoint save, and the brain's predicted-failure pre-emptive save
    CKPT_TRIGGER_PERIODIC = "periodic"
    CKPT_TRIGGER_BREAKPOINT = "breakpoint"
    CKPT_TRIGGER_PREEMPTIVE = "preemptive"
    # device-memory ledger categories (observability/memory.py): every
    # byte the MemoryAccountant tracks is attributed to exactly one of
    # these; ``dlrover_memory_bytes{category}`` and the memory_pressure
    # journal payload draw from this vocabulary ONLY (the interproc half
    # of DLR013 certifies call sites against it)
    MEM_PARAMS = "params"
    MEM_OPT_STATE = "opt_state"
    MEM_ACTIVATIONS = "activations"
    MEM_KV_CACHE = "kv_cache"
    MEM_PREFIX_CACHE = "prefix_cache"
    MEM_STAGING = "staging"
    MEM_OTHER = "other"
    MEMORY_CATEGORIES = (
        MEM_PARAMS, MEM_OPT_STATE, MEM_ACTIVATIONS, MEM_KV_CACHE,
        MEM_PREFIX_CACHE, MEM_STAGING, MEM_OTHER,
    )
    # recompile-storm varying-dimension attribution (observability/
    # compile_watch.py): the signature axis whose churn explains a storm;
    # ``recompile_storm{dim}`` and ``dlrover_compile_storms_total{dim}``
    # draw from this vocabulary ONLY
    STORM_DIM_BATCH = "batch"
    STORM_DIM_SEQ_LEN = "seq_len"
    STORM_DIM_FN = "fn"
    STORM_DIM_DTYPE = "dtype"
    STORM_DIM_UNKNOWN = "unknown"
    STORM_DIMS = (
        STORM_DIM_BATCH, STORM_DIM_SEQ_LEN, STORM_DIM_FN, STORM_DIM_DTYPE,
        STORM_DIM_UNKNOWN,
    )


class GRPC:
    # retained name for familiarity; the transport is the typed msgpack RPC
    MAX_MESSAGE_BYTES = 512 * 1024 * 1024


class DefaultPort:
    MASTER = 0  # 0 → pick a free port
