"""Agent-side async checkpoint saver: shm → storage, commit, breakpoint saves.

Reference: dlrover/python/elastic_agent/torch/ckpt_saver.py —
``AsyncCheckpointSaver``:399 (daemon threads consuming a SharedQueue),
``CommonDirCheckpointSaver.save_step_checkpoint``:925 (threadpool per-shard
persist), ``commit_checkpoint``:992 (done-files + tracker), signal-handler
persistence on SIGTERM (:533), ``save_shm_to_storage``:758 (breakpoint save).

The reference needs a saver subclass per torch framework (DDP/Megatron/
DeepSpeed/FSDP-DCP, :1266–1314) because each lays out shards differently;
here the jax engine writes one self-describing frame per worker process, so
a single saver persists them all — shard semantics live in the frame meta
(NamedSharding start indices), not in the saver.

Disk layout per checkpoint::

    <ckpt_dir>/latest_step.txt                      # tracker (commit marker)
    <ckpt_dir>/step_00000042/frame_<node>_<local>.dlrover
    <ckpt_dir>/step_00000042/._done/done_<node>_<local>
"""

import os
import queue
import signal
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from dlrover_tpu.analysis.race_detector import shared
from dlrover_tpu.common.config import get_context
from dlrover_tpu.common.constants import (
    CheckpointConstant,
    MetricLabel,
    SharedResourceName,
    SpanName,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.registry import get_registry
from dlrover_tpu.common.storage import (
    CheckpointDeletionStrategy,
    CheckpointStorage,
    PosixDiskStorage,  # noqa: F401 — re-exported for callers
    get_checkpoint_storage,
)
from dlrover_tpu.ckpt import manifest
from dlrover_tpu.ckpt.manifest import (  # noqa: F401 — canonical layout
    frame_file,
    step_dir,
)
from dlrover_tpu.ckpt.shm_handler import SharedMemoryHandler, parse_frame


def latest_step(ckpt_dir: str, storage: Optional[CheckpointStorage] = None) -> int:
    storage = storage or get_checkpoint_storage(ckpt_dir)
    tracker = os.path.join(ckpt_dir, CheckpointConstant.TRACKER_FILE)
    content = storage.read(tracker, "r")
    if not content:
        return -1
    try:
        return int(str(content).strip())
    except ValueError:
        return -1


def load_frames_for_step(
    ckpt_dir: str, step: int, storage: Optional[CheckpointStorage] = None
) -> List[Dict]:
    storage = storage or get_checkpoint_storage(ckpt_dir)
    d = step_dir(ckpt_dir, step)
    if manifest.manifest_links(ckpt_dir, step, storage):
        # chain layout: reconstruct through the manifest links (delta
        # shards resolve into ancestor steps' payload files). A chain that
        # fails verification yields NOTHING — the loose .dlrover files in
        # a chain-format dir are unverified payloads, not fallbacks.
        try:
            return manifest.load_step_frames(ckpt_dir, step, storage)
        except manifest.ChainError as e:
            logger.error(
                "manifest chain for step %s unusable (%s)", step, e.reason
            )
            return []
    frames = []
    for name in storage.listdir(d):
        if not name.endswith(".dlrover"):
            continue
        blob = storage.read(os.path.join(d, name))
        if blob is None:
            continue
        meta = parse_frame(blob)
        if meta is not None:
            frames.append(meta)
    return frames


def merge_frame_leaves(frames):
    """Merge frames' leaf metas into {path: meta with all shards}; each
    shard entry carries its source frame under ``_frame`` (used by both
    the engine's storage restore and the orbax export)."""
    merged = {}
    for frame in frames:
        for leaf in frame["leaves"]:
            entry = merged.setdefault(
                leaf["path"],
                {**{k: v for k, v in leaf.items() if k != "shards"},
                 "shards": []},
            )
            entry["shards"].extend(
                dict(sh, _frame=frame) for sh in leaf.get("shards", [])
            )
    return merged


def persist_shm_frame(
    shm: SharedMemoryHandler,
    ckpt_dir: str,
    step: int,
    storage: Optional[CheckpointStorage] = None,
) -> bool:
    """Persist one shm frame as a manifest chain link (used directly by
    agent-less workers — same on-disk format as the agent saver)."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    meta = shm.read_meta()
    if meta is None or meta["step"] != step:
        return False
    blob = shm.read_frame_bytes()
    if blob is None:
        return False
    # prev_state=None: the chain tip is re-seeded from the on-disk
    # manifests, so restarted single-process jobs still write deltas
    pool = ThreadPoolExecutor(
        max_workers=get_context().ckpt_save_workers,
        thread_name_prefix="ckpt-persist",
    )
    try:
        manifest.persist_frame(
            storage, ckpt_dir, step, meta, blob, executor=pool
        )
    finally:
        pool.shutdown(wait=False)
    # agent-less path commits immediately (single process owns the dir)
    tracker = os.path.join(ckpt_dir, CheckpointConstant.TRACKER_FILE)
    manifest.commit_file(storage, str(step), tracker, step=step)
    return True


class AsyncCheckpointSaver:
    """Agent-process daemon that persists worker shm frames.

    ``expected_frames`` is the number of frames a committed checkpoint must
    contain across all hosts (world_size of worker processes); the
    lowest-node-rank agent commits once the done-dir fills (reference
    ``commit_checkpoint``:992 polls the same way).
    """

    _instance: Optional["AsyncCheckpointSaver"] = None

    def __init__(
        self,
        ckpt_dir: str = "",
        storage: Optional[CheckpointStorage] = None,
        node_rank: int = 0,
        local_world_size: int = 1,
        expected_frames: Optional[int] = None,
        is_commit_leader: Optional[bool] = None,
        deletion_strategy: Optional[CheckpointDeletionStrategy] = None,
    ):
        self.ckpt_dir = ckpt_dir
        # path-aware default: gs:// checkpoint dirs get the GCS backend
        self._storage = storage or get_checkpoint_storage(ckpt_dir)
        self._node_rank = node_rank
        self._local_world_size = local_world_size
        self._expected_frames = expected_frames or local_world_size
        self._is_commit_leader = (
            (node_rank == 0) if is_commit_leader is None else is_commit_leader
        )
        self._deletion_strategy = deletion_strategy
        self._ipc_server = None
        self._stopped = threading.Event()
        self._consumer: Optional[threading.Thread] = None
        self._executor = ThreadPoolExecutor(
            max_workers=get_context().ckpt_save_workers,
            thread_name_prefix="ckpt-persist",
        )
        # shm frame name → last persisted step; the "ckpt-saver" consumer
        # thread and bp-commit threads meet here — registered with the
        # race detector, accessed only under _lock
        self._persisted_steps: Dict[str, int] = shared(
            {}, "AsyncCheckpointSaver._persisted_steps")
        # (path, frame) → chain tip state from the last committed link:
        # per-shard digests (the delta decision) + resolved shard map.
        # Same thread-crossing as _persisted_steps — registered with the
        # race detector, accessed only under _lock
        self._chain_state: Dict[str, Dict] = shared(
            {}, "AsyncCheckpointSaver._chain_state")
        reg = get_registry()
        self._persist_bytes = reg.counter(
            "dlrover_ckpt_persist_bytes_total",
            "Checkpoint payload bytes persisted to storage",
            labelnames=("kind",),
        )
        self._persist_frames = reg.counter(
            "dlrover_ckpt_persist_frames_total",
            "Checkpoint frame links committed", labelnames=("kind",),
        )
        self._lock = threading.Lock()
        # serializes tracker check+write across the event thread and any
        # async breakpoint-commit threads (the monotonic check is useless
        # if two commits interleave between check and move)
        self._commit_lock = threading.Lock()
        # best-effort commit telemetry: the agent wires this to its
        # master client so every tracker move lands in the journal as
        # ckpt_committed {step, trigger, frames} — the incident
        # stitcher's counterfactual line scores the brain's pre-emptive
        # saves against the last periodic commit from these records
        self._reporter = None
        AsyncCheckpointSaver._instance = self

    def set_reporter(self, fn) -> None:
        """``fn(kind: str, data: dict)`` — typically the agent's
        ``client.report_event``; commit telemetry must never block or
        fail a commit, so calls are wrapped."""
        self._reporter = fn

    def _report_commit(self, step: int, trigger: str, frames: int) -> None:
        if self._reporter is None:
            return
        from dlrover_tpu.observability.journal import JournalEvent

        try:
            self._reporter(
                JournalEvent.CKPT_COMMITTED,
                {"step": step, "trigger": trigger, "frames": frames},
            )
        except Exception:  # noqa: BLE001 — telemetry only
            logger.warning("ckpt_committed report failed", exc_info=True)

    # -- lifecycle ---------------------------------------------------------

    def update_world(
        self, node_rank: int, expected_frames: int, is_commit_leader: bool
    ) -> None:
        """Called by the agent after every rendezvous: the commit quorum is
        a property of the *current* world, not of launch-time config."""
        self._node_rank = node_rank
        self._expected_frames = max(1, expected_frames)
        self._is_commit_leader = is_commit_leader
        logger.info(
            "ckpt saver world update: node_rank=%s expected_frames=%s "
            "commit_leader=%s", node_rank, expected_frames, is_commit_leader,
        )

    def start(self, ipc_server) -> None:
        self._ipc_server = ipc_server
        self._consumer = threading.Thread(
            target=self._consume_events, name="ckpt-saver", daemon=True
        )
        self._consumer.start()

    def stop(self) -> None:
        self._stopped.set()
        self._executor.shutdown(wait=False)

    def install_signal_handlers(self) -> None:
        """Persist shm on SIGTERM before dying (reference ckpt_saver.py:533).
        Call from the agent main thread only."""
        prev_term = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            logger.info("SIGTERM: persisting in-memory checkpoints")
            try:
                self.save_shm_to_storage(reason="sigterm")
            finally:
                if callable(prev_term):
                    prev_term(signum, frame)
                else:
                    raise SystemExit(143)

        signal.signal(signal.SIGTERM, _on_term)

    # -- event loop --------------------------------------------------------

    def _consume_events(self) -> None:
        q = self._ipc_server.local_queue(SharedResourceName.SAVE_EVENT_QUEUE)
        while not self._stopped.is_set():
            try:
                event = q.get(timeout=0.5)
            except queue.Empty:
                continue
            try:
                self._handle_save_event(event)
            except Exception:  # noqa: BLE001
                logger.exception("checkpoint persist failed: %s", event)

    def _local_shm_handlers(self) -> List[SharedMemoryHandler]:
        """Worker shm segments registered in the meta dict."""
        handlers = []
        if self._ipc_server is None:
            return handlers
        meta = self._ipc_server.local_dict(SharedResourceName.SHM_META_DICT)
        for info in dict(meta).values():
            handlers.append(SharedMemoryHandler(info["shm"]))
        return handlers

    def _handle_save_event(self, event: Dict) -> None:
        step = event["step"]
        path = event.get("path") or self.ckpt_dir
        if not path:
            logger.warning("save event without a checkpoint dir — dropped")
            return
        # the worker engine stamped its trace context onto the event
        # (engine.save_to_storage): restore it so the persist/commit spans
        # join the save_to_storage trace across the SharedQueue boundary
        carried = tracing.extract_wire(event.get(tracing.WIRE_KEY))
        with tracing.activate(carried):
            self.save_step_checkpoint(step, path)

    def save_step_checkpoint(self, step: int, path: str) -> None:
        """Persist every local frame for ``step``, then commit
        (reference ``save_step_checkpoint``:925)."""
        with tracing.span(
            SpanName.CKPT_PERSIST, source=f"saver_{self._node_rank}",
            step=step,
        ) as sp:
            handlers = self._local_shm_handlers()
            # frames persist sequentially; the parallelism lives INSIDE
            # each persist (stripe fan-out over self._executor in
            # manifest.persist_frame). Submitting frames to the same pool
            # their stripes need would deadlock it.
            persisted = [
                shm for shm in handlers
                if self._persist_one(shm, path, step)
            ]
            sp.add_event("persisted", frames=len(persisted),
                         handlers=len(handlers))
            if not persisted:
                logger.warning(
                    "no shm frame matched step %s — nothing persisted", step
                )
                return
            # done markers ONLY for frames that really landed — a skipped
            # or stale frame must hold the commit quorum open
            self._write_done_files(path, step, persisted)
            if self._is_commit_leader:
                # quorum size rides in the frame meta (engine._plan_state):
                # a single-writer job's commit must wait for its one frame,
                # not one per host
                meta = persisted[0].read_meta() or {}
                self._traced_commit(
                    tracing.current_context(), path, step,
                    expected_frames=meta.get("expected_frames"),
                )

    def _traced_commit(self, ctx, path: str, step: int, **kwargs) -> None:
        """``commit_checkpoint`` under a ``ckpt.commit`` span in the trace
        of ``ctx`` (captured before a commit thread starts)."""
        with tracing.activate(ctx), tracing.span(
            SpanName.CKPT_COMMIT, source=f"saver_{self._node_rank}",
            step=step,
        ):
            self.commit_checkpoint(path, step, **kwargs)

    def _frame_lock(self, shm: SharedMemoryHandler):
        """The per-frame lock the worker writes under — the agent takes it
        while copying shm out so a concurrent save can't tear the frame."""
        from dlrover_tpu.common.multi_process import SharedLock

        if self._ipc_server is None:
            return None
        return SharedLock(shm.name + ".lock", self._ipc_server.path)

    def _persist_one(
        self, shm: SharedMemoryHandler, path: str, step: int,
        lock_timeout: float = CheckpointConstant.SAVE_TIMEOUT_S,
    ) -> int:
        """Persist ``shm``'s frame of ``step`` as a chain link. Returns the
        frame's bytes, 0 where nothing was persisted."""
        source = f"saver_{self._node_rank}"
        lock = self._frame_lock(shm)
        # opened before the lock is asked for: the wait is the read's
        read = tracing.span(SpanName.CKPT_PERSIST_READ, source=source)
        if lock is not None and not lock.acquire(timeout=lock_timeout):
            read.end(status="error")
            logger.warning(
                "could not take frame lock for %s in %.0fs — skipping to "
                "avoid a torn read", shm.name, lock_timeout,
            )
            return 0
        try:
            with read:
                meta = shm.read_meta()
                if meta is None or meta["step"] != step:
                    return 0
                blob = shm.read_frame_bytes()
                if blob is None:
                    return 0
                read.attrs["bytes"] = len(blob)
            # never persist bytes that already fail their shard CRCs: a
            # corrupt frame on disk outlives the replica copies that could
            # repair it (restore-time checks would only catch it later,
            # after the good copies are gone)
            with tracing.span(
                SpanName.CKPT_PERSIST_VERIFY, source=source,
                shards=sum(len(leaf.get("shards", []))
                           for leaf in meta["leaves"]),
            ) as verify:
                bad = shm.verify_frame()
                verify.attrs["bad"] = len(bad)
            if bad:
                logger.error(
                    "refusing to persist %s step %s: corrupt shard(s) %s",
                    shm.name, step, bad,
                )
                return 0
        finally:
            if lock is not None:
                lock.release()
        chain_key = f"{path}|{shm.name}"
        with self._lock:
            prev = self._chain_state.get(chain_key)
        try:
            with tracing.span(
                SpanName.CKPT_PERSIST_WRITE, source=source,
            ) as write:
                state = manifest.persist_frame(
                    self._storage, path, step, meta, blob,
                    prev_state=prev, executor=self._executor,
                )
                write.attrs.update(bytes_written=state["bytes_written"],
                                   kind=state["kind"])
        except Exception:  # noqa: BLE001 — a failed persist holds the quorum open
            logger.exception(
                "persist of %s for step %s failed — no done marker, the "
                "commit quorum stays open", shm.name, step,
            )
            return 0
        with self._lock:
            self._chain_state[chain_key] = state
            # a frame counts as "persisted at step N" only once its
            # manifest link committed — payload files alone are invisible
            # to restore, so the breakpoint-save skip must not trust them
            self._persisted_steps[shm.name] = step
        self._persist_bytes.labels(kind=state["kind"]).inc(
            state["bytes_written"])
        self._persist_frames.labels(kind=state["kind"]).inc()
        return len(blob)

    def _write_done_files(
        self, path: str, step: int, handlers: List[SharedMemoryHandler]
    ) -> None:
        done_dir = os.path.join(step_dir(path, step), CheckpointConstant.DONE_DIR)
        self._storage.safe_makedirs(done_dir)
        for shm in handlers:
            meta = shm.read_meta()
            if meta is None:
                continue
            done = os.path.join(
                done_dir, f"done_{meta['node_rank']}_{meta['local_rank']}"
            )
            self._storage.write("1", done)

    def commit_checkpoint(
        self, path: str, step: int, timeout_s: Optional[float] = None,
        expected_frames: Optional[int] = None,
        trigger: str = MetricLabel.CKPT_TRIGGER_PERIODIC,
    ) -> bool:
        """Wait for all expected done files, then move the tracker
        (reference ``commit_checkpoint``:992). ``expected_frames``
        overrides the world-derived default — the saver group's size as
        recorded in the frame meta (a single-writer job commits on ONE
        frame regardless of world size). ``trigger`` names what caused
        the save (MetricLabel.CKPT_TRIGGER_*) and rides the journaled
        ckpt_committed record."""
        timeout_s = timeout_s or CheckpointConstant.SAVE_TIMEOUT_S
        expected = expected_frames or self._expected_frames
        done_dir = os.path.join(step_dir(path, step), CheckpointConstant.DONE_DIR)
        poll = get_context().ckpt_commit_poll_s
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            count = len([
                f for f in self._storage.listdir(done_dir)
                if f.startswith("done_")
            ])
            if count >= expected:
                # monotonic: a late commit (e.g. an async breakpoint
                # commit whose quorum filled after training resumed and
                # committed a NEWER step) must never move the restore
                # point backwards. The lock makes check+write atomic and
                # the per-step tmp name keeps concurrent commits from
                # moving each other's payloads.
                with self._commit_lock:
                    if latest_step(path, self._storage) >= step:
                        logger.info(
                            "checkpoint step %s superseded — tracker kept",
                            step,
                        )
                        return True
                    tracker = os.path.join(
                        path, CheckpointConstant.TRACKER_FILE
                    )
                    tmp = f"{tracker}.tmp{step}"
                    self._storage.write(str(step), tmp)
                    self._storage.safe_move(tmp, tracker)
                logger.info("checkpoint step %s committed (%s frames)",
                            step, count)
                self._report_commit(step, trigger, count)
                if self._deletion_strategy is not None:
                    # chain-aware GC: never collects a link on a live
                    # tip's digest walk or a payload a live link resolves
                    # into (a delta step keeps its base reachable)
                    self._deletion_strategy.clean_up(
                        step,
                        lambda s: manifest.gc_step(self._storage, path, s),
                    )
                return True
            if self._stopped.is_set():
                return False
            time.sleep(poll)
        logger.error("checkpoint step %s commit timed out", step)
        return False

    # -- breakpoint saves --------------------------------------------------

    def save_shm_to_storage(
        self, reason: str = "", workers_dead: bool = False,
        async_commit: bool = False,
        trigger: str = MetricLabel.CKPT_TRIGGER_BREAKPOINT,
    ) -> int:
        """Persist any shm frame newer than what's on disk — called when
        workers fail, membership changes, or the agent gets SIGTERM
        (reference ``save_shm_to_storage``:758). Returns #frames persisted.

        ``workers_dead=True`` force-releases frame locks first: a worker
        that died mid-save can never release its lock itself.
        ``async_commit=True`` runs the leader's commit-quorum wait on a
        background thread: a restart triggered by a DEAD peer must not
        block re-rendezvous for the full quorum timeout (the peer's frame
        is never coming; if agents are merely restarting, their saves land
        and the background commit succeeds). SIGTERM saves stay
        synchronous — the process is about to die."""
        if not self.ckpt_dir:
            return 0
        with tracing.span(
            SpanName.CKPT_PERSIST, source=f"saver_{self._node_rank}",
            trigger=trigger,
        ) as sp:
            persisted, nbytes = 0, 0
            handlers = self._local_shm_handlers()
            steps = set()
            for shm in handlers:
                if workers_dead:
                    lock = self._frame_lock(shm)
                    if lock is not None:
                        lock.release()
                meta = shm.read_meta()
                if meta is None and not workers_dead:
                    # the worker's async drain may still be landing the
                    # frame (engine.py save_to_memory holds the frame lock
                    # until the shm write completes) — wait for it, then
                    # re-read
                    lock = self._frame_lock(shm)
                    if lock is not None and lock.acquire(timeout=10.0):
                        lock.release()
                        meta = shm.read_meta()
                if meta is None:
                    continue
                step = meta["step"]
                with self._lock:
                    already = self._persisted_steps.get(shm.name, -1)
                if step <= already:
                    continue
                frame_bytes = self._persist_one(shm, self.ckpt_dir, step,
                                                lock_timeout=10.0)
                if frame_bytes:
                    persisted += 1
                    nbytes += frame_bytes
                    steps.add(step)
            for step in steps:
                done = [
                    h for h in handlers
                    if (m := h.read_meta()) is not None and m["step"] == step
                ]
                self._write_done_files(self.ckpt_dir, step, done)
                # commit still demands the full-world quorum: on a
                # membership change every agent breakpoint-saves, so the
                # done-dir fills and the leader's wait succeeds; a lone
                # host's partial save leaves the tracker untouched (correct
                # — a partial step must never become the restore point).
                if self._is_commit_leader:
                    kwargs = {"timeout_s": 30.0, "trigger": trigger}
                    if async_commit:
                        # its ckpt.commit joins this persist's trace
                        threading.Thread(
                            target=self._traced_commit,
                            args=(tracing.current_context(), self.ckpt_dir,
                                  step),
                            kwargs=kwargs, name=f"bp-commit-{step}",
                            daemon=True,
                        ).start()
                    else:
                        self._traced_commit(tracing.current_context(),
                                            self.ckpt_dir, step, **kwargs)
            if persisted:
                # benchmarks/harness/resume_path.py PERSISTED parses this
                # line for resume.persist_s: keep it word for word
                logger.info(
                    "breakpoint save (%s): persisted %s frame(s) to %s",
                    reason, persisted, self.ckpt_dir,
                )
            sp.attrs.update(step=max(steps, default=-1), frames=persisted,
                            bytes=nbytes)
        return persisted

    @classmethod
    def get_instance(cls) -> Optional["AsyncCheckpointSaver"]:
        return cls._instance
