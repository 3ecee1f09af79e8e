"""Elastic data plane: exactly-once shard ledger, worker client, chaos.

Layers under test (docs/design/elastic_data_plane.md):

- ledger algebra on :class:`TaskManager` with an injectable fake clock —
  lease/ack/requeue/steal idempotence, first-ack-wins, epoch boundary;
- chaos sites ``data.dispatch`` / ``data.report``: a dropped ack replays
  without double-counting, a dropped dispatch re-leases after expiry;
- mid-epoch restore through ``get_shard_checkpoint`` /
  ``restore_shard_checkpoint`` / ``export_data_state`` and the
  delta-chain ``data_state.json`` sidecar (ckpt/manifest.py);
- the worker-side :class:`DataShardClient` + :class:`PrefetchPipeline`;
- a ``race``-marked drill certifying the dispatch/ack/steal cycle under
  the happens-before detector;
- the full exactly-once drill (examples/data_exactly_once.py) as a
  subprocess: world cut + SIGKILL mid-epoch, restore from the chain,
  seeded content-hash audit.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from dlrover_tpu import chaos
from dlrover_tpu.common import comm
from dlrover_tpu.common.config import Context, get_context
from dlrover_tpu.master.task_manager import TaskManager
from dlrover_tpu.observability.journal import EventJournal, JournalEvent
from dlrover_tpu.trainer.data_plane import DataShardClient, PrefetchPipeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_slate():
    yield
    chaos.reset_injector()
    Context.reset()


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _params(name="ds", size=16, batch=2, minibatches=2):
    # shard size = batch * minibatches -> size/(batch*minibatches) shards
    return comm.DatasetShardParams(
        batch_size=batch,
        num_epochs=1,
        dataset_size=size,
        shuffle=False,
        num_minibatches_per_shard=minibatches,
        dataset_name=name,
        storage_type="",
        splitter="batch",
    )


def _ledger(clock=None, journal=None, **tm_kw):
    tm = TaskManager(monotonic=clock or FakeClock(), journal=journal,
                     **tm_kw)
    tm.new_dataset(_params())
    return tm


class _DirectClient:
    """MasterClient stand-in wired straight into a TaskManager — the
    subset DataShardClient uses, minus the RPC layer (which the e2e
    drill and the servicer tests cover)."""

    def __init__(self, tm: TaskManager, node_id: int = 0):
        self._tm = tm
        self._node_id = node_id

    def setup_dataset(self, params):
        self._tm.new_dataset(params)
        return True

    def get_task(self, dataset_name):
        return self._tm.get_task(self._node_id, dataset_name)

    def report_shard_acks(self, acks):
        c = self._tm.ack_batch(self._node_id, list(acks))
        return comm.ShardAckResponse(
            accepted=c["accepted"], duplicates=c["duplicates"],
            unknown=c["unknown"], released=c["released"],
            revoked=c["revoked"],
        )


# -- ledger algebra ----------------------------------------------------------


def test_lease_ack_drains_epoch_exactly_once():
    journal = EventJournal()
    tm = _ledger(journal=journal)
    seen = []
    while True:
        task = tm.get_task(0, "ds")
        if task is None:
            break
        seen.append(task.task_id)
        assert tm.ack_task("ds", task.task_id, 0, True) == "accepted"
    assert seen == [0, 1, 2, 3]  # 16 rows / (2*2) per shard
    assert tm.finished("ds")
    assert tm.completed_count("ds") == 4
    kinds = [e["kind"] for e in journal.events()]
    assert kinds.count(JournalEvent.DATA_DISPATCH) == 4
    assert kinds.count(JournalEvent.DATA_ACK) == 4
    assert JournalEvent.DATA_EPOCH_COMPLETE in kinds


def test_duplicate_ack_is_noop():
    tm = _ledger()
    task = tm.get_task(0, "ds")
    assert tm.ack_task("ds", task.task_id, 0, True) == "accepted"
    assert tm.ack_task("ds", task.task_id, 0, True) == "duplicate"
    # an ack replayed from a DIFFERENT node (stolen + both finished) is
    # equally a no-op — the acked set is the idempotence anchor
    assert tm.ack_task("ds", task.task_id, 7, True) == "duplicate"
    assert tm.completed_count("ds") == 1


def test_failure_ack_releases_lease_back_to_todo():
    tm = _ledger()
    task = tm.get_task(0, "ds")
    assert tm.ack_task("ds", task.task_id, 0, False) == "released"
    again = tm.get_task(1, "ds")
    assert again.task_id == task.task_id  # requeued at the FRONT
    assert tm.ack_task("ds", 99, 0, True) == "unknown"


def test_lease_expiry_requeues_on_master_clock():
    clock = FakeClock()
    journal = EventJournal()
    tm = _ledger(clock=clock, journal=journal)
    task = tm.get_task(0, "ds")
    assert tm.check_leases() == 0  # not expired yet
    clock.advance(get_context().shard_lease_timeout_s + 1.0)
    assert tm.check_leases() == 1
    again = tm.get_task(1, "ds")
    assert again.task_id == task.task_id
    requeues = [e for e in journal.events()
                if e["kind"] == JournalEvent.DATA_REQUEUE]
    assert requeues and requeues[0]["data"]["reason"] == "lease_expired"


def test_recover_tasks_requeues_only_dead_nodes_leases():
    journal = EventJournal()
    tm = _ledger(journal=journal)
    t_dead = tm.get_task(1, "ds")
    t_live = tm.get_task(2, "ds")
    tm.recover_tasks(1)
    # the dead node's shard is dispatchable again; the live lease is not
    redispatched = tm.get_task(3, "ds")
    assert redispatched.task_id == t_dead.task_id
    assert tm.ack_task("ds", t_live.task_id, 2, True) == "accepted"
    ev = [e for e in journal.events()
          if e["kind"] == JournalEvent.DATA_REQUEUE]
    assert ev[0]["data"]["reason"] == "node_dead"
    assert ev[0]["data"]["task_ids"] == [t_dead.task_id]


def test_first_ack_wins_after_steal_and_redispatch():
    clock = FakeClock()
    journal = EventJournal()
    tm = _ledger(clock=clock, journal=journal)
    t0 = tm.get_task(0, "ds")
    clock.advance(0.1)
    t1 = tm.get_task(0, "ds")
    stolen = tm.shed_node(0, bias=1)
    assert stolen == [t1.task_id]  # tail lease (newest) is shed
    assert tm.pending_revokes(0) == {"ds": [t1.task_id]}
    # wedged victim: the shortened grace deadline expires the lease
    clock.advance(get_context().shard_lease_timeout_s / 4.0 + 1.0)
    assert tm.check_leases() == 1
    t1b = tm.get_task(5, "ds")
    assert t1b.task_id == t1.task_id
    # the victim finishes anyway (it had started): FIRST ack wins...
    assert tm.ack_task("ds", t1.task_id, 0, True) == "accepted"
    # ...and the thief's late ack is a duplicate, not a double-train
    assert tm.ack_task("ds", t1.task_id, 5, True) == "duplicate"
    assert tm.ack_task("ds", t0.task_id, 0, True) == "accepted"
    assert tm.completed_count("ds") == 2
    kinds = [e["kind"] for e in journal.events()]
    assert JournalEvent.DATA_STEAL in kinds


def test_ack_pulls_requeued_copy_out_of_todo():
    clock = FakeClock()
    tm = _ledger(clock=clock)
    task = tm.get_task(0, "ds")
    clock.advance(get_context().shard_lease_timeout_s + 1.0)
    tm.check_leases()  # task sits requeued in TODO
    # the original holder's ack lands late but proves the work finished
    assert tm.ack_task("ds", task.task_id, 0, True) == "accepted"
    # nobody trains it again: the TODO copy is gone
    drained = []
    while True:
        t = tm.get_task(1, "ds")
        if t is None:
            break
        drained.append(t.task_id)
        tm.ack_task("ds", t.task_id, 1, True)
    assert task.task_id not in drained
    assert tm.completed_count("ds") == 4


def test_shed_node_keeps_at_least_one_lease_and_scales_with_bias():
    clock = FakeClock()
    tm = TaskManager(monotonic=clock)
    tm.new_dataset(_params(size=64))  # 16 shards
    leases = []
    for _ in range(8):
        leases.append(tm.get_task(0, "ds"))
        clock.advance(0.01)
    # bias=1 -> keep len>>1 = 4; bias=4 -> keep len>>4 -> floor of 1
    stolen = tm.shed_node(0, bias=1)
    assert len(stolen) == 4
    assert stolen == [t.task_id for t in leases[4:]]
    stolen2 = tm.shed_node(0, bias=4)  # repeat offender sheds harder
    assert len(tm.pending_revokes(0)["ds"]) == 7  # keeps only the oldest
    assert set(stolen2).isdisjoint(stolen)  # idempotent per lease
    assert tm.shed_node(0, bias=4) == []  # nothing new to mark
    # the victim releases a revoked lease cooperatively -> back to TODO
    tm.release_task("ds", stolen[0], 0)
    assert tm.get_task(3, "ds").task_id == stolen[0]


def test_straggler_history_bias_hook():
    clock = FakeClock()
    tm = TaskManager(monotonic=clock,
                     straggler_history=lambda: {0: 3})
    tm.new_dataset(_params(size=64))
    for _ in range(8):
        tm.get_task(0, "ds")
        clock.advance(0.01)
    stolen = tm.shed_straggler(0)
    assert len(stolen) == 7  # keep len>>3 = 1
    assert tm.shed_straggler(99) == []  # unknown node: nothing held


# -- chaos sites -------------------------------------------------------------


@pytest.mark.chaos
def test_dropped_ack_report_replays_without_double_count():
    tm = _ledger()
    client = DataShardClient(
        _DirectClient(tm), "ds", batch_size=2, dataset_size=16,
        flush_every=1,
    )
    chaos.configure("data.report:drop@nth=1", seed=7)
    task = client.next_task()
    # first flush drops on the wire: acks re-stage, nothing is lost
    assert client.complete(task) is None
    assert client.pending_acks() == 1
    assert tm.completed_count("ds") == 0
    # the replay lands and counts exactly once
    resp = client.flush()
    assert resp.accepted == 1 and resp.duplicates == 0
    assert client.pending_acks() == 0
    assert tm.completed_count("ds") == 1
    # a paranoid second replay of the same ack is a duplicate, not a
    # double count
    resp2 = tm.ack_batch(0, [comm.TaskResult(
        dataset_name="ds", task_id=task.task_id, node_id=0, success=True)])
    assert resp2["duplicates"] == 1
    assert tm.completed_count("ds") == 1


@pytest.mark.chaos
def test_dropped_dispatch_releases_after_timeout_no_double_lease():
    clock = FakeClock()
    tm = _ledger(clock=clock)
    chaos.configure("data.dispatch:drop@nth=1", seed=7)
    # the dispatch reply drops AFTER the lease is recorded: the worker
    # never sees task 0, but the ledger holds it leased (no double
    # dispatch to the next caller)
    with pytest.raises(chaos.InjectedFault):
        tm.get_task(0, "ds")
    assert tm.get_task(1, "ds").task_id == 1
    # expiry on the master clock returns the orphan to TODO
    clock.advance(get_context().shard_lease_timeout_s + 1.0)
    assert tm.check_leases() == 2  # both the orphan and node 1's lease
    ids = {tm.get_task(2, "ds").task_id, tm.get_task(2, "ds").task_id}
    assert 0 in ids  # the orphaned shard is dispatchable exactly once


# -- mid-epoch restore -------------------------------------------------------


def test_shard_checkpoint_roundtrip_preserves_acked_set():
    tm = _ledger()
    done = tm.get_task(0, "ds")
    tm.ack_task("ds", done.task_id, 0, True)
    tm.get_task(0, "ds")  # left in-flight at snapshot time
    snap = tm.get_shard_checkpoint("ds")

    journal = EventJournal()
    tm2 = TaskManager(monotonic=FakeClock(), journal=journal)
    tm2.new_dataset(_params())
    tm2.restore_shard_checkpoint(snap)
    # acked survives: a replayed ack for the pre-snapshot shard is a
    # duplicate, never a re-train
    assert tm2.ack_task("ds", done.task_id, 0, True) == "duplicate"
    # the in-flight lease came back as TODO; the remainder drains to a
    # full epoch without the acked shard ever re-dispatching
    drained = []
    while True:
        t = tm2.get_task(1, "ds")
        if t is None:
            break
        drained.append(t.task_id)
        tm2.ack_task("ds", t.task_id, 1, True)
    assert done.task_id not in drained
    assert sorted(drained + [done.task_id]) == [0, 1, 2, 3]
    assert tm2.finished("ds")
    kinds = [e["kind"] for e in journal.events()]
    assert JournalEvent.DATA_STATE_RESTORED in kinds


def test_export_import_data_state_registers_and_restores():
    tm = _ledger()
    t = tm.get_task(0, "ds")
    tm.ack_task("ds", t.task_id, 0, True)
    blob = tm.export_data_state()

    tm2 = TaskManager(monotonic=FakeClock())  # blank master post-cut
    tm2.import_data_state(blob)
    assert tm2.dataset_names() == ["ds"]
    assert tm2.ack_task("ds", t.task_id, 0, True) == "duplicate"
    tm2.import_data_state(blob)  # idempotent re-import
    assert tm2.dataset_names() == ["ds"]
    tm2.import_data_state("")  # empty sidecar: no-op


def test_manifest_data_state_sidecar_roundtrip(tmp_path):
    from dlrover_tpu.ckpt import manifest

    ckpt_dir = str(tmp_path)
    assert manifest.read_data_state(ckpt_dir, 5) is None
    manifest.write_data_state(ckpt_dir, 5, '{"v": 1}')
    assert manifest.read_data_state(ckpt_dir, 5) == '{"v": 1}'
    assert os.path.basename(
        manifest.data_state_file(ckpt_dir, 5)) == "data_state.json"


# -- worker client + prefetch ------------------------------------------------


def test_prefetch_pipeline_trains_each_shard_once_with_bounded_queue():
    tm = TaskManager(monotonic=FakeClock())
    client = DataShardClient(
        _DirectClient(tm), "ds", batch_size=2, dataset_size=32,
        flush_every=2,
    )
    loaded = []

    def loader(task):
        loaded.append(task.task_id)
        return list(range(task.shard.start, task.shard.end))

    pipe = PrefetchPipeline(client, loader, depth=2)
    rows = []
    try:
        for task, payload in pipe:
            assert pipe.occupancy() <= 2
            rows.extend(payload)
            client.complete(task)
    finally:
        pipe.stop()
    client.drain()
    assert sorted(rows) == list(range(32))
    assert sorted(loaded) == list(range(8))  # each shard loaded once
    assert tm.completed_count("ds") == 8
    assert tm.finished("ds")


def test_client_releases_revoked_lease_before_training():
    clock = FakeClock()
    tm = TaskManager(monotonic=clock)
    client = DataShardClient(
        _DirectClient(tm, node_id=0), "ds", batch_size=2, dataset_size=32,
        flush_every=1,
    )
    a = client.next_task()
    clock.advance(0.01)
    b = client.next_task()
    tm.shed_node(0, bias=1)  # master wants the tail lease back
    client.complete(a)  # flush reply piggybacks the revoke list
    assert client.is_revoked(b)
    assert not client.is_revoked(a)
    client.release(b)  # cooperative give-back
    assert tm.get_task(1, "ds").task_id == b.task_id


# -- race certification ------------------------------------------------------


@pytest.mark.race
def test_dispatch_ack_steal_cycle_is_race_free(race_guard):
    """The ledger's shared maps (todo/doing/acked) under the
    happens-before detector while four planes hammer it concurrently:
    workers leasing+acking, the stealer shedding, the death path
    requeueing, and the lease monitor expiring."""
    clock = FakeClock()
    tm = TaskManager(monotonic=clock)
    tm.new_dataset(_params(size=256))  # 64 shards
    assert race_guard.tracked_created > 0, (
        "shared() registration never engaged — the drill certifies "
        "nothing"
    )
    stop = threading.Event()

    def worker(node_id):
        while not stop.is_set():
            task = tm.get_task(node_id, "ds")
            if task is None:
                if tm.finished("ds"):
                    return
                time.sleep(0.001)
                continue
            if node_id == 1:  # one slow rank: holds leases, acks late
                time.sleep(0.003)
            tm.ack_batch(node_id, [comm.TaskResult(
                dataset_name="ds", task_id=task.task_id,
                node_id=node_id, success=True)])

    def stealer():
        while not stop.is_set():
            tm.shed_node(1, bias=1)
            tm.pending_revokes(1)
            time.sleep(0.002)

    def reaper():
        while not stop.is_set():
            tm.recover_tasks(3)  # node 3 keeps "dying"
            clock.advance(0.5)
            tm.check_leases()
            tm.get_shard_checkpoint("ds")
            time.sleep(0.002)

    threads = [threading.Thread(target=worker, args=(n,))
               for n in range(4)]
    threads += [threading.Thread(target=stealer),
                threading.Thread(target=reaper)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 20.0
    while not tm.finished("ds") and time.monotonic() < deadline:
        time.sleep(0.01)
    stop.set()
    for t in threads:
        t.join(5.0)
    assert tm.finished("ds"), "drill never drained the epoch"
    assert tm.completed_count("ds") == 64
    assert race_guard.races == [], race_guard.report()


# -- full exactly-once drill (subprocess e2e) --------------------------------


def test_exactly_once_drill_world_cut_sigkill_restore():
    """examples/data_exactly_once.py: worker checkpoints mid-epoch with
    the ledger sidecar in the chain, a wedged victim's leases are stolen
    then SIGKILLed, the world is cut, a fresh master+worker restore from
    the chain and drain — and the seeded per-sample content hash proves
    every sample trained exactly once on the committed stream."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "data_exactly_once.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["committed_total"] == result["dataset_size"] == 64
    assert result["dropped"] == []
    assert result["duplicated"] == []
    assert result["hash_ok"] is True
    # world A journaled the steal and the death-path requeue
    assert result["journal_a_steal"] >= 1
    assert result["journal_a_requeue"] >= 1
    assert "node_dead" in result["requeue_reasons"]
    # world B restored the ledger from the chain and finished the epoch
    assert result["journal_b_restored"] >= 1
    assert result["journal_b_epoch_complete"] >= 1
    # the victim held live leases when it was killed (the drill is real)
    assert result["victim_leases"]
    assert result["stolen"]
