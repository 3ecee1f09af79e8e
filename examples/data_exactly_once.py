"""Exactly-once data plane chaos drill (elastic data plane, ISSUE 11).

The proof behind docs/design/elastic_data_plane.md: cut the world
mid-epoch — a worker SIGKILLed while HOLDING live shard leases, the
master torn down and replaced — restore from the delta-chain checkpoint
(model + ``data_state.json`` ledger sidecar), finish the epoch, and
audit with a seeded per-sample content hash that every sample was
COMMITTED exactly once: zero dropped, zero duplicated.

Cast (all real processes; the parent runs the masters in-process):

- master A — the first world. Its journal must record DATA_STEAL (the
  victim is shed as a straggler) and DATA_REQUEUE (the SIGKILL's
  conn-drop detection requeues the victim's leases).
- W0 "ckpt"  — trains shards with synchronous per-shard acks, then runs
  a REAL CheckpointEngine.save_to_storage (delta chain + ledger
  sidecar) and exits: the last durable lineage of world A.
- W1 "victim" — takes two leases, trains ONE without ever acking, then
  wedges (heartbeating only). SIGKILLed holding both leases. Its
  trained-but-unacked shard is the rolled-back work the audit must see
  retrained (trained twice, committed once).
- master B — a brand-new master after the cut. Knows nothing until the
  restore pushes the ledger into it.
- W2 "restore" — engine.load() from the chain (restores the model AND
  imports the sidecar into master B), then drains the rest of the
  epoch. Master B's journal must record DATA_STATE_RESTORED and
  DATA_EPOCH_COMPLETE.

Run: ``python examples/data_exactly_once.py`` → last stdout line is the
audit JSON (consumed by tests/test_data_plane.py).
"""

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DATASET = "drill"
DATASET_SIZE = 64
BATCH_SIZE = 4
MINIBATCHES_PER_SHARD = 2  # shard = 8 samples → 8 shards
SEED = 20260805
CKPT_STEP = 7


def sample_hash(idx: int) -> str:
    """The seeded per-sample content hash: training sample ``idx`` IS
    computing this (both worlds must agree bit-for-bit)."""
    return hashlib.sha256(f"{SEED}:{idx}".encode()).hexdigest()[:16]


def _log(path: str, record: dict) -> None:
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(record) + "\n")
        f.flush()
        os.fsync(f.fileno())


def _read_log(path: str):
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def _mk_client(node_id: int):
    from dlrover_tpu.agent.master_client import MasterClient

    return MasterClient(os.environ["DRILL_MASTER_ADDR"], node_id=node_id)


def _mk_shard_client(mc):
    from dlrover_tpu.trainer.data_plane import DataShardClient

    return DataShardClient(
        mc, DATASET, batch_size=BATCH_SIZE, dataset_size=DATASET_SIZE,
        num_minibatches_per_shard=MINIBATCHES_PER_SHARD, flush_every=1,
    )


def _train_shard(task, trained_log: str, who: str) -> list:
    samples = []
    for idx in range(task.shard.start, task.shard.end):
        samples.append({"idx": idx, "hash": sample_hash(idx)})
    _log(trained_log, {"who": who, "task_id": task.task_id,
                       "samples": samples})
    return samples


def _commit(resp, task, samples, committed_log: str, who: str) -> None:
    if resp is None:
        raise RuntimeError(f"ack flush failed for task {task.task_id}")
    if resp.accepted < 1:
        raise RuntimeError(
            f"task {task.task_id} ack not accepted: {resp!r}")
    _log(committed_log, {"who": who, "task_id": task.task_id,
                         "samples": samples})


def _mk_engine(mc, ckpt_dir: str, rank: int = 0):
    from dlrover_tpu.ckpt.engine import CheckpointEngine

    return CheckpointEngine(
        ckpt_dir, job_name="exactly-once", node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=rank,
        master_client=mc,
    )


def worker_ckpt(workdir: str) -> int:
    """Train 3 shards with per-shard sync acks, checkpoint, exit."""
    import jax.numpy as jnp

    from dlrover_tpu.ckpt import manifest

    mc = _mk_client(0)
    mc.heartbeat()
    client = _mk_shard_client(mc)
    trained = os.path.join(workdir, "w0.trained.log")
    committed = os.path.join(workdir, "w0.committed.log")
    for _ in range(3):
        task = client.next_task()
        assert task is not None, "dataset exhausted too early"
        samples = _train_shard(task, trained, "w0")
        _commit(client.complete(task), task, samples, committed, "w0")
    ckpt_dir = os.path.join(workdir, "ckpt")
    engine = _mk_engine(mc, ckpt_dir)
    state = {"w": jnp.full((8, 8), float(CKPT_STEP), dtype=jnp.float32)}
    ok = engine.save_to_storage(CKPT_STEP, state)
    assert ok, "save_to_storage failed"
    deadline = time.time() + 30
    sidecar = manifest.data_state_file(ckpt_dir, CKPT_STEP)
    while time.time() < deadline:
        if (manifest.newest_candidate_step(ckpt_dir) == CKPT_STEP
                and os.path.exists(sidecar)):
            break
        time.sleep(0.1)
    assert os.path.exists(sidecar), "ledger sidecar never landed"
    _log(os.path.join(workdir, "w0.done"), {"ok": True})
    return 0


def worker_victim(workdir: str) -> int:
    """Take two leases, train one WITHOUT acking, wedge until SIGKILL."""
    mc = _mk_client(1)
    mc.heartbeat()
    client = _mk_shard_client(mc)
    t_a = client.next_task()
    t_b = client.next_task()
    assert t_a is not None and t_b is not None
    _train_shard(t_a, os.path.join(workdir, "w1.trained.log"), "w1")
    _log(os.path.join(workdir, "w1.leases.json"),
         {"task_ids": [t_a.task_id, t_b.task_id]})
    while True:  # wedged: alive on the liveness plane, never acking
        mc.heartbeat()
        time.sleep(0.1)


def worker_restore(workdir: str) -> int:
    """Restore model+ledger from the chain into master B, drain epoch."""
    import numpy as np
    import jax.numpy as jnp

    mc = _mk_client(2)
    mc.heartbeat()
    ckpt_dir = os.path.join(workdir, "ckpt")
    # a world cut lands the restore on a fresh host: the dead worker's
    # shm frame does not survive, so load MUST walk the delta chain
    # (which is where the data-state sidecar import happens)
    from dlrover_tpu.ckpt.shm_handler import shm_name
    from dlrover_tpu.common.multi_process import unlink_shared_memory

    unlink_shared_memory(shm_name("exactly-once", 0, 0))
    engine = _mk_engine(mc, ckpt_dir)
    target = {"w": jnp.zeros((8, 8), dtype=jnp.float32)}
    state, step = engine.load(target)
    assert step == CKPT_STEP, f"restored step {step} != {CKPT_STEP}"
    assert float(np.asarray(state["w"])[0, 0]) == float(CKPT_STEP)
    client = _mk_shard_client(mc)  # setup_dataset idempotent post-import
    trained = os.path.join(workdir, "w2.trained.log")
    committed = os.path.join(workdir, "w2.committed.log")
    while True:
        task = client.next_task()
        if task is None:
            break
        samples = _train_shard(task, trained, "w2")
        _commit(client.complete(task), task, samples, committed, "w2")
    _log(os.path.join(workdir, "w2.done"), {"ok": True, "step": step})
    return 0


# -- parent orchestration ---------------------------------------------------


def _spawn(role: str, workdir: str, master_addr: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DRILL_MASTER_ADDR=master_addr)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--worker", role, "--workdir", workdir],
        env=env, cwd=REPO,
    )


def _wait_file(path: str, timeout_s: float = 60.0) -> None:
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if os.path.exists(path):
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {path}")


def _journal_kinds(master):
    return [e["kind"] for e in master.event_journal.events()]


def _committed_samples(path: str):
    out = {}
    for rec in _read_log(path):
        for s in rec["samples"]:
            out[s["idx"]] = s["hash"]
    return out


def run_drill(workdir: str) -> dict:
    from dlrover_tpu.common.config import get_context
    from dlrover_tpu.master.master import LocalJobMaster
    from dlrover_tpu.observability.journal import JournalEvent

    get_context().set("conn_drop_grace_s", 0.5)
    get_context().set("heartbeat_interval_s", 0.2)

    t0 = time.time()
    # ---- world A --------------------------------------------------------
    master_a = LocalJobMaster(job_name="exactly-once", node_num=2)
    master_a.prepare()
    victim = _spawn("victim", workdir, master_a.addr)
    _wait_file(os.path.join(workdir, "w1.leases.json"))
    victim_leases = _read_log(
        os.path.join(workdir, "w1.leases.json"))[0]["task_ids"]

    ckpt_worker = _spawn("ckpt", workdir, master_a.addr)
    rc0 = ckpt_worker.wait(timeout=120)
    assert rc0 == 0, "ckpt worker failed"

    # the victim never acks: shed its tail lease (the straggler-steal
    # path the SkewMonitor listener drives in production)
    stolen = master_a.task_manager.shed_node(1, bias=1)

    # SIGKILL the victim HOLDING both leases: conn-drop detection must
    # requeue them on master A (journaled)
    victim.send_signal(signal.SIGKILL)
    victim.wait(timeout=30)
    deadline = time.time() + 30
    while time.time() < deadline:
        if JournalEvent.DATA_REQUEUE in _journal_kinds(master_a):
            break
        time.sleep(0.1)
    journal_a = master_a.event_journal.events()
    kinds_a = [e["kind"] for e in journal_a]
    requeue_events = [
        e for e in journal_a if e["kind"] == JournalEvent.DATA_REQUEUE
    ]
    # ---- the world cut --------------------------------------------------
    master_a.stop()

    # ---- world B --------------------------------------------------------
    master_b = LocalJobMaster(job_name="exactly-once", node_num=2)
    master_b.prepare()
    restorer = _spawn("restore", workdir, master_b.addr)
    rc2 = restorer.wait(timeout=120)
    assert rc2 == 0, "restore worker failed"
    journal_b = master_b.event_journal.events()
    kinds_b = [e["kind"] for e in journal_b]
    master_b.stop()

    # ---- the exactly-once audit ----------------------------------------
    w0 = _committed_samples(os.path.join(workdir, "w0.committed.log"))
    w2 = _committed_samples(os.path.join(workdir, "w2.committed.log"))
    dup = sorted(set(w0) & set(w2))
    committed = {**w0, **w2}
    missing = sorted(set(range(DATASET_SIZE)) - set(committed))
    hash_ok = all(
        committed.get(i) == sample_hash(i) for i in range(DATASET_SIZE)
        if i in committed
    )
    # the victim's trained-but-unacked shard must have been RETRAINED by
    # W2 (rolled-back work is repeated, not lost)
    w1_trained = set()
    for rec in _read_log(os.path.join(workdir, "w1.trained.log")):
        w1_trained.update(s["idx"] for s in rec["samples"])
    w2_trained = set()
    for rec in _read_log(os.path.join(workdir, "w2.trained.log")):
        w2_trained.update(s["idx"] for s in rec["samples"])

    return {
        "dataset_size": DATASET_SIZE,
        "committed_total": len(committed),
        "dropped": missing,
        "duplicated": dup,
        "hash_ok": hash_ok,
        "w0_committed": len(w0),
        "w2_committed": len(w2),
        "victim_leases": victim_leases,
        "victim_retrained": sorted(w1_trained & w2_trained),
        "stolen": stolen,
        "journal_a_steal": kinds_a.count(JournalEvent.DATA_STEAL),
        "journal_a_requeue": kinds_a.count(JournalEvent.DATA_REQUEUE),
        "requeue_reasons": sorted({
            e["data"].get("reason", "") for e in requeue_events
        }),
        "journal_a_fault": kinds_a.count(JournalEvent.FAULT_DETECTED),
        "journal_b_restored": kinds_b.count(
            JournalEvent.DATA_STATE_RESTORED),
        "journal_b_epoch_complete": kinds_b.count(
            JournalEvent.DATA_EPOCH_COMPLETE),
        "wall_s": round(time.time() - t0, 2),
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--worker", choices=["ckpt", "victim", "restore"])
    parser.add_argument("--workdir", default="")
    args = parser.parse_args()

    if args.worker:
        fn = {"ckpt": worker_ckpt, "victim": worker_victim,
              "restore": worker_restore}[args.worker]
        return fn(args.workdir)

    workdir = args.workdir
    if not workdir:
        import tempfile

        workdir = tempfile.mkdtemp(prefix="exactly_once_")
    os.makedirs(workdir, exist_ok=True)
    result = run_drill(workdir)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
