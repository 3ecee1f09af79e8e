"""Flash Checkpoint tests: real shm, sharded jax.Arrays on the 8-device CPU
mesh (reference strategy: checkpoint tests use real shm, SURVEY.md §4.4)."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.ckpt.ckpt_saver import (
    AsyncCheckpointSaver,
    latest_step,
    step_dir,
)
from dlrover_tpu.ckpt.checkpointer import Checkpointer, StorageType
from dlrover_tpu.ckpt.engine import CheckpointEngine
from dlrover_tpu.ckpt.shm_handler import SharedMemoryHandler, shm_name
from dlrover_tpu.common.multi_process import LocalIPCServer, unlink_shared_memory


JOB = f"ckpttest{os.getpid()}"


@pytest.fixture(autouse=True)
def _clean_shm():
    yield
    for lr in range(4):
        unlink_shared_memory(shm_name(JOB, 0, lr))


@pytest.fixture()
def mesh():
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devices, ("data", "model"))


def make_state(mesh):
    w = jax.device_put(
        jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        NamedSharding(mesh, P("data", "model")),
    )
    b = jax.device_put(
        jnp.ones((8,), dtype=jnp.float32), NamedSharding(mesh, P(None))
    )
    return {"params": {"w": w, "b": b}, "step": 3, "lr": 0.5}


def test_engine_roundtrip_no_agent(tmp_path, mesh):
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state = make_state(mesh)
    assert engine.save_to_memory(7, state)
    # restore into a same-sharded target
    target = jax.tree.map(lambda x: x, state)
    restored, step = engine.load(target)
    assert step == 7
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.asarray(state["params"]["w"])
    )
    assert restored["step"] == 3 and restored["lr"] == 0.5
    # sharding preserved
    assert restored["params"]["w"].sharding == state["params"]["w"].sharding


def test_unsharded_leaves_restore_uncommitted(tmp_path, mesh):
    """Leaves the target never mesh-sharded (optax counts, step scalars)
    must come back UNCOMMITTED: committing them to a process-local device
    makes multi-process jit reject the state ('incompatible devices') on
    the first post-restore step."""
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state = make_state(mesh)
    # the optax-style leaves: scalar count + small unsharded vector, both
    # plain jnp arrays with SingleDeviceSharding
    state["count"] = jnp.zeros((), jnp.int32) + 7
    state["mu"] = jnp.arange(4, dtype=jnp.float32)
    assert engine.save_to_memory(2, state)
    target = make_state(mesh)
    target["count"] = jnp.zeros((), jnp.int32)
    target["mu"] = jnp.zeros(4, jnp.float32)
    restored, step = engine.load(target)
    assert step == 2
    assert restored["count"]._committed is False
    assert restored["mu"]._committed is False
    assert int(restored["count"]) == 7
    np.testing.assert_array_equal(np.asarray(restored["mu"]),
                                  np.arange(4, dtype=np.float32))


def test_async_save_survives_donation(tmp_path, mesh):
    """The standard train step donates its state (jit donate_argnums),
    deleting the old device buffers right after a save dispatch — the
    on-device snapshot (engine.py _plan_state) must keep the async drain
    valid, and a drain failure must be visible via wait_drained."""
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state = make_state(mesh)
    expected = np.asarray(state["params"]["w"]).copy()
    assert engine.save_to_memory(5, state)
    # donation: delete every device buffer immediately after dispatch
    for leaf in jax.tree.leaves(state):
        if hasattr(leaf, "delete"):
            leaf.delete()
    assert engine.wait_drained(60), "drain lost the snapshot"
    restored, step = engine.load(make_state(mesh))
    assert step == 5
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), expected
    )


def test_replicated_array_saved_once(tmp_path, mesh):
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state = make_state(mesh)
    engine.save_to_memory(1, state)
    assert engine.wait_drained(60)   # async contract: frame lands in shm
    shm = SharedMemoryHandler(shm_name(JOB, 0, 0))
    meta = shm.read_meta()
    b_leaf = next(l for l in meta["leaves"] if "'b'" in l["path"])
    # replicated on 8 devices but stored exactly once (replica_id 0)
    assert len(b_leaf["shards"]) == 1
    w_leaf = next(l for l in meta["leaves"] if "'w'" in l["path"])
    assert len(w_leaf["shards"]) == 8  # 4x2 mesh, one shard per device
    shm.close()


def test_unsealed_frame_is_unreadable_not_torn():
    """Crash-consistency contract of the seal write order: a writer killed
    mid-write leaves the length word zeroed (write_frame zeroes it FIRST
    and rewrites it LAST), so readers see `None` — never a parseable meta
    over partial tensor bytes — and the next complete write recovers."""
    import struct

    name = shm_name(JOB, 0, 3)
    shm = SharedMemoryHandler(name)
    arr = np.arange(16, dtype=np.float32)
    meta = {
        "step": 4, "ts": time.time(), "job": JOB, "node_rank": 0,
        "local_rank": 3,
        "leaves": [{
            "path": "w", "kind": "array", "dtype": "float32",
            "gshape": [16],
            "shards": [{"offset": 0, "nbytes": arr.nbytes,
                        "lshape": [16], "start": [0]}],
        }],
    }
    shm.write_frame(meta, [arr])
    assert shm.read_meta()["step"] == 4
    # simulate death mid-write: the invalidation happened, the seal didn't
    shm._shm.buf[:8] = struct.pack("<Q", 0)
    shm._shm.buf[64:80] = b"\xff" * 16  # scribbled partial data
    assert shm.read_meta() is None
    assert shm.read_frame_bytes() is None
    assert shm.step == -1
    # a complete write over the dead frame is readable again
    meta["step"] = 5
    for leaf in meta["leaves"]:
        for s in leaf["shards"]:
            s.pop("abs_offset", None)
    shm.write_frame(meta, [arr])
    assert shm.read_meta()["step"] == 5
    shm.close()


def test_storage_save_and_resharded_restore(tmp_path, mesh):
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state = make_state(mesh)
    assert engine.save_to_storage(11, state)
    assert latest_step(str(tmp_path)) == 11
    # restore under a DIFFERENT topology: transpose-sharded target
    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh2 = Mesh(devices, ("data", "model"))
    target = {
        "params": {
            "w": jax.device_put(
                jnp.zeros((8, 8), jnp.float32),
                NamedSharding(mesh2, P("model", "data")),
            ),
            "b": jax.device_put(
                jnp.zeros((8,), jnp.float32), NamedSharding(mesh2, P("data"))
            ),
        },
        "step": 0, "lr": 0.0,
    }
    # wipe shm to force the storage path
    engine._shm.unlink()
    restored, step = engine.load(target)
    assert step == 11
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.arange(64, dtype=np.float32).reshape(8, 8),
    )
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["b"]), np.ones((8,), np.float32)
    )
    assert restored["params"]["w"].sharding.spec == P("model", "data")
    assert restored["step"] == 3


def test_load_nothing_returns_minus_one(tmp_path, mesh):
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state, step = engine.load(make_state(mesh))
    assert step == -1


@pytest.fixture()
def agent_ipc(tmp_path):
    server = LocalIPCServer(str(tmp_path / "ipc.sock"))
    server.start()
    yield server
    server.stop()


def test_async_save_via_agent(tmp_path, mesh, agent_ipc):
    ckpt_dir = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver(
        ckpt_dir=ckpt_dir, node_rank=0, local_world_size=1, expected_frames=1
    )
    saver.start(agent_ipc)
    try:
        engine = CheckpointEngine(
            ckpt_dir, job_name=JOB, node_rank=0, local_rank=0,
            ipc_socket=agent_ipc.path, world_size=1, rank=0,
        )
        state = make_state(mesh)
        assert engine.save_to_storage(21, state)
        deadline = time.time() + 10
        while latest_step(ckpt_dir) != 21 and time.time() < deadline:
            time.sleep(0.05)
        assert latest_step(ckpt_dir) == 21
        assert os.path.exists(
            os.path.join(step_dir(ckpt_dir, 21), "frame_0_0.dlrover")
        )
    finally:
        saver.stop()


@pytest.mark.race
def test_flash_ckpt_cycle_is_race_free_under_race_guard(
    tmp_path, mesh, agent_ipc, race_guard
):
    """One full flash-checkpoint save/restore cycle under the
    happens-before race detector: the worker engine hands frames to the
    agent saver over SharedQueue/SharedDict (channel clocks), the
    "ckpt-saver" consumer thread persists and stamps the registered
    ``_persisted_steps`` map — all certified free of unsynchronized
    access at fixture teardown."""
    ckpt_dir = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver(
        ckpt_dir=ckpt_dir, node_rank=0, local_world_size=1, expected_frames=1
    )
    saver.start(agent_ipc)
    try:
        engine = CheckpointEngine(
            ckpt_dir, job_name=JOB, node_rank=0, local_rank=0,
            ipc_socket=agent_ipc.path, world_size=1, rank=0,
        )
        state = make_state(mesh)
        assert engine.save_to_storage(21, state)
        deadline = time.time() + 10
        while latest_step(ckpt_dir) != 21 and time.time() < deadline:
            time.sleep(0.05)
        assert latest_step(ckpt_dir) == 21
        assert race_guard.tracked_created > 0, (
            "the saver's shared() registration never engaged"
        )
        restored, step = engine.load(make_state(mesh))
        assert step == 21
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]),
            np.asarray(state["params"]["w"]),
        )
        assert race_guard.races == [], race_guard.report()
    finally:
        saver.stop()


def test_breakpoint_save_after_worker_death(tmp_path, mesh, agent_ipc):
    """THE flash-checkpoint property: worker saves to memory only and dies;
    the agent persists the shm bytes (reference save_shm_to_storage:758)."""
    ckpt_dir = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver(
        ckpt_dir=ckpt_dir, node_rank=0, local_world_size=1, expected_frames=1
    )
    saver.start(agent_ipc)
    try:
        engine = CheckpointEngine(
            ckpt_dir, job_name=JOB, node_rank=0, local_rank=0,
            ipc_socket=agent_ipc.path, world_size=1, rank=0,
        )
        state = make_state(mesh)
        assert engine.save_to_memory(33, state)  # memory only — no event
        assert latest_step(ckpt_dir) == -1
        # "worker dies"; agent does a breakpoint save
        n = saver.save_shm_to_storage(reason="worker failed")
        assert n == 1
        assert latest_step(ckpt_dir) == 33
        # a fresh engine (restarted worker) restores from storage
        engine2 = CheckpointEngine(
            ckpt_dir, job_name=JOB, node_rank=0, local_rank=0,
            ipc_socket="/nonexistent", world_size=1, rank=0,
        )
        engine2._shm.unlink()
        restored, step = engine2.load(make_state(mesh))
        assert step == 33
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["w"]),
            np.arange(64, dtype=np.float32).reshape(8, 8),
        )
    finally:
        saver.stop()


def test_breakpoint_save_skips_already_persisted(tmp_path, mesh, agent_ipc):
    ckpt_dir = str(tmp_path / "ckpt")
    saver = AsyncCheckpointSaver(
        ckpt_dir=ckpt_dir, node_rank=0, local_world_size=1, expected_frames=1
    )
    saver.start(agent_ipc)
    try:
        engine = CheckpointEngine(
            ckpt_dir, job_name=JOB, node_rank=0, local_rank=0,
            ipc_socket=agent_ipc.path, world_size=1, rank=0,
        )
        engine.save_to_storage(5, make_state(mesh))
        deadline = time.time() + 10
        while latest_step(ckpt_dir) != 5 and time.time() < deadline:
            time.sleep(0.05)
        assert saver.save_shm_to_storage(reason="restart") == 0
    finally:
        saver.stop()


def test_checkpointer_api(tmp_path, mesh):
    ckpt = Checkpointer(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state = make_state(mesh)
    assert ckpt.save_checkpoint(2, state, StorageType.MEMORY)
    restored, step = ckpt.load_checkpoint(state)
    assert step == 2
    assert ckpt.save_checkpoint(4, state, StorageType.DISK)
    ckpt.engine._shm.unlink()
    restored, step = ckpt.load_checkpoint(state)
    assert step == 4


def test_bfloat16_roundtrip(tmp_path, mesh):
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    x = jax.device_put(
        jnp.arange(32, dtype=jnp.bfloat16).reshape(4, 8),
        NamedSharding(mesh, P("data", None)),
    )
    engine.save_to_memory(1, {"x": x})
    restored, step = engine.load({"x": x})
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(restored["x"], dtype=np.float32),
        np.asarray(x, dtype=np.float32),
    )
    assert restored["x"].dtype == jnp.bfloat16


def test_restore_dispatch_is_parallel():
    """Restore must overlap shard reads/transfers (VERDICT r1 weak #3):
    two leaf reads rendezvous on a barrier — serial dispatch would break
    the barrier on timeout."""
    from dlrover_tpu.ckpt.engine import _assemble, _tree_flatten_with_names

    target = {
        "a": np.zeros((4,), np.float32),
        "b": np.zeros((4,), np.float32),
    }
    named, _ = _tree_flatten_with_names(target)
    payload = np.arange(4, dtype=np.float32)
    lookup = {
        path: {
            "path": path, "kind": "array", "dtype": "float32",
            "gshape": [4],
            "shards": [{"start": [0], "lshape": [4], "nbytes": 16}],
        }
        for path, _ in named
    }
    barrier = threading.Barrier(2, timeout=20)

    def reader(leaf_meta, shard_meta):
        barrier.wait()
        return payload.tobytes()

    out = _assemble(target, lookup, reader)
    np.testing.assert_array_equal(out["a"], payload)
    np.testing.assert_array_equal(out["b"], payload)
    # numpy targets keep their historical writability despite the
    # zero-copy frombuffer fast path
    assert out["a"].flags.writeable


class _FakeKVMaster:
    """Just the KV surface the readiness exchange uses, shared across
    'ranks' in-process."""

    def __init__(self):
        from dlrover_tpu.master.kv_store import KVStoreService

        self._kv = KVStoreService()

    def kv_set(self, k, v):
        self._kv.set(k, v)

    def kv_multi_get(self, keys):
        return self._kv.multi_get(keys)

    def kv_delete(self, k):
        self._kv.delete(k)


def _engine(tmp_path, rank, world, master, lr):
    return CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=lr,
        ipc_socket="/nonexistent", world_size=world, rank=rank,
        master_client=master,
    )


def test_save_skipped_on_all_ranks_when_one_busy(tmp_path, mesh):
    """All-or-none saves (reference check_all_rank_ready engine.py:57):
    if any rank's drain is busy, EVERY rank skips — so persisted step
    dirs always collect all frames."""
    master = _FakeKVMaster()
    e0 = _engine(tmp_path, 0, 2, master, 0)
    e1 = _engine(tmp_path, 1, 2, master, 1)
    state = make_state(mesh)
    # warm both (coordinated attempt must run on both ranks concurrently)
    t = threading.Thread(target=lambda: e1.save_to_memory(1, state))
    t.start()
    assert e0.save_to_memory(1, state)
    t.join()
    assert e0.wait_drained(60) and e1.wait_drained(60)

    # fake a busy drain on rank 1
    release = threading.Event()
    e1._drain_thread = threading.Thread(target=release.wait)
    e1._drain_thread.start()
    os.environ["DLROVER_TPU_CKPT_READY_TIMEOUT"] = "10"
    try:
        got = {}
        t = threading.Thread(
            target=lambda: got.update(r1=e1.save_to_memory(2, state))
        )
        t.start()
        got["r0"] = e0.save_to_memory(2, state)  # rank 0 is ready…
        t.join()
        # …but must skip because rank 1 was not
        assert got == {"r0": False, "r1": False}
    finally:
        release.set()
        e1._drain_thread.join()
        os.environ.pop("DLROVER_TPU_CKPT_READY_TIMEOUT", None)

    # both ready again → both save
    t = threading.Thread(target=lambda: got.update(r1=e1.save_to_memory(3, state)))
    t.start()
    got["r0"] = e0.save_to_memory(3, state)
    t.join()
    assert got == {"r0": True, "r1": True}
    assert e0.wait_drained(60) and e1.wait_drained(60)


def test_storage_save_waits_out_busy_drain(tmp_path, mesh):
    """Disk saves must not be starved by fast steps: a busy drain is
    waited out (bounded), not skipped."""
    engine = CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    state = make_state(mesh)
    done = threading.Event()
    engine._drain_thread = threading.Thread(
        target=lambda: (time.sleep(0.5), done.set())
    )
    engine._drain_thread.start()
    t0 = time.time()
    assert engine.save_to_storage(5, state)
    assert done.is_set(), "storage save should have waited for the drain"
    assert time.time() - t0 >= 0.4
    restored, step = engine.load(jax.tree.map(lambda x: x, state))
    assert step == 5


def test_packed_restore_many_small_leaves(tmp_path, mesh):
    """Many small leaves (mixed dtypes, sharded + replicated + scalar)
    restore bit-exact through the packed transfer path, with the H2D put
    count collapsing to ~one per device rather than one per leaf×device
    (engine.py _Stager — the per-put fixed cost is what dominated
    many-leaf restores)."""
    import numpy as np

    from dlrover_tpu.ckpt import engine as eng_mod

    state = {"step": jnp.zeros((), jnp.int32)}
    rng = np.random.default_rng(0)
    for i in range(40):
        state[f"w{i}"] = jax.device_put(
            jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
            NamedSharding(mesh, P("data", "model")),
        )
        state[f"b{i}"] = jax.device_put(
            jnp.asarray(rng.standard_normal((16,)), jnp.bfloat16),
            NamedSharding(mesh, P(None)),
        )
    state["q"] = jax.device_put(
        jnp.asarray(rng.integers(-100, 100, (32,)), jnp.int8),
        NamedSharding(mesh, P(None)),
    )
    engine = CheckpointEngine(
        str(tmp_path), job_name=f"pack{os.getpid()}", node_rank=0,
        local_rank=0, ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    try:
        assert engine.save_to_memory(3, state, blocking=True)

        puts = []
        real_put = jax.device_put

        def counting_put(x, *a, **k):
            puts.append(getattr(x, "nbytes", 0))
            return real_put(x, *a, **k)

        jax.device_put = counting_put
        try:
            restored, step = engine.load(state)
        finally:
            jax.device_put = real_put
        assert step == 3
        for k in state:
            np.testing.assert_array_equal(
                np.asarray(restored[k]), np.asarray(state[k]),
                err_msg=k,
            )
            assert restored[k].dtype == state[k].dtype, k
        # 81 small leaves × 8 devices would be ~650 direct puts; packed,
        # it's one buffer per device (scalar 'step' may add a couple)
        assert len(puts) <= 2 * len(jax.devices()), len(puts)
    finally:
        unlink_shared_memory(shm_name(engine.job_name, 0, 0))


@pytest.mark.parametrize("writable", [True, False])
def test_load_rebuilds_numpy_targets(tmp_path, writable):
    """A numpy target leaf, writable or read-only, comes back as a
    writable array of its own holding the saved bytes; the target's
    buffer is left as it was."""
    rng = np.random.default_rng(0)
    state = {
        "big": rng.standard_normal((256, 1024)).astype(np.float32),
        "small": rng.standard_normal((16,)).astype(np.float32),
        "step_no": 7,
    }
    engine = CheckpointEngine(
        str(tmp_path), job_name=f"nptarget{os.getpid()}", node_rank=0,
        local_rank=0, ipc_socket="/nonexistent", world_size=1, rank=0,
    )
    try:
        assert engine.save_to_memory(5, state, blocking=True)
        target = {
            "big": np.zeros((256, 1024), np.float32),
            "small": np.zeros((16,), np.float32),
            "step_no": 0,
        }
        target["big"].flags.writeable = writable
        restored, step = engine.load(target)
        assert step == 5
        assert restored["step_no"] == 7
        for name in ("big", "small"):
            np.testing.assert_array_equal(restored[name], state[name])
            assert restored[name].flags.writeable, name
            assert not np.shares_memory(restored[name], target[name]), name
            assert not target[name].any(), name
    finally:
        unlink_shared_memory(shm_name(engine.job_name, 0, 0))
