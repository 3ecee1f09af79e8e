"""The memory save's streamed form: the snapshot is cut into blocks when
it is taken, fetched through a bounded window and written into the frame
piece by piece. Real shm, the CPU devices; no assertion on wall time."""

import copy
import os
import struct
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.ckpt import engine as engine_mod
from dlrover_tpu.ckpt.engine import CheckpointEngine
from dlrover_tpu.ckpt.shm_handler import (
    FrameWriter,
    SharedMemoryHandler,
    pack_frame,
    shm_name,
)
from dlrover_tpu.common.constants import SpanName
from dlrover_tpu.common.multi_process import unlink_shared_memory
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.registry import get_registry

JOB = f"streamtest{os.getpid()}"
CHUNK = 1024  # what the tests shrink ``_PACK_CHUNK_BYTES`` to


@pytest.fixture(autouse=True)
def _clean():
    tracing.reset_tracer()
    yield
    tracing.reset_tracer()
    for name in (shm_name(JOB, 0, 0), JOB + "_plain", JOB + "_pieces"):
        unlink_shared_memory(name)


@pytest.fixture()
def small_chunk(monkeypatch):
    monkeypatch.setattr(engine_mod, "_PACK_CHUNK_BYTES", CHUNK)
    monkeypatch.setattr(engine_mod, "_D2H_WINDOW_BYTES", 2 * CHUNK)
    # the restore's constants keep their order: a batch fits a chunk
    monkeypatch.setattr(engine_mod, "_PACK_BATCH_BYTES", CHUNK // 4)
    monkeypatch.setattr(engine_mod, "_PACK_MAX_BYTES", CHUNK // 8)


def engine_for(tmp_path):
    return CheckpointEngine(
        str(tmp_path), job_name=JOB, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0)


def plain_write_frame(shm: SharedMemoryHandler, meta, buffers) -> None:
    """The frame writer as it was before the streamed form, whole buffers
    and one checksum call each: the tests' reference for the bytes of a
    frame."""
    rel, expected = 0, {}
    for b in buffers:
        expected[rel] = int(b.nbytes)
        rel += int(b.nbytes)
    for leaf in meta["leaves"]:
        for shard in leaf.get("shards", []):
            if expected.get(shard["offset"]) == shard["nbytes"]:
                shard["crc"] = b"\x00\x00\x00\x00"
                shard["dig"] = b"\x00" * 8
    header = pack_frame(meta)
    data_start = len(header)
    for leaf in meta["leaves"]:
        for shard in leaf.get("shards", []):
            shard["abs_offset"] = data_start + shard["offset"]
    header = pack_frame(meta)
    while len(header) != data_start:
        data_start = len(header)
        for leaf in meta["leaves"]:
            for shard in leaf.get("shards", []):
                shard["abs_offset"] = data_start + shard["offset"]
        header = pack_frame(meta)
    assert shm._ensure(data_start + sum(int(b.nbytes) for b in buffers))
    buf = shm._shm.buf
    buf[:8] = struct.pack("<Q", 0)
    pos = data_start
    crcs, digs = {}, {}
    for b in buffers:
        flat = np.ascontiguousarray(b).view(np.uint8).reshape(-1)
        n = flat.nbytes
        buf[pos:pos + n] = flat.data
        rel = pos - data_start
        crcs[rel] = zlib.crc32(flat.data) & 0xFFFFFFFF
        digs[rel] = struct.pack(
            ">II", crcs[rel], zlib.adler32(flat.data) & 0xFFFFFFFF)
        pos += n
    for leaf in meta["leaves"]:
        for shard in leaf.get("shards", []):
            if shard["offset"] in crcs and "crc" in shard:
                shard["crc"] = struct.pack(">I", crcs[shard["offset"]])
            if shard["offset"] in digs and "dig" in shard:
                shard["dig"] = digs[shard["offset"]]
    sealed = pack_frame(meta)
    assert len(sealed) == len(header)
    buf[8:len(sealed)] = sealed[8:]
    buf[:8] = sealed[:8]


def frame_bytes(shm: SharedMemoryHandler) -> bytes:
    blob = shm.read_frame_bytes()
    assert blob is not None
    return bytes(blob)


def bits(x) -> bytes:
    return np.asarray(x).tobytes()


def placement(name):
    if name == "one":
        return lambda x: jax.device_put(x, jax.devices()[0])
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    return lambda x: jax.device_put(x, NamedSharding(
        mesh, P(*("data", "model")[:x.ndim])))


def make_leaf(kind, shard_bytes, shards):
    """A two-dimensional leaf whose every shard on ``shards`` = (4, 2)
    or (1, 1) devices has ``shard_bytes`` bytes, with bits that no
    rounding would keep (NaN payloads among the floats)."""
    dtype = {"f32": np.float32, "bf16": jnp.bfloat16, "int32": np.int32,
             "0d": np.float32}[kind]
    itemsize = np.dtype(dtype).itemsize
    rows, cols = 8 * shards[0], shard_bytes // itemsize // 8 * shards[1]
    raw = np.random.default_rng(rows * cols).integers(
        0, 256, rows * cols * itemsize, dtype=np.uint8)
    return raw.view(dtype).reshape(rows, cols)


# -- (a) the frame's bytes -----------------------------------------------------


@pytest.mark.parametrize("mesh", ["one", "4x2"])
@pytest.mark.parametrize("size", ["under", "at", "over"])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int32", "0d"])
def test_saved_frame_equals_the_plain_writers_and_restores_every_bit(
        tmp_path, small_chunk, kind, size, mesh):
    put = placement(mesh)
    shard_bytes = {"under": CHUNK // 2, "at": CHUNK, "over": 5 * CHUNK + 64}[
        size]
    state = {"w": put(make_leaf(kind, shard_bytes,
                                (4, 2) if mesh == "4x2" else (1, 1))),
             "b": put(np.arange(16, dtype=np.float32)), "lr": 0.5}
    if kind == "0d":
        state["count"] = jnp.asarray(7, jnp.int32)
    engine = engine_for(tmp_path)
    meta, pending = engine._plan_state(3, state)
    shard_metas = [shard for shard, _ in pending]
    assert [s["nbytes"] for s in shard_metas if s["nbytes"] > 64] == [
        shard_bytes] * (8 if mesh == "4x2" else 1)
    # what the plain writer gets: the same meta, every shard whole
    plain_meta = copy.deepcopy(meta)
    buffers = [np.asarray(s.data)
               for leaf in jax.tree.leaves(state) if isinstance(leaf, jax.Array)
               for s in leaf.addressable_shards if s.replica_id == 0]
    assert [int(b.nbytes) for b in buffers] == [
        s["nbytes"] for s in shard_metas]
    split = [len(pieces) > 1 for _, pieces in pending]
    assert split == [s["nbytes"] > CHUNK for s in shard_metas]

    fetched = engine._fetch_and_write(meta, pending)
    assert fetched["split_leaves"] == sum(split)
    assert fetched["blocks"] >= len(buffers) + fetched["split_leaves"]
    assert fetched["inflight_peak_bytes"] <= 2 * CHUNK

    plain = SharedMemoryHandler(JOB + "_plain")
    plain_write_frame(plain, plain_meta, buffers)
    assert frame_bytes(engine._shm) == frame_bytes(plain)
    # and the public whole-buffer form is the same writer
    whole = SharedMemoryHandler(JOB + "_pieces")
    whole.write_frame(copy.deepcopy(meta), buffers)
    assert frame_bytes(whole) == frame_bytes(plain)
    plain.close()
    whole.close()

    assert engine._shm.verify_frame() == []
    restored, step = engine.load(jax.tree.map(lambda x: x, state))
    assert step == 3
    for name in [k for k in state if k != "lr"]:
        assert bits(restored[name]) == bits(state[name]), name
        assert restored[name].dtype == state[name].dtype
    assert restored["lr"] == 0.5


@pytest.mark.parametrize("cuts", [(), (1,), (7, 8), (1, 100, 101, 4095)])
def test_pieces_of_any_size_seal_the_same_frame(cuts):
    rng = np.random.default_rng(len(cuts))
    buffers = [rng.integers(0, 256, n, dtype=np.uint8)
               for n in (4096, 0, 12)]
    meta = {"step": 1, "ts": 0.0, "leaves": []}
    offset = 0
    for n, b in enumerate(buffers):
        meta["leaves"].append({
            "path": f"l{n}", "kind": "array", "dtype": "uint8",
            "gshape": [b.size], "shards": [{
                "offset": offset, "nbytes": b.size, "lshape": [b.size],
                "start": [0]}]})
        offset += b.size
    plain = SharedMemoryHandler(JOB + "_plain")
    plain_write_frame(plain, copy.deepcopy(meta), buffers)

    pieces = SharedMemoryHandler(JOB + "_pieces")
    frame = pieces.open_frame(copy.deepcopy(meta),
                              [b.size for b in buffers])
    assert isinstance(frame, FrameWriter)
    edges = (0, *cuts, buffers[0].size)
    for lo, hi in zip(edges, edges[1:]):
        frame.write(0, lo, buffers[0][lo:hi])
    frame.write(2, 0, buffers[2])
    took = frame.seal()
    assert took["copy_s"] >= 0 and took["checksum_s"] >= 0
    assert frame_bytes(pieces) == frame_bytes(plain)
    assert pieces.verify_frame() == []
    plain.close()
    pieces.close()


def test_a_piece_out_of_place_and_a_short_frame_are_refused():
    shm = SharedMemoryHandler(JOB + "_pieces")
    data = np.arange(64, dtype=np.uint8)
    meta = {"step": 1, "leaves": [{
        "path": "l", "kind": "array", "dtype": "uint8", "gshape": [64],
        "shards": [{"offset": 0, "nbytes": 64, "lshape": [64],
                    "start": [0]}]}]}
    frame = shm.open_frame(meta, [64])
    frame.write(0, 0, data[:16])
    with pytest.raises(ValueError):
        frame.write(0, 32, data[32:48])  # a gap: the running CRC would lie
    with pytest.raises(ValueError):
        frame.write(0, 16, np.zeros(64, np.uint8))  # past the shard's end
    with pytest.raises(ValueError):
        frame.seal()  # 48 bytes missing
    assert shm.read_meta() is None
    shm.close()


# -- (b) the window ------------------------------------------------------------


class RecordedArray:
    """Stands in for a device array on the drain's path: says when its
    copy to the host was asked for and when it was waited for."""

    def __init__(self, log, name, data):
        self._log, self._name, self._data = log, name, data
        self.nbytes, self.shape, self.dtype = (
            data.nbytes, data.shape, data.dtype)

    def copy_to_host_async(self):
        self._log.append(("issue", self._name, self.nbytes))

    def __array__(self, dtype=None, copy=None):
        self._log.append(("land", self._name, self.nbytes))
        return self._data


def by_hand(log, sizes, piece_bytes):
    """(meta, pending, the data) for shards of ``sizes`` bytes, each cut
    into ``RecordedArray`` pieces of at most ``piece_bytes``."""
    rng = np.random.default_rng(sum(sizes))
    leaves, pending, datas, offset = [], [], [], 0
    for n, size in enumerate(sizes):
        data = rng.integers(0, 256, size, dtype=np.uint8)
        shard = {"offset": offset, "nbytes": size, "lshape": [size],
                 "start": [0]}
        leaves.append({"path": f"l{n}", "kind": "array", "dtype": "uint8",
                       "gshape": [size], "shards": [shard]})
        pending.append((shard, [
            (lo, RecordedArray(log, (n, lo), data[lo:lo + piece_bytes]), 0)
            for lo in range(0, size, piece_bytes)] or [
                (0, RecordedArray(log, (n, 0), data), 0)]))
        datas.append(data)
        offset += size
    return {"step": 4, "leaves": leaves}, pending, datas


@pytest.mark.parametrize("sizes,piece", [
    ((5000, 10, 3000, 1024), 1000),   # pieces under half the window
    ((4096, 4096), 1024),             # two fill it exactly
    ((9000, 100, 100), 3000),         # larger than the window: one alone
    ((10, 20, 30, 0, 40), 1000),      # many small ones ride together
])
def test_bytes_issued_and_not_landed_stay_inside_the_window(
        tmp_path, small_chunk, sizes, piece):
    log = []
    meta, pending, datas = by_hand(log, sizes, piece)
    order = [(n, lo) for n, (_, pieces) in enumerate(pending)
             for lo, _, _ in pieces]
    engine = engine_for(tmp_path)
    fetched = engine._fetch_and_write(meta, pending)
    assert pending == []  # nothing holds a block once it is written
    # the overlapping phases left the thread's trace context as it was
    assert tracing.current_context() is None

    window, inflight, peak = 2 * CHUNK, 0, 0
    for what, name, nbytes in log:
        if what == "issue":
            # over the window only alone on the link
            assert inflight + nbytes <= window or inflight == 0, log
            inflight += nbytes
            peak = max(peak, inflight)
        else:
            inflight -= nbytes
    assert inflight == 0
    with_bytes = [name for name in order if sizes[name[0]]]
    assert [name for what, name, _ in log if what == "issue"] == with_bytes
    assert [name for what, name, _ in log if what == "land"] == order
    assert fetched["inflight_peak_bytes"] == peak
    assert fetched["blocks"] == len(order)
    assert fetched["split_leaves"] == sum(size > piece for size in sizes)
    assert fetched["blocked_s"] >= 0
    # more than one piece on the link whenever two fit
    if 2 * piece <= window and max(sizes) >= 2 * piece:
        assert peak > piece

    meta = engine._shm.read_meta()
    assert meta["step"] == 4 and engine._shm.verify_frame() == []
    for leaf, data in zip(meta["leaves"], datas):
        assert bytes(engine._shm.read_shard_bytes(leaf["shards"][0])) == (
            data.tobytes())


def test_host_leaves_are_written_and_never_counted_on_the_link(tmp_path):
    engine = engine_for(tmp_path)
    state = {"a": np.arange(1 << 16, dtype=np.float32), "n": 3}
    meta, pending = engine._plan_state(1, state)
    fetched = engine._fetch_and_write(meta, pending)
    assert fetched == {"blocks": 1, "split_leaves": 0,
                       "inflight_peak_bytes": 0,
                       "blocked_s": fetched["blocked_s"]}
    restored, step = engine.load({"a": np.zeros(1 << 16, np.float32),
                                  "n": 0})
    assert step == 1 and restored["n"] == 3
    np.testing.assert_array_equal(restored["a"], state["a"])


@pytest.mark.parametrize("mesh", ["one", "4x2"])
def test_the_plan_issues_no_copy_and_splits_every_shard_over_a_chunk(
        tmp_path, small_chunk, monkeypatch, mesh):
    from jax._src.array import ArrayImpl

    issued = []
    real = ArrayImpl.copy_to_host_async

    def recorded(self):
        issued.append(self.nbytes)
        return real(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", recorded)
    put = placement(mesh)
    state = {
        "big": put(make_leaf("f32", 3 * CHUNK + 128, (4, 2))),
        "deep": put(np.arange(16 * 2 * 8 * 40, dtype=np.float32).reshape(
            16, 2, 8, 40)),            # rows of 1280 bytes: over a chunk
        "flat": jnp.arange(700, dtype=jnp.int32),  # 2800 bytes, one axis
        "small": put(np.ones((8, 8), np.float32)),
        "count": jnp.asarray(2, jnp.int32),
    }
    engine = engine_for(tmp_path)
    meta, pending = engine._plan_state(9, state)
    assert issued == []
    for shard, pieces in pending:
        assert (len(pieces) > 1) == (shard["nbytes"] > CHUNK), shard
        assert all(array.nbytes <= CHUNK for _, array, _ in pieces)
        # the pieces tile the shard, in order
        at = 0
        for offset, array, skip in pieces:
            assert offset == at and 0 <= skip < max(1, array.nbytes)
            at += array.nbytes - skip
        assert at == shard["nbytes"]
    theirs = {s.data.unsafe_buffer_pointer() for leaf in state.values()
              for s in leaf.addressable_shards}
    ours = {array.unsafe_buffer_pointer()
            for _, pieces in pending for _, array, _ in pieces}
    # a private copy each, never the caller's buffer handed through
    assert ours.isdisjoint(theirs)

    fetched = engine._fetch_and_write(meta, pending)
    assert sum(issued) >= sum(s["nbytes"] for leaf in meta["leaves"]
                              for s in leaf.get("shards", []))
    assert len(issued) == fetched["blocks"]
    assert fetched["inflight_peak_bytes"] <= 2 * CHUNK
    # "big" and "deep" over a chunk on every device that holds a shard,
    # "flat" on its one
    assert fetched["split_leaves"] == (8 + 8 + 1 if mesh == "4x2" else 3)
    restored, step = engine.load(jax.tree.map(lambda x: x, state))
    assert step == 9
    for name in state:
        assert bits(restored[name]) == bits(state[name]), name


# -- (c) a writer that stops ---------------------------------------------------


@pytest.mark.parametrize("pieces_written", [0, 1, 4, 8])
def test_a_writer_stopped_after_any_piece_leaves_the_frame_unreadable(
        tmp_path, small_chunk, monkeypatch, pieces_written):
    engine = engine_for(tmp_path)
    put = placement("one")
    first = {"w": put(make_leaf("int32", 8 * CHUNK, (1, 1))),  # 8 blocks
             "b": put(np.arange(16, dtype=np.float32))}
    assert engine.save_to_storage(1, first, str(tmp_path))
    assert engine._shm.read_meta()["step"] == 1

    class Stopped(Exception):
        pass

    real, calls = FrameWriter.write, []

    def write(self, shard, offset, data):
        if len(calls) == pieces_written:
            raise Stopped()
        calls.append((shard, offset))
        return real(self, shard, offset, data)

    monkeypatch.setattr(FrameWriter, "write", write)
    second = jax.tree.map(lambda x: x + 1, first)
    with pytest.raises(Stopped):
        engine.save_to_memory(2, second, blocking=True)
    assert len(calls) == pieces_written
    monkeypatch.setattr(FrameWriter, "write", real)
    # the length word is zero: no reader takes the torn frame for a frame
    assert bytes(engine._shm._shm.buf[:8]) == bytes(8)
    assert engine._shm.read_meta() is None and engine.shm_step() == -1
    # and the ladder's next rung still has the step before
    restored, step = engine.load(jax.tree.map(jnp.zeros_like, first))
    assert step == 1
    for name in first:
        assert bits(restored[name]) == bits(first[name])
    # the next save is none the worse
    assert engine.save_to_memory(3, second, blocking=True)
    assert engine.shm_step() == 3


# -- (d) donation --------------------------------------------------------------


def test_async_save_of_a_split_leaf_survives_donation(tmp_path, small_chunk):
    """``tests/test_ckpt.py::test_async_save_survives_donation`` with a
    leaf over the chunk: its blocks are made by work enqueued before the
    caller's buffers go, none is a buffer of the caller's."""
    engine = engine_for(tmp_path)
    put = placement("4x2")
    state = {"w": put(make_leaf("bf16", 4 * CHUNK, (4, 2))),
             "b": put(np.ones(8, np.float32)),
             "count": jnp.asarray(5, jnp.int32), "lr": 0.25}
    expected = {k: bits(v) for k, v in state.items() if k != "lr"}
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, state)
    assert engine.save_to_memory(5, state)
    for leaf in jax.tree.leaves(state):
        if hasattr(leaf, "delete"):
            leaf.delete()
    assert engine.wait_drained(60), "drain lost the snapshot"
    restored, step = engine.load(target)
    assert step == 5
    for name, want in expected.items():
        assert bits(restored[name]) == want, name


# -- (e) what the drain says of itself -----------------------------------------


def test_one_span_a_phase_and_the_drains_own_account(tmp_path, small_chunk):
    engine = engine_for(tmp_path)
    put = placement("one")
    state = {"w": put(make_leaf("f32", 8 * CHUNK, (1, 1))),  # 8 blocks
             "b": put(np.arange(16, dtype=np.float32))}
    counter = get_registry().counter("dlrover_ckpt_drain_blocks_total")
    before = counter.value
    for step in (1, 2):
        assert engine.save_to_memory(step, state)
        assert engine.wait_drained(60)
    spans = tracing.get_tracer().finished_spans()

    def named(name):
        return [sp for sp in spans if sp.name == name]

    drains = named(SpanName.CKPT_DRAIN)
    assert len(drains) == 2
    for name in (SpanName.CKPT_DRAIN_D2H_WAIT, SpanName.CKPT_DRAIN_SHM_WRITE,
                 SpanName.CKPT_DRAIN_PUBLISH):
        # one a drain, each the drain's own child
        assert sorted(sp.parent_id for sp in named(name)) == sorted(
            sp.span_id for sp in drains), name
    for drain in drains:
        d2h, write, publish = (
            next(sp for sp in named(name) if sp.parent_id == drain.span_id)
            for name in (SpanName.CKPT_DRAIN_D2H_WAIT,
                         SpanName.CKPT_DRAIN_SHM_WRITE,
                         SpanName.CKPT_DRAIN_PUBLISH))
        assert drain.attrs["blocks"] == 8 + 1  # w in eight, b whole
        assert drain.attrs["split_leaves"] == 1
        assert 0 < drain.attrs["inflight_peak_bytes"] <= 2 * CHUNK
        assert drain.attrs["blocked_s"] >= 0
        assert drain.attrs["bytes"] == 8 * CHUNK + 64
        assert write.attrs["copy_s"] >= 0 and write.attrs["checksum_s"] > 0
        # the first piece is written while later ones are still to land
        assert (drain.start_t <= d2h.start_t <= write.start_t < d2h.end_t
                <= write.end_t <= publish.start_t <= drain.end_t)
    assert counter.value - before == 2 * 9
    # the drain thread handed its trace context back
    assert all(sp.status == "ok" for sp in spans)


def test_a_segment_caught_between_creation_and_sizing_is_not_there_yet():
    """The agent's saver may open a worker's segment while the drain
    thread is creating it (the streamed save creates it as soon as the
    first blocks are on the link): an empty file is no frame, not an
    error."""
    import _posixshmem

    name = JOB + "_pieces"
    fd = _posixshmem.shm_open("/" + name, os.O_CREAT | os.O_EXCL | os.O_RDWR,
                              mode=0o600)
    try:
        shm = SharedMemoryHandler(name)
        assert shm.read_meta() is None and shm.step == -1
    finally:
        os.close(fd)
        _posixshmem.shm_unlink("/" + name)
