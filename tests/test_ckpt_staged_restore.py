"""The shm restore through the staging ring (engine.py ``_Stager``).

Every packable region of a jax leaf is read into a small ring of warm
host chunks and put from there; a region larger than a chunk is split
into blocks and rebuilt on the device. The chunk constants are patched
small here, so the tests move kilobytes through the same code. What
they hold:

- a restored array owns its bytes: the ring's chunks are overwritten by
  the next job and the segment by the next save (the CPU backend aliases
  any 64-byte-aligned host buffer, so this bites here);
- every size class (a slice of a shared chunk, exactly one chunk, one
  row more, several chunks) x dtype x a target sharded over two devices
  comes back bit for bit;
- the CRC pass, now on several threads, still checks every stamped shard
  and ends before the frame may be elected;
- the registry says which bytes took which path.
"""

import os
import threading
import zlib

import ml_dtypes
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from dlrover_tpu.ckpt import engine as eng_mod  # noqa: E402
from dlrover_tpu.ckpt import shm_handler  # noqa: E402
from dlrover_tpu.ckpt.engine import CheckpointEngine  # noqa: E402
from dlrover_tpu.ckpt.shm_handler import shm_name  # noqa: E402
from dlrover_tpu.common.multi_process import unlink_shared_memory  # noqa: E402
from dlrover_tpu.observability import tracing  # noqa: E402

CHUNK = 4096
COLS = 64


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    monkeypatch.setattr(eng_mod, "_PACK_CHUNK_BYTES", CHUNK)
    monkeypatch.setattr(eng_mod, "_PACK_BATCH_BYTES", CHUNK // 4)
    monkeypatch.setattr(eng_mod, "_PACK_MAX_BYTES", CHUNK // 8)


@pytest.fixture()
def engine(tmp_path, request):
    job = f"staged{os.getpid()}{abs(hash(request.node.name)) % 10**8}"
    eng = CheckpointEngine(
        str(tmp_path), job_name=job, node_rank=0, local_rank=0,
        ipc_socket="/nonexistent", world_size=1, rank=0,
        master_client=_StubMaster(),
    )
    yield eng
    unlink_shared_memory(shm_name(job, 0, 0))


class _StubMaster:
    """Records the engine's journal events; absorbs kv traffic."""

    def __init__(self):
        self.events = []

    def kv_set(self, key, value):
        pass

    def report_event(self, kind, data=None):
        self.events.append((kind, data or {}))


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("x",))


def _random(rng, shape, dtype):
    """Random bit patterns of ``dtype`` (NaNs and all: equality below is
    of bytes)."""
    dtype = np.dtype(dtype)
    if dtype == np.dtype(bool):
        return rng.integers(0, 2, shape).astype(bool)
    n = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    return rng.integers(0, 256, n, dtype=np.uint8).view(dtype).reshape(shape)


def _same_bits(a, b, what=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


def _moved():
    return {path: eng_mod._restore_bytes(path).value
            for path in ("staged", "direct")}


def _since(before):
    return {path: value - before[path] for path, value in _moved().items()}


def _save(engine, step, state):
    assert engine.save_to_memory(step, state)
    assert engine.wait_drained(60)


# -- (b) sizes x dtypes x two devices ----------------------------------------

SIZES = {  # bytes of one device's region
    "chunk_slice": CHUNK // 16,
    "one_chunk": CHUNK,
    "chunk_plus_row": CHUNK,  # and one row, below
    "two_and_a_half_chunks": 5 * CHUNK // 2,
}
DTYPES = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32,
          "int8": np.int8, "bool": np.bool_}


@pytest.mark.parametrize("dtype_name", list(DTYPES))
@pytest.mark.parametrize("size", list(SIZES))
def test_every_size_and_dtype_restores_bit_exact(engine, size, dtype_name):
    dtype = np.dtype(DTYPES[dtype_name])
    rows = SIZES[size] // (COLS * dtype.itemsize)
    rows += size == "chunk_plus_row"
    sharding = NamedSharding(_mesh(2), P("x"))
    value = _random(np.random.default_rng(rows), (2 * rows, COLS), dtype)
    state = {"leaf": jax.device_put(value, sharding)}
    _save(engine, 1, state)
    before = _moved()
    restored, step = engine.load(state)
    assert step == 1
    _same_bits(restored["leaf"], value)
    assert restored["leaf"].sharding == sharding
    path = "direct" if dtype == np.dtype(bool) else "staged"
    assert _since(before) == {
        path: value.nbytes, {"staged": "direct", "direct": "staged"}[path]: 0}


def test_zero_d_scalar_restores_bit_exact(engine):
    sharding = NamedSharding(_mesh(2), P())
    state = {"count": jax.device_put(jnp.asarray(41, jnp.int32), sharding),
             "scale": jax.device_put(jnp.asarray(0.5, jnp.bfloat16), sharding)}
    _save(engine, 2, state)
    before = _moved()
    restored, step = engine.load(state)
    assert step == 2
    for name in state:
        _same_bits(restored[name], state[name], name)
        assert restored[name].sharding == sharding
    # one put a scalar, to the whole sharding
    assert _since(before) == {"staged": 0, "direct": 4 + 2}


@pytest.mark.parametrize("shape", [
    (1, 96, COLS),       # a stacked layer: the split runs along axis 1
    (3, 2, 1024),        # one row is two chunks: along axis 1, a row a block
    (2, 3000),           # the last axis alone is over a chunk
    (5000,),             # a vector
    (97, COLS),          # a prime number of rows: the last block overlaps
])
def test_large_leaf_of_any_shape_is_split_and_rebuilt(engine, shape):
    itemsize = 4
    block_shape, blocks = eng_mod._row_blocks(shape, itemsize)
    nbytes = int(np.prod(shape)) * itemsize
    assert int(np.prod(block_shape)) * itemsize <= CHUNK
    assert sum(fresh for _, _, fresh in blocks) == nbytes
    # what the blocks say, done on the host: every byte lands in its place
    value = _random(np.random.default_rng(7), shape, np.float32)
    flat, rebuilt = value.reshape(-1), np.zeros(shape, np.float32)
    n = int(np.prod(block_shape))
    for offset, start, _ in blocks:
        where = tuple(slice(s, s + b) for s, b in zip(start, block_shape))
        rebuilt[where] = flat[offset // itemsize:][:n].reshape(block_shape)
    _same_bits(rebuilt, value)
    # and through the engine, on the device
    state = {"leaf": jax.device_put(value, NamedSharding(_mesh(1), P()))}
    _save(engine, 3, state)
    restored, _ = engine.load(state)
    _same_bits(restored["leaf"], value)
    names = [sp.name for sp in tracing.get_tracer().finished_spans()]
    assert "ckpt.restore.ring" in names


def test_region_cut_from_several_saved_shards_streams_through_the_ring(
        engine):
    """Saved over two devices, restored replicated: each device's region
    is assembled on the host from two saved shards, then staged."""
    value = _random(np.random.default_rng(3), (160, COLS), np.float32)
    mesh = _mesh(2)
    _save(engine, 4, {"leaf": jax.device_put(value,
                                             NamedSharding(mesh, P("x")))})
    target = {"leaf": jax.ShapeDtypeStruct(
        value.shape, value.dtype, sharding=NamedSharding(mesh, P()))}
    before = _moved()
    restored, step = engine.load(target)
    assert step == 4
    _same_bits(restored["leaf"], value)
    assert _since(before) == {"staged": 2 * value.nbytes, "direct": 0}


# -- (a) a restored array owns its bytes ---------------------------------------

def _mixed_state(seed):
    rng = np.random.default_rng(seed)
    where = NamedSharding(_mesh(2), P("x"))
    whole = NamedSharding(_mesh(2), P())
    return {
        "big": jax.device_put(_random(rng, (200, COLS), np.float32), where),
        "mid": jax.device_put(
            _random(rng, (32, COLS), ml_dtypes.bfloat16), whole),
        "small": [jax.device_put(_random(rng, (8, 8), np.float32), where)
                  for _ in range(12)],
        "flags": jax.device_put(_random(rng, (70, 70), np.bool_), whole),
        "count": jax.device_put(jnp.asarray(seed, jnp.int32), whole),
    }


def test_restored_arrays_alias_neither_a_staging_chunk_nor_the_segment(
        engine):
    first, second = _mixed_state(1), _mixed_state(2)
    first_bits = jax.tree.map(lambda x: np.asarray(x).copy(), first)
    _save(engine, 1, first)
    restored_first, step = engine.load(first)
    assert step == 1
    # other values into the same segment, and through new rings
    _save(engine, 2, second)
    restored_second, step = engine.load(second)
    assert step == 2
    jax.tree.map(_same_bits, restored_second, second)
    # what was restored first still is what was saved first
    jax.tree.map(_same_bits, restored_first, first_bits)


# -- (c) the CRC pass, fanned out, before the election ---------------------------

def test_corrupt_shard_is_caught_before_election_by_the_fanned_out_pass(
        engine, monkeypatch):
    state = _mixed_state(5)
    _save(engine, 9, state)
    meta = engine._shm.read_meta()
    shards = [(leaf["path"], sh) for leaf in meta["leaves"]
              for sh in leaf.get("shards", []) if sh.get("crc")]
    assert len(shards) > eng_mod._RESTORE_THREADS
    # a flipped byte in a shard of the middle and in the frame's last
    victims = [shards[len(shards) // 2], shards[-1]]
    for _, sh in victims:
        at = sh["abs_offset"] + sh["nbytes"] // 2
        engine._shm._shm.buf[at] ^= 0xFF

    checked = []
    real_crc32 = zlib.crc32

    def counting_crc32(data, *args):
        checked.append(threading.current_thread().name)
        return real_crc32(data, *args)

    monkeypatch.setattr(shm_handler.zlib, "crc32", counting_crc32)
    bad = engine._shm.verify_frame()
    assert bad == [f"{path}@{sh['offset']}" for path, sh in victims]
    # every stamped shard was checked, and not by one thread
    assert len(checked) == len(shards)
    assert len(set(checked)) > 1
    assert all(name.startswith("ckpt-verify") for name in checked)

    order = []
    real_consistent = engine._shm_step_consistent

    def consistent(step=None):
        order.append(("elect", step, len(checked)))
        return real_consistent(step)

    monkeypatch.setattr(engine, "_shm_step_consistent", consistent)
    monkeypatch.setattr(
        engine, "_load_from_shm",
        lambda *a, **k: pytest.fail("a corrupt frame was elected"))
    checked.clear()
    restored, step = engine.load(state)
    assert step == -1  # excluded; storage is empty
    # the whole pass had ended when the election was held, and the rank
    # published -1
    assert order == [("elect", -1, len(shards))]
    corrupt = [d for k, d in engine._master.events if k == "ckpt_corrupt"]
    assert corrupt and corrupt[0]["shards"] == bad
    assert corrupt[0]["medium"] == "shm" and corrupt[0]["step"] == 9


# -- (d) the registry says which bytes took which path ---------------------------

def test_registry_counts_staged_and_direct_bytes(engine):
    state = _mixed_state(8)
    _save(engine, 1, state)
    before = _moved()
    restored, step = engine.load(state)
    assert step == 1
    jax.tree.map(_same_bits, restored, state)
    leaves = jax.tree.leaves(state)
    packable = sum(
        x.nbytes * (1 if x.sharding.spec == P("x") else 2) for x in leaves
        if x.ndim and x.dtype != jnp.bool_)
    # a put a device for an array, one to the whole sharding for a scalar
    direct = sum((2 if x.ndim else 1) * x.nbytes for x in leaves
                 if not x.ndim or x.dtype == jnp.bool_)
    assert _since(before) == {"staged": packable, "direct": direct}
    reads = [sp for sp in tracing.get_tracer().finished_spans()
             if sp.name == "ckpt.restore.read"][-200:]
    assert {sp.attrs.get("staged") for sp in reads} == {True, False}
