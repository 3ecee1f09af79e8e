"""Shared-memory checkpoint buffer layout + reader/writer.

Reference: dlrover/python/elastic_agent/torch/ckpt_saver.py
``SharedMemoryHandler``:234 — pickled meta dict + flat tensor buffer
(:286–367). This build's layout (no pickle):

    [0:8)              little-endian uint64 = len(meta)
    [8:8+len(meta))    msgpack meta (see below)
    [data_start:...]   tensor bytes at meta-recorded offsets

meta = {
  "step": int, "ts": float, "job": str, "node_rank": int, "local_rank": int,
  "leaves": [ {"path": str, "kind": "array"|"value",
               "value": <small scalar/list, if kind=value>,
               "dtype": str, "gshape": [..],         # if kind=array
               "shards": [ {"offset": int, "nbytes": int,
                            "lshape": [..], "start": [..]} ] } ]
}

``start`` is the per-dimension global start index of the shard (from the
``jax.Array`` shard's index slices), so storage restore can reassemble the
global array under any target topology.
"""

import os
import struct
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import msgpack
import numpy as np

from dlrover_tpu.common.constants import (
    ChaosSite,
    ConfigKey,
    EnvKey,
    env_flag,
    env_str,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.multi_process import (
    create_shared_memory,
    unlink_shared_memory,
)

_U64 = struct.Struct("<Q")
_CRC = struct.Struct(">I")
# 8-byte content digest (crc32 + adler32) stamped per shard next to the
# CRC — the incremental saver (ckpt/manifest.py) compares these across
# steps to find dirty shards without hashing the frame again; two
# independent 32-bit checksums make a silent delta-skip collision
# vanishingly unlikely at adler/crc cost (no cryptographic hash in the
# drain path)
_DIG = struct.Struct(">II")


def shard_digest(data) -> bytes:
    """The 8-byte content digest of one shard's bytes (same function the
    frame writer stamps into the sealed meta as ``dig``)."""
    return _DIG.pack(
        zlib.crc32(data) & 0xFFFFFFFF, zlib.adler32(data) & 0xFFFFFFFF
    )

# per-shard CRC32 stamping on frame writes; on by default, env-gated for
# benchmarking the raw write path
CRC_ENV = ConfigKey.CKPT_CRC


def _crc_enabled() -> bool:
    return env_flag(CRC_ENV, default=True)


def shm_name(job_name: str, node_rank: int, local_rank: int,
             incarnation: Optional[str] = None) -> str:
    """Segment name for one worker's frame.

    ``incarnation`` (default: ``EnvKey.SHM_INCARNATION`` from the
    environment) is a nonce the agent mints once per agent process and
    passes to its workers: a restarted agent gets fresh segment names
    instead of reattaching to a previous incarnation's possibly
    half-written memory, and :func:`cleanup_orphan_segments` can tell the
    old segments from the live ones."""
    if incarnation is None:
        incarnation = env_str(EnvKey.SHM_INCARNATION)
    base = f"dlrtpu_{job_name}_{node_rank}_{local_rank}"
    return f"{base}_i{incarnation}" if incarnation else base


def cleanup_orphan_segments(job_name: str, node_rank: int,
                            incarnation: Optional[str] = None) -> List[str]:
    """Unlink this node's shm segments left by a previous agent
    incarnation (different — or missing — nonce). Returns the names
    removed. A crashed agent can't clean up after itself; without this its
    segments leak /dev/shm until reboot and a same-name successor would
    reattach to torn memory."""
    if incarnation is None:
        incarnation = env_str(EnvKey.SHM_INCARNATION)
    prefix = f"dlrtpu_{job_name}_{node_rank}_"
    keep_suffix = f"_i{incarnation}" if incarnation else None
    removed: List[str] = []
    try:
        names = os.listdir("/dev/shm")
    except OSError:
        return removed
    for name in sorted(names):
        if not name.startswith(prefix):
            continue
        tail = name[len(prefix):]
        if keep_suffix is not None and name.endswith(keep_suffix):
            continue  # current incarnation
        if keep_suffix is None and "_i" not in tail:
            continue  # un-nonced segment and we run un-nonced: it's ours
        unlink_shared_memory(name)
        removed.append(name)
    if removed:
        logger.warning(
            "unlinked %d orphan shm segment(s) from a previous agent "
            "incarnation: %s", len(removed), removed,
        )
    return removed


class TensorShard:
    """One contiguous saved shard of one array."""

    def __init__(self, offset: int, nbytes: int, lshape: List[int],
                 start: List[int]):
        self.offset = offset
        self.nbytes = nbytes
        self.lshape = lshape
        self.start = start

    def to_meta(self) -> Dict:
        return {
            "offset": self.offset, "nbytes": self.nbytes,
            "lshape": self.lshape, "start": self.start,
        }


def pack_frame(meta: Dict) -> bytes:
    meta_bytes = msgpack.packb(meta, use_bin_type=True)
    return _U64.pack(len(meta_bytes)) + meta_bytes


class SharedMemoryHandler:
    """Owns one shm segment holding one checkpoint frame."""

    def __init__(self, name: str):
        self._name = name
        self._shm = None
        self._fd = None  # /dev/shm fd for pread-based shard reads
        self._fd_shm = None  # the segment the fd belongs to

    @property
    def name(self) -> str:
        return self._name

    def _ledger(self) -> None:
        """Sync this segment's claim in the device-memory ledger to its
        currently-mapped size (0 = released)."""
        from dlrover_tpu.common.constants import MetricLabel
        from dlrover_tpu.observability.memory import get_accountant

        get_accountant().adjust(
            MetricLabel.MEM_STAGING, f"ckpt_shm/{self._name}",
            int(self._shm.size) if self._shm is not None else 0)

    def _ensure(self, size: int) -> bool:
        if self._shm is not None and self._shm.size >= size:
            return True
        if self._shm is not None:
            self._shm.close()
            self._shm = None
        # round up generously so step-to-step meta jitter doesn't re-create
        alloc = max(1024, int(size * 1.05))
        self._shm = create_shared_memory(self._name, create=True, size=alloc)
        self._ledger()
        return self._shm is not None

    def open(self) -> bool:
        if self._shm is not None:
            return True
        self._shm = create_shared_memory(self._name, create=False)
        self._ledger()
        return self._shm is not None

    def close(self) -> None:
        if self._shm is not None:
            self._shm.close()
            self._shm = None
            self._ledger()
        if self._fd is not None:
            try:
                import os

                os.close(self._fd)
            except OSError:
                pass
            self._fd = None
            self._fd_shm = None

    def _shard_fd(self) -> Optional[int]:
        """fd on the segment's /dev/shm file, for pread-based reads.

        Reading large segments through the mmap walks a 4 KB-page mapping
        (tmpfs gets no hugepages), which the kernel's read path does not.
        On the v5e's host (PERF.md section 6, PR 25; 512 MB into a warm
        64 MiB buffer, one thread): ``pread`` 0.027 s, the mapping 0.107 s
        in the process that wrote the segment (4x) and 1.03 s on a fresh
        process's first walk, where ``pread`` takes 0.083 s (12x). r05
        measured 4-45x on other VM hosts. Linux-only; callers fall back
        to the mmap view."""
        import os

        if self._fd is not None and self._fd_shm is self._shm:
            return self._fd
        if self._fd is not None:
            try:
                os.close(self._fd)
            except OSError:
                pass
            self._fd = None
            self._fd_shm = None
        try:
            self._fd = os.open(
                "/dev/shm/" + self._shm.name.lstrip("/"), os.O_RDONLY
            )
            self._fd_shm = self._shm
        except OSError:
            self._fd = None
        return self._fd

    def unlink(self) -> None:
        self.close()
        unlink_shared_memory(self._name)

    # -- write -------------------------------------------------------------

    def write_frame(self, meta: Dict,
                    buffers: List[np.ndarray]) -> Dict[str, float]:
        """Write meta + tensor buffers. ``meta['leaves']`` offsets must match
        the order/sizes of ``buffers``. Returns where the data pass spent
        its time: ``copy_s`` (buffers into the segment) and ``checksum_s``
        (CRC32 + Adler32 over them), each summed over the buffers."""
        frame = self.open_frame(meta, [int(b.nbytes) for b in buffers])
        for shard, b in enumerate(buffers):
            frame.write(shard, 0, b)
        return frame.seal()

    def open_frame(self, meta: Dict, sizes: List[int]) -> "FrameWriter":
        """Start a frame of ``meta`` and data shards of ``sizes`` bytes, in
        the order of the offsets in ``meta['leaves']``: the header is sized
        and the frame in the segment invalidated; the shards' bytes then
        come piece by piece through the returned writer, whose ``seal``
        publishes the frame."""
        compute_crc = _crc_enabled()
        starts, rel = [], 0
        for n in sizes:
            starts.append(rel)
            rel += n
        if compute_crc:
            # reserve fixed-width CRC slots for every shard that maps onto
            # a buffer BEFORE sizing the header: real CRCs are stamped
            # after the data pass, and a 4-byte bin always packs to the
            # same length, so the header size (and thus every abs_offset)
            # stays stable across the re-pack
            expected = dict(zip(starts, sizes))
            for leaf in meta["leaves"]:
                for shard in leaf.get("shards", []):
                    if expected.get(shard["offset"]) == shard["nbytes"]:
                        shard["crc"] = b"\x00\x00\x00\x00"
                        shard["dig"] = b"\x00" * 8
        # offsets in meta are relative to data_start; rewrite the header
        # with absolute offsets until its own length (abs_offset adds
        # bytes) no longer moves them
        data_start = None
        header = pack_frame(meta)
        while len(header) != data_start:
            data_start = len(header)
            for leaf in meta["leaves"]:
                for shard in leaf.get("shards", []):
                    shard["abs_offset"] = data_start + shard["offset"]
            header = pack_frame(meta)
        if not self._ensure(data_start + rel):
            raise RuntimeError(f"cannot create shm segment {self._name}")
        # crash-consistent write order: invalidate the frame (zero length
        # word), write tensor data, write the meta bytes, then seal by
        # writing the length word LAST. A writer killed at any point leaves
        # an unreadable frame (read_meta -> None, callers fall back to the
        # last persisted checkpoint) — never a parseable header over torn
        # data. This is what makes it safe for the agent to SIGKILL a
        # wedged worker without a long graceful-exit grace. The length
        # word is the frame's COMMIT MARKER; the per-shard CRCs stamped
        # at the seal cover what the marker can't: corruption that happens
        # *after* a clean seal (bit rot, a stray writer) or a torn
        # replica/storage copy of a sealed frame.
        self._shm.buf[:8] = _U64.pack(0)
        return FrameWriter(self, meta, header, starts, sizes, compute_crc)

    def _maybe_inject_corruption(self, meta: Dict, data_start: int) -> None:
        """``shm.write`` injection site: mutate the sealed frame's data the
        way bit rot or a torn copy would — the seal stays valid, only the
        CRCs can catch it."""
        from dlrover_tpu.chaos import get_injector

        inj = get_injector()
        if inj is None:
            return
        act = inj.fire(ChaosSite.SHM_WRITE, step=meta.get("step"))
        if act is None:
            return
        shards = [
            (leaf.get("path", "?"), shard)
            for leaf in meta.get("leaves", [])
            for shard in leaf.get("shards", [])
            if "abs_offset" in shard and shard.get("nbytes", 0) > 0
        ]
        if not shards:
            return
        buf = self._shm.buf
        if act["kind"] == "torn":
            # zero the tail half of the LAST shard: a write that stopped
            # partway but was still sealed/copied as if complete
            path, shard = shards[-1]
            off, n = shard["abs_offset"], shard["nbytes"]
            cut = n // 2
            buf[off + cut : off + n] = bytes(n - cut)
        else:  # bitflip
            path, shard = shards[0]
            off, n = shard["abs_offset"], shard["nbytes"]
            at = off + int(act.get("rnd", 0.0) * max(1, n - 1))
            buf[at] = buf[at] ^ 0xFF
        logger.warning(
            "chaos: injected %s into shm frame %s shard %r (step %s)",
            act["kind"], self._name, path, meta.get("step"),
        )

    def write_raw(self, blob: bytes) -> None:
        """Write a complete pre-framed blob (e.g. a peer replica fetched
        over TCP) into the segment verbatim (same seal order as
        ``write_frame``: length word last)."""
        if not self._ensure(len(blob)):
            raise RuntimeError(f"cannot create shm segment {self._name}")
        buf = self._shm.buf
        buf[:8] = _U64.pack(0)
        buf[8 : len(blob)] = blob[8:]
        buf[:8] = blob[:8]

    # -- read --------------------------------------------------------------

    @staticmethod
    def _preadv_full(fd, buf, offset: int) -> bool:
        """Read exactly ``len(buf)`` bytes at ``offset``, looping over
        short reads: a single ``preadv`` caps at MAX_RW_COUNT (~2 GB on
        Linux), so one-shot reads silently truncate on multi-GB frames
        and would push them onto the slower mmap walk (``_shard_fd``)."""
        import os

        mv = memoryview(buf).cast("B")
        pos, n = 0, len(mv)
        while pos < n:
            try:
                got = os.preadv(fd, [mv[pos:]], offset + pos)
            except OSError:
                return False
            if got <= 0:
                return False
            pos += got
        return True

    def read_meta(self) -> Optional[Dict]:
        if not self.open():
            return None
        try:
            (meta_len,) = _U64.unpack(bytes(self._shm.buf[:8]))
            if meta_len == 0 or meta_len > self._shm.size:
                return None
            return msgpack.unpackb(
                bytes(self._shm.buf[8 : 8 + meta_len]), raw=False
            )
        except Exception:  # noqa: BLE001,DLR003 — torn/empty frame → None is the contract
            return None

    def read_shard_bytes(self, shard_meta: Dict):
        """Bytes of one shard. Returns a WRITABLE buffer (bytearray) when
        the pread fast path is available, so ``np.frombuffer`` views built
        on it need no defensive copy; falls back to an immutable ``bytes``
        copy off the mmap."""
        if not self.open():
            return None
        off = shard_meta["abs_offset"]
        n = shard_meta["nbytes"]
        fd = self._shard_fd()
        if fd is not None:
            buf = bytearray(n)
            if self._preadv_full(fd, buf, off):
                return buf
        return bytes(self._shm.buf[off : off + n])

    def read_shard_into(self, shard_meta: Dict, out,
                        offset: int = 0) -> bool:
        """Fill ``out`` (a writable contiguous buffer) with the shard's
        bytes from ``offset`` on — no allocation, so a restore that reads
        into warm staging skips the page population that dominates
        fresh-buffer reads (PERF.md section 6, PR 25). False when the
        range does not lie inside the shard."""
        if not self.open():
            return False
        mv = memoryview(out)
        if not mv.contiguous:
            return False
        mv = mv.cast("B")
        if offset < 0 or offset + mv.nbytes > shard_meta["nbytes"]:
            return False
        off = shard_meta["abs_offset"] + offset
        fd = self._shard_fd()
        if fd is not None and self._preadv_full(fd, mv, off):
            return True
        mv[:] = self._shm.buf[off : off + mv.nbytes]
        return True

    def read_frame_bytes(self):
        """The entire frame (header + data) for persisting as one blob
        (``bytes`` or ``bytearray``; None when no sealed frame exists)."""
        meta = self.read_meta()
        if meta is None:
            return None
        end = 8 + len(msgpack.packb(meta, use_bin_type=True))
        for leaf in meta["leaves"]:
            for shard in leaf.get("shards", []):
                end = max(end, shard["abs_offset"] + shard["nbytes"])
        fd = self._shard_fd()
        if fd is not None:
            buf = bytearray(end)
            if self._preadv_full(fd, buf, 0):
                # bytearray, not bytes: callers sendall/write it, and
                # a bytes() conversion would double multi-GB frames
                return buf
        return bytes(self._shm.buf[:end])

    @property
    def step(self) -> int:
        meta = self.read_meta()
        return int(meta["step"]) if meta else -1

    # -- integrity ---------------------------------------------------------

    def verify_frame(self) -> List[str]:
        """Names of shards whose stored CRC mismatches their bytes
        (``leafpath@offset``). Empty list ⇒ frame intact, no sealed frame,
        or a pre-CRC frame (no stamps to check).

        CRCs stream zero-copy over the mapped segment (memoryview slices,
        no ``read_shard_bytes`` allocation): the pre-restore check must
        cost memory-bandwidth, not a second pass through the restore read
        channel."""
        meta = self.read_meta()
        if meta is None:
            return []
        buf = self._shm.buf

        def _view(shard_meta: Dict):
            off = shard_meta["abs_offset"]
            n = shard_meta["nbytes"]
            if off + n > len(buf):
                return None  # shard extends past the segment: torn
            return buf[off : off + n]

        return _verify_shards(meta, _view)


class FrameWriter:
    """The data pass and the seal of one frame
    (``SharedMemoryHandler.open_frame``). A shard's bytes arrive in one
    piece or in several, each shard's in order, and its CRC32 and Adler32
    are carried from piece to piece: the sealed frame is the same, byte
    for byte, whatever the pieces were."""

    def __init__(self, handler: SharedMemoryHandler, meta: Dict,
                 header: bytes, starts: List[int], sizes: List[int],
                 compute_crc: bool):
        self._handler = handler
        self._meta = meta
        self._header = header
        self._starts = starts
        self._sizes = sizes
        self._compute_crc = compute_crc
        self._written = [0] * len(sizes)
        self._crc = [zlib.crc32(b"")] * len(sizes)
        self._adler = [zlib.adler32(b"")] * len(sizes)
        self._copy_s = self._checksum_s = 0.0

    def write(self, shard: int, offset: int, data) -> None:
        """The next bytes of ``shard``: they start at ``offset`` in it,
        which is where its last piece ended."""
        t_start = time.monotonic()
        flat = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
        n = flat.nbytes
        if (offset != self._written[shard]
                or offset + n > self._sizes[shard]):
            raise ValueError(
                f"shard {shard}: a piece of {n} bytes at {offset}, after "
                f"{self._written[shard]} of {self._sizes[shard]} bytes")
        if n:
            # ``copyto`` lets other threads run while it copies; a slice
            # assignment to the memoryview holds the interpreter lock for
            # all of it (PERF.md section 6, PR 24)
            np.copyto(np.frombuffer(
                self._handler._shm.buf, np.uint8, n,
                len(self._header) + self._starts[shard] + offset), flat)
        t_copied = time.monotonic()
        if self._compute_crc:
            self._crc[shard] = zlib.crc32(flat, self._crc[shard])
            self._adler[shard] = zlib.adler32(flat, self._adler[shard])
        self._written[shard] = offset + n
        self._copy_s += t_copied - t_start
        self._checksum_s += time.monotonic() - t_copied

    def seal(self) -> Dict[str, float]:
        """Stamp the checksums, write the header and, last, the length
        word. Returns ``copy_s`` and ``checksum_s`` summed over the
        pieces."""
        if self._written != self._sizes:
            raise ValueError(
                f"frame sealed with {sum(self._written)} of "
                f"{sum(self._sizes)} data bytes written")
        meta, header = self._meta, self._header
        if self._compute_crc:
            crcs = {
                rel: (crc & 0xFFFFFFFF, adler & 0xFFFFFFFF)
                for rel, crc, adler in zip(
                    self._starts, self._crc, self._adler)
            }
            for leaf in meta["leaves"]:
                for shard in leaf.get("shards", []):
                    stamp = crcs.get(shard["offset"])
                    if stamp is not None and "crc" in shard:
                        shard["crc"] = _CRC.pack(stamp[0])
                    if stamp is not None and "dig" in shard:
                        shard["dig"] = _DIG.pack(*stamp)
            sealed = pack_frame(meta)
            assert len(sealed) == len(header), "CRC stamp changed header size"
            header = sealed
        buf = self._handler._shm.buf
        buf[8 : len(header)] = header[8:]
        buf[:8] = header[:8]
        self._handler._maybe_inject_corruption(meta, len(header))
        return {"copy_s": self._copy_s, "checksum_s": self._checksum_s}


def parse_frame(blob: bytes) -> Optional[Dict]:
    """Parse a persisted frame file back into (meta, memoryview-able bytes)."""
    if len(blob) < 8:
        return None
    (meta_len,) = _U64.unpack(blob[:8])
    if 8 + meta_len > len(blob):
        return None
    meta = msgpack.unpackb(blob[8 : 8 + meta_len], raw=False)
    meta["_blob"] = blob
    return meta


def frame_shard_bytes(meta: Dict, shard_meta: Dict) -> memoryview:
    """One shard of a parsed frame, as a window onto the blob: no copy
    (a ``bytes`` slice would put every shard into fresh pages)."""
    off = shard_meta["abs_offset"]
    return memoryview(meta["_blob"])[off : off + shard_meta["nbytes"]]


# threads of the CRC pass over a frame: ``zlib.crc32`` releases the
# interpreter lock, so the shards of a frame are checked side by side
_VERIFY_THREADS = 8


def _verify_shards(meta: Dict, read: Callable[[Dict], Any]) -> List[str]:
    """Names (``leafpath@offset``, in the frame's order) of the stamped
    shards whose bytes do not give their CRC. Every stamped shard is
    checked and the pass ends before it returns: largest shards first
    over ``_VERIFY_THREADS`` threads, since one thread a frame made the
    check a fifth of a restore (PERF.md section 6, PR 25)."""
    stamped = [
        (leaf, shard)
        for leaf in meta.get("leaves", [])
        for shard in leaf.get("shards", [])
        if shard.get("crc") and "abs_offset" in shard
    ]

    def intact(shard: Dict) -> bool:
        data = read(shard)
        return (data is not None
                and (zlib.crc32(data) & 0xFFFFFFFF)
                == _CRC.unpack(shard["crc"])[0])

    by_size = sorted(stamped, key=lambda ls: -int(ls[1].get("nbytes", 0)))
    with ThreadPoolExecutor(
        min(_VERIFY_THREADS, max(1, len(stamped))),
        thread_name_prefix="ckpt-verify",
    ) as pool:
        corrupt = {
            id(shard) for (_, shard), good in zip(
                by_size, pool.map(intact, [shard for _, shard in by_size]))
            if not good
        }
    return [
        f"{leaf.get('path', '?')}@{shard['offset']}"
        for leaf, shard in stamped if id(shard) in corrupt
    ]


def verify_parsed_frame(meta: Dict) -> List[str]:
    """CRC-check a :func:`parse_frame` result (storage/replica blob);
    returns the corrupt shard names (``leafpath@offset``)."""
    return _verify_shards(meta, lambda shard: frame_shard_bytes(meta, shard))


def verify_frame_blob(blob) -> List[str]:
    """CRC-check a raw frame blob end-to-end. An unparseable blob counts
    as one corrupt '<frame>' entry (its seal/commit-marker is broken)."""
    try:
        meta = parse_frame(bytes(blob) if not isinstance(blob, bytes)
                           else blob)
    except Exception:  # noqa: BLE001,DLR003 — torn header counted as corrupt below
        meta = None
    if meta is None:
        return ["<frame>"]
    return verify_parsed_frame(meta)
