"""Incremental, crash-consistent checkpoint chains (the manifest plane).

Flash Checkpoint's cold path used to persist every frame whole through a
single serial writer (86 MB/s for a 3 GB host-resident state; CPU
sandbox, before PR 9), and also the fragile path: a saver killed
mid-persist left the step whole-or-nothing. This module replaces it with
delta chains (FastPersist, arxiv 2406.13768, motivates decoupled parallel checkpoint
writes; ElasWave, arxiv 2510.00606, the graded-recovery framing):

- **dirty-shard deltas**: the saver compares per-shard content digests
  (``dig`` stamps in the sealed frame meta, shm_handler.py) against the
  chain tip and persists only changed shards;
- **manifest chain**: each step commits one *link* per frame
  (``manifest_<node>_<local>.mf``) carrying the frame header, per-shard
  CRCs/digests, the parent link's digest, and a **fully resolved** shard
  map — unchanged shards point into ancestor steps' payload files, so the
  tip link alone locates every byte while the digest walk tip→base proves
  the chain was never torn;
- **striped parallel persist/restore**: payloads are written through
  ``CheckpointStorage.write_stripes`` (parallel pwrite on POSIX) and read
  back with ranged ``read_at`` fan-out, so cold I/O scales with shard
  count instead of one stream;
- **bounded chains**: after ``CKPT_CHAIN_MAX`` delta links the next save
  full-rebases (a fresh base link), and :func:`gc_step` deletes only
  artifacts unreachable from every live link.

Commit protocol (the ONE place checkpoint artifacts become visible):
payload files are written in place (their visibility is gated by the
manifest), then the link commits via :func:`commit_file` — write-temp →
flush+fsync → atomic ``safe_move`` — so a crash at any point leaves either
the old chain tip or the new one, never a half-link. Chaos sites:
``storage.persist`` fires before every payload stripe write,
``storage.commit`` between the link's temp write and its atomic replace.

Recovery walks step dirs newest-first; a candidate is restorable only when
every expected link is present, its digest walk reaches a base, and every
referenced payload range CRC-verifies. Any failure raises
:class:`ChainError` with a reason the caller journals as
``ckpt_chain_truncated`` before falling back link-by-link.

GC/restore concurrency invariant: :func:`gc_step` removes a victim step's
*link files first* (so a concurrent restore skips the candidate outright),
then payloads not referenced by any live link; a restore already past the
link read can at worst hit a missing payload, which is a journaled
truncation, never a wrong load.
"""

import hashlib
import os
import struct
import zlib
from typing import Callable, Dict, List, Optional, Tuple

import msgpack

from dlrover_tpu.common.constants import (
    ChaosSite,
    CheckpointConstant,
    ConfigKey,
    env_flag,
    env_int,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.storage import (
    CheckpointStorage,
    get_checkpoint_storage,
)

_U64 = struct.Struct("<Q")
_MANIFEST_VERSION = 1


def delta_enabled() -> bool:
    return env_flag(ConfigKey.CKPT_DELTA, default=True)


def chain_max() -> int:
    """Delta links allowed before the next save full-rebases."""
    return max(1, env_int(ConfigKey.CKPT_CHAIN_MAX, 8))


def stripe_bytes() -> int:
    return max(1 << 20, env_int(ConfigKey.CKPT_STRIPE_BYTES, 64 << 20))


# -- layout -----------------------------------------------------------------


def step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"step_{step:08d}")


def frame_file(ckpt_dir: str, step: int, node_rank: int,
               local_rank: int) -> str:
    return os.path.join(
        step_dir(ckpt_dir, step),
        f"frame_{node_rank}_{local_rank}{CheckpointConstant.FRAME_SUFFIX}",
    )


def manifest_file(ckpt_dir: str, step: int, node_rank: int,
                  local_rank: int) -> str:
    return os.path.join(
        step_dir(ckpt_dir, step),
        f"{CheckpointConstant.MANIFEST_PREFIX}{node_rank}_{local_rank}"
        f"{CheckpointConstant.MANIFEST_SUFFIX}",
    )


def delta_file(ckpt_dir: str, step: int, node_rank: int, local_rank: int,
               key: int) -> str:
    return os.path.join(
        step_dir(ckpt_dir, step),
        f"{CheckpointConstant.DELTA_PREFIX}{node_rank}_{local_rank}"
        f"_{key:016d}.bin",
    )


def data_state_file(ckpt_dir: str, step: int) -> str:
    """The elastic data plane's shard-ledger sidecar: one JSON blob per
    step dir (rank 0 writes it) holding the master's whole dispatch
    position (master/task_manager.py ``export_data_state``). It rides
    the step dir's lifecycle — compaction/GC that drops the step drops
    the sidecar — so ``engine.load`` restores the ledger from exactly
    the step the model chain landed on (mid-epoch exactly-once resume)."""
    return os.path.join(step_dir(ckpt_dir, step), "data_state.json")


def write_data_state(ckpt_dir: str, step: int, content: str,
                     storage: Optional[CheckpointStorage] = None) -> str:
    """Commit the ledger sidecar with the DLR012 atomic discipline
    (write-temp → ``storage.commit`` chaos site → safe_move)."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    path = data_state_file(ckpt_dir, step)
    storage.safe_makedirs(os.path.dirname(path))
    commit_file(storage, content.encode("utf-8"), path,
                kind="data_state", step=step)
    return path


def read_data_state(ckpt_dir: str, step: int) -> Optional[str]:
    """The sidecar's content at ``step``, or None when the chain predates
    the data plane (model-only restore stays valid)."""
    path = data_state_file(ckpt_dir, step)
    if not os.path.exists(path):
        return None
    with open(path, "rb") as f:
        return f.read().decode("utf-8")


def parse_manifest_name(name: str) -> Optional[Tuple[int, int]]:
    """``manifest_<node>_<local>.mf`` → (node, local), else None."""
    pre, suf = (CheckpointConstant.MANIFEST_PREFIX,
                CheckpointConstant.MANIFEST_SUFFIX)
    if not (name.startswith(pre) and name.endswith(suf)):
        return None
    body = name[len(pre):-len(suf)]
    node, sep, local = body.partition("_")
    if not sep:
        return None
    try:
        return int(node), int(local)
    except ValueError:
        return None


def list_step_dirs(ckpt_dir: str,
                   storage: Optional[CheckpointStorage] = None) -> List[int]:
    """Step numbers with a ``step_%08d`` dir, newest first."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    steps = []
    for name in storage.listdir(ckpt_dir):
        if not name.startswith("step_"):
            continue
        try:
            steps.append(int(name[5:]))
        except ValueError:
            continue
    return sorted(steps, reverse=True)


class ChainError(Exception):
    """A manifest chain failed verification; ``reason`` is the journaled
    truncation cause."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"{reason}: {detail}" if detail else reason)
        self.reason = reason


# -- commit helper ----------------------------------------------------------


def commit_file(storage: CheckpointStorage, content, path: str,
                **ctx) -> None:
    """THE atomic-commit primitive for checkpoint/manifest artifacts:
    write-temp (durable — ``storage.write`` fsyncs on POSIX) → chaos site
    ``storage.commit`` → atomic ``safe_move``. Rule DLR012 flags renames of
    checkpoint artifacts that bypass this discipline."""
    from dlrover_tpu.chaos import get_injector

    tmp = path + ".tmp"
    storage.write(content, tmp)
    inj = get_injector()
    if inj is not None:
        inj.fire(ChaosSite.STORAGE_COMMIT, path=path, **ctx)
    storage.safe_move(tmp, path)


def _link_digest(link_bytes) -> bytes:
    return hashlib.sha1(bytes(link_bytes)).digest()


# -- persist ----------------------------------------------------------------


def _frame_shards(meta: Dict, blob) -> List[Dict]:
    """Flatten the sealed meta's shards into manifest form: one record per
    shard keyed by its data-relative offset, with crc/dig taken from the
    seal stamps or computed from the blob when CRC stamping was disabled."""
    from dlrover_tpu.ckpt.shm_handler import shard_digest

    mv = memoryview(blob)
    out = []
    for leaf in meta.get("leaves", []):
        for shard in leaf.get("shards", []):
            if "abs_offset" not in shard or shard.get("nbytes", 0) <= 0:
                continue
            off, n = shard["abs_offset"], shard["nbytes"]
            stamp = shard.get("crc")
            crc = (
                struct.unpack(">I", stamp)[0] if stamp
                else zlib.crc32(mv[off:off + n]) & 0xFFFFFFFF
            )
            dig = shard.get("dig") or shard_digest(mv[off:off + n])
            out.append({
                "k": shard["offset"], "abs": off, "n": n,
                "crc": crc, "dig": bytes(dig),
            })
    return out


def _chunks(total: int, size: int) -> List[Tuple[int, int]]:
    return [(off, min(size, total - off)) for off in range(0, total, size)]


def _run_jobs(jobs: List[Callable[[], None]], executor) -> None:
    if executor is None or len(jobs) <= 1:
        for job in jobs:
            job()
        return
    futures = [executor.submit(job) for job in jobs]
    for f in futures:
        f.result()


def persist_frame(
    storage: CheckpointStorage,
    ckpt_dir: str,
    step: int,
    meta: Dict,
    blob,
    prev_state: Optional[Dict] = None,
    executor=None,
) -> Dict:
    """Persist one sealed frame as a chain link: a delta when the previous
    tip covers the same shard set and the chain is still short, a full
    base otherwise. Returns the new chain state (the caller caches it and
    passes it back as ``prev_state`` next step).

    Crash consistency: all payload bytes land (durably) before the link
    commits; a kill anywhere leaves the previous tip intact.
    """
    node, local = meta["node_rank"], meta["local_rank"]
    (meta_len,) = _U64.unpack(bytes(blob[:8]))
    hdr = bytes(blob[:8 + meta_len])
    shards = _frame_shards(meta, blob)
    total = max((s["abs"] + s["n"] for s in shards), default=0)
    total = max(total, len(hdr))
    digests = {s["k"]: s["dig"] for s in shards}
    sizes = {s["k"]: s["n"] for s in shards}

    if prev_state is None:
        prev_state = load_chain_state(ckpt_dir, node, local, storage=storage)
    as_delta = (
        delta_enabled()
        and prev_state is not None
        and prev_state["step"] < step
        and prev_state.get("sizes") == sizes
        and set(prev_state.get("digests", {})) == set(digests)
        and prev_state.get("chain_len", 0) < chain_max()
    )

    d = step_dir(ckpt_dir, step)
    storage.safe_makedirs(d)
    mv = memoryview(blob)
    entries: Dict[int, Dict] = {}
    ctx = {"step": step, "frame": f"{node}_{local}"}
    if as_delta:
        kind = "delta"
        dirty = [
            k for k in digests if prev_state["digests"][k] != digests[k]
        ]
        jobs = []
        for s in shards:
            k = s["k"]
            if k not in dirty:
                prev_e = prev_state["entries"][k]
                entries[k] = dict(prev_e, crc=s["crc"], dig=s["dig"])
                continue
            path = delta_file(ckpt_dir, step, node, local, k)
            data = mv[s["abs"]:s["abs"] + s["n"]]
            stripes = [
                (off, data[off:off + n], ctx)
                for off, n in _chunks(s["n"], stripe_bytes())
            ]
            entries[k] = {
                "k": k, "f": os.path.relpath(path, ckpt_dir), "o": 0,
                "n": s["n"], "crc": s["crc"], "dig": s["dig"], "s": step,
            }
            jobs.append(
                lambda p=path, n=s["n"], st=stripes:
                storage.write_stripes(p, n, st)
            )
        # one dirty shard: stripe WITHIN the file; many: fan out across
        # files (never both on the shared executor — a job waiting on
        # sub-jobs in the same pool can deadlock it)
        if len(jobs) == 1 and executor is not None:
            path = delta_file(ckpt_dir, step, node, local, dirty[0])
            s = next(s for s in shards if s["k"] == dirty[0])
            data = mv[s["abs"]:s["abs"] + s["n"]]
            stripes = [
                (off, data[off:off + n], ctx)
                for off, n in _chunks(s["n"], stripe_bytes())
            ]
            storage.write_stripes(path, s["n"], stripes, executor=executor)
        else:
            _run_jobs(jobs, executor)
        bytes_written = sum(sizes[k] for k in dirty)
        parent_step = prev_state["step"]
        parent_digest = prev_state["link_digest"]
        chain_len = prev_state["chain_len"] + 1
    else:
        kind = "base"
        dirty = sorted(digests)
        path = frame_file(ckpt_dir, step, node, local)
        stripes = [
            (off, mv[off:off + n], ctx)
            for off, n in _chunks(total, stripe_bytes())
        ]
        storage.write_stripes(path, total, stripes, executor=executor)
        rel = os.path.relpath(path, ckpt_dir)
        for s in shards:
            entries[s["k"]] = {
                "k": s["k"], "f": rel, "o": s["abs"], "n": s["n"],
                "crc": s["crc"], "dig": s["dig"], "s": step,
            }
        bytes_written = total
        parent_step = -1
        parent_digest = b""
        chain_len = 1

    link = {
        "v": _MANIFEST_VERSION,
        "step": step,
        "kind": kind,
        "node": node,
        "local": local,
        "expected_frames": int(meta.get("expected_frames") or 1),
        "parent_step": parent_step,
        "parent_digest": parent_digest,
        "chain_len": chain_len,
        "hdr": hdr,
        "total": total,
        "dirty": sorted(dirty),
        "shards": [entries[k] for k in sorted(entries)],
    }
    link_bytes = msgpack.packb(link, use_bin_type=True)
    commit_file(storage, link_bytes, manifest_file(ckpt_dir, step, node,
                                                   local), **ctx)
    logger.info(
        "persisted %s link for frame %s_%s step %s: %d/%d shard(s), "
        "%.1f MB of %.1f MB",
        kind, node, local, step, len(dirty), len(shards),
        bytes_written / 1e6, total / 1e6,
    )
    return {
        "step": step,
        "node": node,
        "local": local,
        "kind": kind,
        "digests": digests,
        "sizes": sizes,
        "entries": entries,
        "chain_len": chain_len,
        "link_digest": _link_digest(link_bytes),
        "bytes_written": bytes_written,
        "bytes_total": total,
    }


# -- chain walk / restore ---------------------------------------------------


def _read_link(storage: CheckpointStorage, ckpt_dir: str, step: int,
               node: int, local: int) -> Optional[Tuple[Dict, bytes]]:
    blob = storage.read(manifest_file(ckpt_dir, step, node, local))
    if blob is None:
        return None
    try:
        link = msgpack.unpackb(bytes(blob), raw=False)
    except Exception:  # noqa: BLE001 — a torn link is a chain failure, not a crash
        logger.warning("manifest link for step %s (%s_%s) is unparseable; "
                       "treating as uncommitted", step, node, local)
        return None
    if not isinstance(link, dict) or link.get("v") != _MANIFEST_VERSION:
        return None
    return link, bytes(blob)


def verify_chain(storage: CheckpointStorage, ckpt_dir: str,
                 link: Dict) -> int:
    """Walk ``link``'s parents to its base, verifying every link digest.
    Returns the base step; raises :class:`ChainError` on a torn chain."""
    node, local = link["node"], link["local"]
    cur = link
    hops = 0
    while cur["kind"] != "base":
        if hops > 100000:
            raise ChainError("chain_cycle", f"frame {node}_{local}")
        got = _read_link(storage, ckpt_dir, cur["parent_step"], node, local)
        if got is None:
            raise ChainError(
                "missing_link",
                f"frame {node}_{local} parent step {cur['parent_step']}",
            )
        parent, parent_bytes = got
        if _link_digest(parent_bytes) != cur["parent_digest"]:
            raise ChainError(
                "link_digest_mismatch",
                f"frame {node}_{local} parent step {cur['parent_step']}",
            )
        cur = parent
        hops += 1
    return cur["step"]


def load_chain_state(ckpt_dir: str, node: int, local: int,
                     storage: Optional[CheckpointStorage] = None
                     ) -> Optional[Dict]:
    """Rebuild the saver's chain state for one frame from storage (cold
    start / restarted agent): the newest step whose link for this frame
    verifies becomes the tip the next delta chains onto."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    for step in list_step_dirs(ckpt_dir, storage):
        got = _read_link(storage, ckpt_dir, step, node, local)
        if got is None:
            continue
        link, link_bytes = got
        try:
            verify_chain(storage, ckpt_dir, link)
        except ChainError as e:
            logger.warning(
                "chain tip at step %s for frame %s_%s unusable (%s) — "
                "scanning older links", step, node, local, e.reason,
            )
            continue
        entries = {e["k"]: dict(e) for e in link["shards"]}
        return {
            "step": link["step"],
            "node": node,
            "local": local,
            "kind": link["kind"],
            "digests": {e["k"]: bytes(e["dig"]) for e in link["shards"]},
            "sizes": {e["k"]: e["n"] for e in link["shards"]},
            "entries": entries,
            "chain_len": link["chain_len"],
            "link_digest": _link_digest(link_bytes),
            "bytes_written": 0,
            "bytes_total": link["total"],
        }
    return None


def _reconstruct_frame(storage: CheckpointStorage, ckpt_dir: str,
                       link: Dict, executor=None) -> Dict:
    """Rebuild one frame blob from a verified link: header + every shard
    read (striped, in parallel) from whichever payload file its entry
    resolves to, CRC-checked as it lands."""
    from dlrover_tpu.ckpt.shm_handler import parse_frame

    hdr = bytes(link["hdr"])
    blob = bytearray(link["total"])
    blob[:len(hdr)] = hdr
    meta = msgpack.unpackb(hdr[8:], raw=False)
    abs_by_key = {
        shard["offset"]: shard["abs_offset"]
        for leaf in meta.get("leaves", [])
        for shard in leaf.get("shards", [])
        if "abs_offset" in shard
    }

    def _fill(entry: Dict) -> None:
        abs_off = abs_by_key.get(entry["k"])
        if abs_off is None:
            raise ChainError(
                "shard_key_unknown",
                f"step {link['step']} shard {entry['k']}",
            )
        data = storage.read_at(
            os.path.join(ckpt_dir, entry["f"]), entry["o"], entry["n"]
        )
        if data is None:
            raise ChainError(
                "missing_payload",
                f"step {link['step']} shard {entry['k']} ← {entry['f']}",
            )
        if (zlib.crc32(data) & 0xFFFFFFFF) != entry["crc"]:
            raise ChainError(
                "payload_crc_mismatch",
                f"step {link['step']} shard {entry['k']} ← {entry['f']}",
            )
        blob[abs_off:abs_off + entry["n"]] = data

    _run_jobs(
        [lambda e=e: _fill(e) for e in link["shards"]], executor
    )
    frame = parse_frame(bytes(blob))
    if frame is None:
        raise ChainError("frame_unparseable", f"step {link['step']}")
    return frame


def manifest_links(ckpt_dir: str, step: int,
                   storage: Optional[CheckpointStorage] = None
                   ) -> List[Dict]:
    """Parsed manifest links present for ``step`` (unverified)."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    links = []
    for name in storage.listdir(step_dir(ckpt_dir, step)):
        who = parse_manifest_name(name)
        if who is None:
            continue
        got = _read_link(storage, ckpt_dir, step, *who)
        if got is not None:
            links.append(got[0])
    return links


def load_step_frames(ckpt_dir: str, step: int,
                     storage: Optional[CheckpointStorage] = None,
                     executor=None) -> List[Dict]:
    """Reconstruct every frame of ``step`` from its manifest chain.
    Raises :class:`ChainError` (with the truncation reason) when the step
    is not provably complete: missing/torn links, a broken digest walk,
    or any payload range that fails its CRC."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    links = manifest_links(ckpt_dir, step, storage)
    if not links:
        raise ChainError("no_committed_links", f"step {step}")
    expected = max(link["expected_frames"] for link in links)
    if len(links) < expected:
        raise ChainError(
            "incomplete_quorum",
            f"step {step}: {len(links)}/{expected} links",
        )
    for link in links:
        verify_chain(storage, ckpt_dir, link)
    pool = executor
    own_pool = None
    if pool is None:
        from concurrent.futures import ThreadPoolExecutor

        from dlrover_tpu.common.config import get_context

        own_pool = ThreadPoolExecutor(
            max_workers=get_context().ckpt_save_workers,
            thread_name_prefix="ckpt-chain-read",
        )
        pool = own_pool
    try:
        # parallelism lives INSIDE each frame's striped reads; frames are
        # reconstructed serially so the shared pool never waits on itself
        return [
            _reconstruct_frame(storage, ckpt_dir, link, executor=pool)
            for link in links
        ]
    finally:
        if own_pool is not None:
            own_pool.shutdown(wait=False)


def _chain_artifacts(names: List[str]) -> Dict[str, bool]:
    """Classify a step dir listing: does it hold manifest links, chain
    payload leftovers (delta files / temp links), or legacy frames?"""
    has = {"links": False, "chain_debris": False, "frames": False,
           "condemned": False}
    for name in names:
        if parse_manifest_name(name) is not None:
            has["links"] = True
        elif name == _GC_MARKER:
            has["condemned"] = True
        elif (name.startswith(CheckpointConstant.DELTA_PREFIX)
              or name.endswith(CheckpointConstant.MANIFEST_SUFFIX + ".tmp")):
            has["chain_debris"] = True
        elif name.endswith(CheckpointConstant.FRAME_SUFFIX):
            has["frames"] = True
    return has


def newest_candidate_step(ckpt_dir: str,
                          storage: Optional[CheckpointStorage] = None
                          ) -> int:
    """Newest step with at least one committed manifest link; -1 when the
    directory holds no chain-format checkpoints (legacy-only or empty)."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    for step in list_step_dirs(ckpt_dir, storage):
        has = _chain_artifacts(storage.listdir(step_dir(ckpt_dir, step)))
        if has["links"] and not has["condemned"]:
            return step
    return -1


def load_newest_chain(
    ckpt_dir: str,
    storage: Optional[CheckpointStorage] = None,
    on_truncate: Optional[Callable[[int, str], None]] = None,
    executor=None,
) -> Tuple[int, List[Dict]]:
    """The recovery walk: newest step dir first, fall back link-by-link to
    the last provably complete step. Every rejected candidate is reported
    via ``on_truncate(step, reason)`` (journaled as ``ckpt_chain_truncated``
    by the engine). Returns ``(-1, [])`` when no chain-format step is
    restorable — including the pure-legacy layout, which the storage rung
    below this one still handles."""
    storage = storage or get_checkpoint_storage(ckpt_dir)
    steps = list_step_dirs(ckpt_dir, storage)
    chain_in_use = any(
        _chain_artifacts(storage.listdir(step_dir(ckpt_dir, s)))["links"]
        for s in steps
    )
    if not chain_in_use:
        return -1, []
    for step in steps:
        names = storage.listdir(step_dir(ckpt_dir, step))
        has = _chain_artifacts(names)
        if has["condemned"]:
            # GC already condemned this step; its remnant links exist only
            # for live children's digest walks — not a restore candidate
            continue
        if not has["links"]:
            if has["chain_debris"] or has["frames"]:
                # a saver died between payload persist and link commit —
                # exactly the torn window the chaos drills SIGKILL into
                if on_truncate is not None:
                    on_truncate(step, "no_committed_links")
            continue
        try:
            frames = load_step_frames(ckpt_dir, step, storage,
                                      executor=executor)
        except ChainError as e:
            if on_truncate is not None:
                on_truncate(step, e.reason)
            continue
        return step, frames
    return -1, []


# -- GC ---------------------------------------------------------------------

_GC_MARKER = "._gc"


def _sweep_dir(storage: CheckpointStorage, ckpt_dir: str, step: int,
               needed_links, needed_files) -> int:
    """One reachability sweep over a condemned step dir: remove every link
    not on a live tip's digest walk and every payload no live link's shard
    map resolves into. Links go first (a concurrent restore then skips the
    step as a candidate instead of finding a link over vanishing payloads).
    Returns the count of artifacts that had to be kept; when zero the dir
    is removed outright, otherwise a ``._gc`` marker condemns it so a later
    GC pass re-sweeps it once its dependents are themselves collected."""
    d = step_dir(ckpt_dir, step)
    names = storage.listdir(d)
    kept = 0
    # pass 1: unneeded links (drop the step as a restore candidate)
    for name in names:
        who = parse_manifest_name(name)
        if who is None:
            continue
        if (step, who[0], who[1]) in needed_links:
            kept += 1
        else:
            storage.safe_remove(os.path.join(d, name))
    # pass 2: payloads not referenced by any live link
    rel_dir = os.path.basename(d)
    for name in names:
        if parse_manifest_name(name) is not None:
            continue
        full = os.path.join(d, name)
        if name == CheckpointConstant.DONE_DIR:
            storage.safe_rmtree(full)
            continue
        if name == _GC_MARKER:
            continue
        if os.path.join(rel_dir, name) in needed_files:
            kept += 1
            continue
        storage.safe_remove(full)
    if kept == 0:
        storage.safe_rmtree(d)
    else:
        commit_file(storage, "condemned", os.path.join(d, _GC_MARKER),
                    step=step)
    return kept


def gc_step(storage: CheckpointStorage, ckpt_dir: str,
            victim_step: int) -> None:
    """Reachability-aware deletion of one checkpoint step: never removes a
    link on any live tip's digest walk, nor a payload file any live link's
    shard map still resolves into. A victim whose artifacts are still
    needed by a younger chain is condemned (``._gc`` marker) instead of
    half-deleted forever: every GC invocation re-sweeps previously
    condemned dirs, so orphaned remnants converge to zero once their
    dependents are themselves collected."""
    sweep = {victim_step}
    live_steps = []
    for s in list_step_dirs(ckpt_dir, storage):
        if s == victim_step:
            continue
        if _chain_artifacts(storage.listdir(step_dir(ckpt_dir, s)))[
                "condemned"]:
            sweep.add(s)
        else:
            live_steps.append(s)
    needed_links = set()
    needed_files = set()
    for s in live_steps:
        for link in manifest_links(ckpt_dir, s, storage):
            node, local = link["node"], link["local"]
            for entry in link["shards"]:
                needed_files.add(entry["f"])
            cur = link
            hops = 0
            while cur["kind"] != "base" and hops < 100000:
                needed_links.add((cur["parent_step"], node, local))
                got = _read_link(storage, ckpt_dir, cur["parent_step"],
                                 node, local)
                if got is None:
                    break
                cur = got[0]
                hops += 1
    for s in sorted(sweep):
        kept = _sweep_dir(storage, ckpt_dir, s, needed_links, needed_files)
        logger.info("gc step %s: kept %d reachable artifact(s)", s, kept)
