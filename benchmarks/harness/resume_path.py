"""Kill to first resumed step, cut where the layers of the path meet.

``jobs/resume.py`` hands back what each process wrote about a kill, as
``ctx["resume"]``::

    {"kills": [{"t": ..., "wall_minus_monotonic": ...}, ...],
     "worker": [{"event": ..., "inc": n, "t": ...}, ...],
     "agent_events": [{"name": ..., "phase": ..., "ts": ...}, ...],
     "agent_log": "<the agent's standard error>",
     "rings": {"1": [<Span.to_dict>, ...]}}

``kills`` are the benchmark's own stamps (``time.monotonic()`` as the
signal is sent); ``worker`` the worker script's lines (``t`` is
``time.monotonic()`` of the same host, ``inc`` the agent's restart count
as ``worker.init()`` read it); ``agent_events`` the records the agent's
event emitter writes where ``DLROVER_TPU_EVENT_DIR`` is set
(``common/event.py``; ``ts`` is ``time.time()``); ``agent_log`` its log
lines (``common/log.py``: ``[<asctime>,<ms>] [LEVEL] [file:line:func]
message``, local time); ``rings`` the tracer's ring of each resumed
worker. A wall stamp is put on the monotonic clock with the offset the
benchmark read as it sent the signal.

The path of kill ``k`` (the ``k``-th of the run; the worker that answers
it has ``inc == k``), its boundaries in order:

    kill               the benchmark sends SIGKILL to the worker
    death_recorded     agent: ``agent#worker_fail`` (``_handle_worker_failure``,
                       reached when ``Popen.poll`` first answers)
    restart_begun      agent: ``agent#restart`` (``_restart_workers``)
    persisted          agent: log line ``breakpoint save (...): persisted``
                       (``AsyncCheckpointSaver.save_shm_to_storage`` opens
                       no span on this path: PERF.md section 7)
    worker_started     worker script's first line
    init_returned      ``worker.init()`` returned (``t_init``)
    backend_up         ``jax.devices()`` answered: the TPU runtime is up
    restore_begun      state made from the seed, trainer built
    restore_done       ``Checkpointer.load_checkpoint`` and ``block_until_ready``
    first_step         the first step's loss is on the host

and the parts the readers report (seconds, the mean over the run's kills):

    resume.detect_s      kill -> death_recorded
    resume.persist_s     restart_begun -> persisted, as far as it lies
                         before worker_started
    resume.relaunch_s    death_recorded -> worker_started, less persist_s
    resume.bootstrap_s   worker_started -> init_returned
    resume.backend_s     init_returned -> backend_up
    resume.state_s       backend_up -> restore_begun
    resume.restore_s     the resumed worker's ``ckpt.restore`` span
    resume.first_step_s  restore_done -> first_step
    resume.wall_s        kill -> first_step: what a user waits
    resume.program_s     the six parts in which the repository's code runs

The eight parts are cut end to end, so their sum is ``wall_s`` whatever
runs beside what: ``persist_s`` is the part of the persist that holds the
relaunch up, and a persist that runs on beside the new worker's start
(PERF.md section 7 queues that) takes nothing from the parts after
``worker_started``; ``persist_total_s`` in a row is the whole of it.
``remainder_s`` is what of ``wall_s`` no part covers (the restore span
against the stamps around it). In ``detect_s`` and ``backend_s`` no line
of this repository runs but the agent's poll of ``Popen.poll`` every
0.2 s: the kernel tears down a killed process that holds some ten
gigabytes and a TPU, and until it has, ``waitpid`` has nothing to report;
then libtpu starts the runtime on the chip. ``program_s`` is the other
six, for a reader who wants the program's share steady (PERF.md section
2); the wall time is ``wall_s`` and nothing else is called that.

A part with a boundary missing in any kill is ``None``: a mean over the
kills that happen to be whole would be of an unknown part of the run.
"""

import re
import time
from typing import Dict, List, Optional

# the parts in which the repository's code runs, then the platform's two
# waits: the eight together are cut end to end from kill to first step
PROGRAM = ("persist_s", "relaunch_s", "bootstrap_s", "state_s", "restore_s",
           "first_step_s")
PLATFORM = ("detect_s", "backend_s")
PARTS = PROGRAM + PLATFORM
PERSISTED = re.compile(
    r"^\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\] .*"
    r"breakpoint save \(.*\): persisted \d+ frame", re.M)


def _log_stamps(text: str) -> List[float]:
    """``time.time()`` of every ``persisted`` line of the agent's log."""
    return [time.mktime(time.strptime(stamp, "%Y-%m-%d %H:%M:%S"))
            + int(ms) / 1e3 for stamp, ms in PERSISTED.findall(text or "")]


def _first(stamps, lo, hi) -> Optional[float]:
    inside = [t for t in stamps if lo <= t <= hi]
    return min(inside) if inside else None


def boundaries(resume: dict) -> List[Dict[str, Optional[float]]]:
    """One dict a kill, every boundary on the host's monotonic clock or
    ``None`` where no process wrote it."""
    out = []
    for k, kill in enumerate(resume.get("kills", []), start=1):
        mine = [e for e in resume.get("worker", []) if e.get("inc") == k]

        def worker(event, key="t"):
            found = [e.get(key) for e in mine if e["event"] == event]
            return found[0] if found else None

        b = {"kill": kill["t"], "worker_started": worker("start"),
             "init_returned": worker("backend", "t_init"),
             "backend_up": worker("backend"),
             "restore_begun": worker("restore", "t_begun"),
             "restore_done": worker("restore"),
             "first_step": worker("first_step")}
        # the agent's stamps that lie between this kill and its answer
        hi = b["first_step"] if b["first_step"] is not None else float("inf")
        off = kill["wall_minus_monotonic"]

        def agent(name):
            return _first([r["ts"] - off for r in resume.get(
                "agent_events", []) if r.get("name") == name],
                kill["t"], hi)

        b["death_recorded"] = agent("agent#worker_fail")
        b["restart_begun"] = agent("agent#restart")
        # a persist may outlast the first step once it runs beside the
        # relaunch: the first one logged after this kill's restart is its
        later = [r["t"] for r in resume["kills"][k:]]
        b["persisted"] = _first(
            [t - off for t in _log_stamps(resume.get("agent_log"))],
            # the log's stamp is cut to the millisecond
            (b["restart_begun"] or kill["t"]) - 1e-3,
            min(later) if later else float("inf"))
        out.append(b)
    return out


def _restore_span_s(resume: dict, k: int) -> Optional[float]:
    ring = (resume.get("rings") or {}).get(str(k))
    found = [sp for sp in ring or [] if sp["name"] == "ckpt.restore"
             and sp.get("end_t") is not None]
    return found[0]["end_t"] - found[0]["start_t"] if found else None


def _between(b, lo, hi) -> Optional[float]:
    return None if b[lo] is None or b[hi] is None else b[hi] - b[lo]


def waterfall(resume: dict) -> List[Dict[str, Optional[float]]]:
    """One dict a kill: ``wall_s``, every part (``None`` where a boundary
    is missing), ``persist_total_s``, ``program_s`` (``None`` unless its
    six parts are there) and ``remainder_s``, what of ``wall_s`` no part
    covers (``None`` unless every part is there)."""
    rows = []
    for k, b in enumerate(boundaries(resume), start=1):
        persist = _between(b, "restart_begun", "persisted")
        gone = _between(b, "death_recorded", "worker_started")
        before_start = None
        if persist is not None and b["worker_started"] is not None:
            # the part of the persist that holds the relaunch up
            before_start = max(0.0, min(
                persist, b["worker_started"] - b["restart_begun"]))
        row = {
            "wall_s": _between(b, "kill", "first_step"),
            "detect_s": _between(b, "kill", "death_recorded"),
            "persist_s": before_start,
            "relaunch_s": (None if gone is None or before_start is None
                           else gone - before_start),
            "bootstrap_s": _between(b, "worker_started", "init_returned"),
            "backend_s": _between(b, "init_returned", "backend_up"),
            "state_s": _between(b, "backend_up", "restore_begun"),
            "restore_s": _restore_span_s(resume, k),
            "first_step_s": _between(b, "restore_done", "first_step"),
            "persist_total_s": persist,
        }
        program = [row[p] for p in PROGRAM]
        row["program_s"] = None if None in program else sum(program)
        covered = [row[p] for p in PARTS] + [row["wall_s"]]
        row["remainder_s"] = (None if None in covered
                              else row["wall_s"] - sum(covered[:-1]))
        rows.append(row)
    return rows


def part(ctx, name: str) -> Optional[float]:
    """The mean of ``name`` (a key of ``waterfall``'s rows) over the run's
    kills, or ``None``: no kill, or a kill without it."""
    resume = ctx.get("resume")
    if not resume:
        return None
    values = [row[name] for row in waterfall(resume)]
    if not values or None in values:
        return None
    return sum(values) / len(values)
