"""The fault arc from inside the agent: its own spans of each kill.

An agent that restarted workers writes, as it exits, a flight-recorder
bundle ``bundle_agent_<rank>_agent_exit_<n>_<pid>/`` under the trace
directory (``DLROVER_TPU_TRACE_DIR``, else ``$TMPDIR/dlrover_tpu_bundles``:
``flight_recorder.default_trace_dir``, the same for the benchmark's process
and the agent it starts). Its ``spans.json`` is every span of the agent's
ring as ``Span.to_dict`` gives it, stamped with ``time.monotonic()`` of the
host: the clock of the benchmark's kill stamps and of the worker's lines,
so nothing is shifted. ``manifest.json`` has ``monotonic_t`` and
``wall_ts`` read together. The run's bundle is the earliest written after
its first kill that holds an ``agent.worker_failure`` begun after it; it is
kept as ``ctx["resume"]["agent_ring"]``::

    {"spans": [<Span.to_dict>, ...], "manifest": {...}}

or ``None`` where there is none: a program without these spans (the
parent of the PR that added them), or tracing switched off.

The spans of kill ``k`` (the ``k``-th of the run; the worker that answers
it has ``inc == k``) are those that start between that kill's stamp and
that worker's first step. The arc, one trace, in the order it runs::

    agent.worker_failure         opened at the poll that saw the death
      agent.failure_report       report to the master, the verdict
      agent.restart_workers
        agent.stop_workers
        ckpt.persist             the breakpoint persist (trigger breakpoint)
          ckpt.persist.read      frame lock held, frame read out of shm
          ckpt.persist.verify    the CRC pass
          ckpt.persist.write     stripes and manifest link to --ckpt-dir
          ckpt.commit            on its own thread
        rdzv.client_round        one rendezvous round on the master
        agent.spawn              attrs ``warm``, ``pids``
          ckpt.restore           the resumed worker's first (its ring)

A part missing in any kill reads ``None``, as in ``resume_path.part``.
"""

import glob
import json
import os
from typing import Dict, List, Optional

FAILURE = "agent.worker_failure"
PERSIST = "ckpt.persist"
BREAKPOINT = "breakpoint"  # MetricLabel.CKPT_TRIGGER_BREAKPOINT
RDZV = "rdzv.client_round"
NODE_CHECK = "agent.node_check"
RESTORE = "ckpt.restore"


def find_bundle(resume: dict, directory: Optional[str] = None
                ) -> Optional[dict]:
    """The run's ``agent_exit`` bundle as ``{"spans", "manifest"}``."""
    kills = resume.get("kills") or []
    if not kills:
        return None
    if directory is None:
        from dlrover_tpu.observability.flight_recorder import (
            default_trace_dir,
        )

        directory = default_trace_dir()
    first_kill = kills[0]["t"]
    found = []
    for name in glob.glob(os.path.join(
            directory, "bundle_*_agent_exit_*", "manifest.json")):
        try:
            with open(name) as f:
                manifest = json.load(f)
            if manifest.get("monotonic_t", 0.0) < first_kill:
                continue
            with open(os.path.join(os.path.dirname(name), "spans.json")) as f:
                spans = json.load(f)
        except (OSError, ValueError):
            continue
        if any(sp["name"] == FAILURE and sp["start_t"] > first_kill
               for sp in spans):
            found.append((manifest["monotonic_t"], spans, manifest))
    if not found:
        return None
    _, spans, manifest = min(found, key=lambda f: f[0])
    return {"spans": spans, "manifest": manifest}


def ring(ctx) -> Optional[dict]:
    """``ctx["resume"]["agent_ring"]``, found and kept on first use."""
    resume = ctx.get("resume")
    if not resume:
        return None
    if "agent_ring" not in resume:
        resume["agent_ring"] = find_bundle(resume)
    return resume["agent_ring"]


def _window(resume: dict, k: int):
    """(kill ``k``'s stamp, the first step of the worker that answers it)."""
    first = [e["t"] for e in resume.get("worker", [])
             if e.get("inc") == k and e["event"] == "first_step"]
    return resume["kills"][k - 1]["t"], (first[0] if first
                                         else float("inf"))


def kill_spans(resume: dict, spans: List[dict], k: int) -> List[dict]:
    lo, hi = _window(resume, k)
    return [sp for sp in spans if lo <= sp["start_t"] <= hi]


def _seconds(sp: dict) -> Optional[float]:
    return None if sp.get("end_t") is None else sp["end_t"] - sp["start_t"]


def _mean(values) -> Optional[float]:
    if not values or None in values:
        return None
    return sum(values) / len(values)


def _per_kill(ctx, reading) -> Optional[float]:
    """The mean over the run's kills of ``reading(spans of kill k)``."""
    found = ring(ctx)
    if not found:
        return None
    resume = ctx["resume"]
    return _mean([reading(kill_spans(resume, found["spans"], k))
                  for k in range(1, len(resume["kills"]) + 1)])


def persist_part(ctx, part: str) -> Optional[float]:
    """Seconds of ``ckpt.persist.<part>`` under the breakpoint persist of
    each kill (summed over its frames), the mean over the kills."""

    def reading(spans):
        persists = [sp for sp in spans if sp["name"] == PERSIST
                    and sp["attrs"].get("trigger") == BREAKPOINT]
        if not persists:
            return None
        ids = {sp["span_id"] for sp in persists}
        parts = [_seconds(sp) for sp in spans
                 if sp["name"] == f"{PERSIST}.{part}"
                 and sp["parent_id"] in ids]
        return sum(parts) if parts and None not in parts else None

    return _per_kill(ctx, reading)


def rdzv_s(ctx) -> Optional[float]:
    """Seconds of each kill's first ``rdzv.client_round``, the mean."""

    def reading(spans):
        rounds = [sp for sp in spans if sp["name"] == RDZV]
        return _seconds(min(rounds, key=lambda sp: sp["start_t"])) \
            if rounds else None

    return _per_kill(ctx, reading)


def node_check_s(ctx) -> Optional[float]:
    """Seconds of the node check of set-up: the last ``agent.node_check``
    begun before the run's first kill."""
    found = ring(ctx)
    if not found:
        return None
    first_kill = ctx["resume"]["kills"][0]["t"]
    checks = [sp for sp in found["spans"]
              if sp["name"] == NODE_CHECK and sp["start_t"] < first_kill]
    return _seconds(max(checks, key=lambda sp: sp["start_t"])) \
        if checks else None


def fault_arc(ctx) -> Optional[List[Dict]]:
    """One dict a kill: the trace_id of its ``agent.worker_failure`` and
    every span of that trace, the agent's and, from the resumed worker's
    ring, its ``ckpt.restore`` with the rungs under it: name, ids,
    source, start and end in seconds after the kill, attributes."""
    found = ring(ctx)
    if not found:
        return None
    resume = ctx["resume"]
    out = []
    for k in range(1, len(resume["kills"]) + 1):
        kill_t = resume["kills"][k - 1]["t"]
        roots = [sp for sp in kill_spans(resume, found["spans"], k)
                 if sp["name"] == FAILURE]
        if not roots:
            out.append({"kill": k, "trace_id": None, "spans": []})
            continue
        trace_id = roots[0]["trace_id"]
        arc = [sp for sp in found["spans"] if sp["trace_id"] == trace_id]
        worker = [sp for sp in (resume.get("rings") or {}).get(str(k)) or []
                  if sp.get("trace_id") == trace_id]
        restores = {sp["span_id"] for sp in worker if sp["name"] == RESTORE}
        arc += [sp for sp in worker if sp["span_id"] in restores
                or sp["parent_id"] in restores]
        out.append({"kill": k, "trace_id": trace_id, "spans": [{
            "name": sp["name"], "span_id": sp["span_id"],
            "parent_id": sp["parent_id"], "source": sp["source"],
            "start_s": sp["start_t"] - kill_t,
            "end_s": (None if sp.get("end_t") is None
                      else sp["end_t"] - kill_t),
            "attrs": sp["attrs"],
        } for sp in sorted(arc, key=lambda sp: sp["start_t"])]})
    return out
