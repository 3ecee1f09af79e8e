"""The program's own spans, read two ways.

``dlrover_tpu/observability/tracing.py`` keeps every finished span of the
process in a ring: name, start and end on the host's monotonic clock,
attributes, parent. A worker (``worker.init()``) also mirrors each span
entered with ``with`` into any profile being taken, as an annotation
named ``dlrover:<span name>`` on the span's own thread: the same span on
the profiler's clock, beside the device's ops. Durations and attributes
are read from the ring (every save, drain, restore and step of the run,
traced or not); what a span has to be set against on the device is read
from the annotations (the traced steps only), with the ring's help for
the spans that outlast the profile (``on_profilers_clock``).

Where the benchmark runs the program in its own process the ring is at
hand; a job that runs it in child processes (``jobs/resume.py``) hands
the worker's ring back as ``ctx["ring"]``, each span as
``Span.to_dict`` gives it. A reader gets nothing (``None``) where there is nothing to read:
an empty ``ctx["job"]``, tracing switched off, a program that has no
such span (the parent of the PR that added it), or a ring that has
dropped spans -- a median over what is left would be over an unknown part
of the run.
"""

import json
import types
from typing import Dict, List, Optional, Tuple

from benchmarks.harness import stats, trace_reduce

PREFIX = "dlrover:"
STEP = "train.step"
SAVE = "ckpt.save_to_memory"
RESTORE = "ckpt.restore"


def note(kind: str, **fields) -> None:
    """An earlier line of the run's output, as ``run.py`` prints them:
    what a reader saw beside the one number it returns."""
    print(json.dumps({"note": kind, **fields}), flush=True)


def ring(ctx) -> Optional[list]:
    """The process's finished spans, oldest first, or None (see above).
    Each has ``name``, ``start_t``, ``end_t``, ``attrs``, ``trace_id``."""
    if not ctx.get("job"):
        return None
    if "ring" in ctx:  # a worker's, handed back; None where it had none
        return ctx["ring"] and sorted(
            (types.SimpleNamespace(**sp) for sp in ctx["ring"]),
            key=lambda sp: sp.start_t)
    from dlrover_tpu.observability import tracing

    tracer = tracing.get_tracer()
    if not tracer.enabled or tracer.dropped():
        return None
    return sorted(tracer.finished_spans(), key=lambda sp: sp.start_t)


def named(spans, name: str) -> list:
    return [sp for sp in spans if sp.name == name]


def seconds(span) -> float:
    return span.end_t - span.start_t


def window_steps(ctx, spans) -> list:
    """The ``train.step`` spans of the measured window: the run's last
    ``job["steps"]`` of them (warm-up comes before, nothing after)."""
    n = ctx["job"].get("steps", 0)
    return named(spans, STEP)[-n:] if n else []


def after_window_opened(ctx, spans, name: str) -> list:
    """Spans called ``name`` that start after the window's first step:
    of the save's phases, those of the window's own saves (the set-up
    save and the one that opens the window come before)."""
    steps = window_steps(ctx, spans)
    if not steps:
        return []
    return [sp for sp in named(spans, name)
            if sp.start_t > steps[0].start_t]


def under_window_steps(ctx, spans, name: str) -> list:
    """Spans called ``name`` that overlap the window's steps: of the
    drain's phases, those with training above them -- the drain of the
    save that opens the window and of every save in it but the last,
    whose drain (like the set-up save's) has a quiet device."""
    steps = window_steps(ctx, spans)
    if not steps:
        return []
    lo, hi = steps[0].start_t, steps[-1].end_t
    return [sp for sp in named(spans, name)
            if sp.start_t < hi and sp.end_t > lo]


def step_intervals(ctx, spans) -> List[Tuple[float, float]]:
    """(start, seconds to the next step's start) of successive window
    steps. With one step in flight a start-to-start interval is a step's
    time on the device plus whatever kept the host from handing over the
    next. An interval that holds a save is left out."""
    steps = window_steps(ctx, spans)
    saves = named(spans, SAVE)
    return [(a.start_t, b.start_t - a.start_t)
            for a, b in zip(steps, steps[1:])
            if not any(a.start_t <= s.start_t < b.start_t for s in saves)]


def mean_step_ms_under(ctx, name: str, metric: str) -> Optional[float]:
    """Mean interval, in ms, of the window steps that start while a span
    called ``name`` is open, or None. The mean and not the median: a
    drain phase does not slow every step, it holds the host's loop a few
    times for long, and a median of mostly steady steps would not move
    if those holds went away. Prints a note called ``metric``: every
    such interval, the window's steady interval (the median of all) and
    the seconds lost against it."""
    spans = ring(ctx)
    if spans is None:
        return None
    every = step_intervals(ctx, spans)
    phases = named(spans, name)
    under = [s for start, s in every
             if any(p.start_t <= start < p.end_t for p in phases)]
    if not under:
        return None
    steady = stats.median([s for _, s in every])
    note(metric, intervals_ms=[round(1e3 * s, 2) for s in under],
         steady_ms=1e3 * steady, lost_s=sum(under) - steady * len(under))
    return 1e3 * sum(under) / len(under)


def last_restore(spans) -> Tuple[Optional[object], list]:
    """(the run's last ``ckpt.restore`` span, the spans of its trace that
    lie inside it), or (None, [])."""
    restores = named(spans, RESTORE)
    if not restores:
        return None, []
    top = restores[-1]
    inside = [sp for sp in spans
              if sp.trace_id == top.trace_id and sp is not top
              and sp.start_t >= top.start_t and sp.end_t <= top.end_t]
    return top, inside


def drain_waterfall(ctx, spans) -> List[dict]:
    """One dict a drain of the run, oldest first: its phases' seconds,
    the frame write split into copies and checksums, and whether the
    window's steps ran above it."""
    under = {id(sp) for sp in under_window_steps(ctx, spans, "ckpt.drain")}
    out = []
    for drain in named(spans, "ckpt.drain"):
        row = {"under_steps": id(drain) in under,
               "drain_s": seconds(drain), "bytes": drain.attrs.get("bytes")}
        for sp in spans:
            if sp.parent_id != drain.span_id:
                continue
            row[sp.name.rsplit(".", 1)[-1] + "_s"] = seconds(sp)
            row.update({k: v for k, v in sp.attrs.items()
                        if k in ("copy_s", "checksum_s")})
        out.append(row)
    return out


def restore_waterfall(spans) -> Optional[dict]:
    """The run's last restore: seconds of ``load`` and of each rung that
    was tried, and for the reads and the host-to-device puts inside
    ``_assemble`` their number, thread-seconds, bytes, the longest one,
    and where their first start and last end lie after the restore's
    start."""
    top, inside = last_restore(spans)
    if top is None:
        return None
    out = {"load_s": seconds(top), "rungs": {
        sp.name: seconds(sp) for sp in inside
        if sp.parent_id == top.span_id}}
    for part in ("read", "h2d"):
        parts = named(inside, "ckpt.restore." + part)
        if parts:
            out[part] = {
                "spans": len(parts),
                "thread_s": sum(seconds(sp) for sp in parts),
                "bytes": sum(sp.attrs.get("bytes", 0) for sp in parts),
                "longest_s": max(seconds(sp) for sp in parts),
                "first_start_s": min(sp.start_t for sp in parts) - top.start_t,
                "last_end_s": max(sp.end_t for sp in parts) - top.start_t,
            }
    return out


# -- the same spans on the profiler's clock ---------------------------------


def annotations(raw) -> List[Tuple[str, int, int]]:
    """(span name, start_ns, end_ns) of the program's annotations in a
    loaded trace, from every host line."""
    if not raw:
        return []
    return [(name[len(PREFIX):], start, start + dur) for name, start, dur
            in trace_reduce.annotation_events(raw, PREFIX)]


# how far apart two readings of one span may lie, in ns: the annotation
# is entered after the span's clock is read and left before it is read
# again, microseconds each way
SAME_SPAN_NS = 100_000


def clock_offset_ns(notes, spans) -> Optional[float]:
    """Profiler's clock minus the tracer's monotonic clock, in ns, or
    None. A profile counts from its own start, so the offset is measured:
    every pair of an annotation and a ring span of the same name and the
    same duration votes for the offset that would make them one span,
    and the offset that nearly all annotations vote for (nine in ten, to
    within ``SAME_SPAN_NS``) is taken. Steps of equal length a step apart
    vote for wrong offsets too, but never all of them at once; where a
    wrong offset ties with the true one (a true pair just missed the
    test), the one that more spans other than ``train.step`` vote for
    wins: a save's phases do not repeat a step apart."""
    by_name: Dict[str, list] = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    votes = sorted(
        (start - sp.start_t * 1e9, name != STEP)
        for name, start, end in notes for sp in by_name.get(name, [])
        if abs((end - start) - seconds(sp) * 1e9) < SAME_SPAN_NS)
    best, lo, others = (0, 0, 0), 0, 0
    for hi, (vote, other) in enumerate(votes):
        others += other
        while vote - votes[lo][0] > SAME_SPAN_NS:
            others -= votes[lo][1]
            lo += 1
        best = max(best, (hi - lo + 1, others, -lo))
    count, lo = best[0], -best[2]
    if not notes or count < 0.9 * len(notes):
        return None
    return stats.median([vote for vote, _ in votes[lo:lo + count]])


def on_profilers_clock(ctx) -> List[Tuple[str, int, int]]:
    """Every program span that can be placed on the profiler's clock, as
    ``annotations`` gives them. An annotation is written when its span
    ends, so a span that the profile's end cuts off (a drain phase of
    seconds under a traced span of a few steps) is missing from the
    profile, though not from the ring. With the offset between the two
    clocks measured on the spans that are in both, every ring span is
    placed. Without a ring or an offset: the annotations alone."""
    notes = annotations(ctx.get("trace_raw"))
    spans = ring(ctx) if notes else None
    offset = clock_offset_ns(notes, spans) if spans else None
    if offset is None:
        return notes
    return [(sp.name, int(sp.start_t * 1e9 + offset),
             int(sp.end_t * 1e9 + offset)) for sp in spans]


def idle_gaps(raw) -> List[Tuple[int, int]]:
    """Chip 0's idle intervals (ns) inside the traced span, as
    ``trace_reduce.reduce_device`` counts them: between the merged
    intervals of its ``XLA Ops`` line."""
    planes = trace_reduce.device_planes(raw) if raw else []
    if not planes:
        return []
    busy = trace_reduce.busy_intervals(
        trace_reduce.line_events(planes[0], trace_reduce.OPS_LINE))
    return [(b1, a2) for (_, b1), (a2, _) in zip(busy, busy[1:]) if a2 > b1]


def idle_by_span(gaps, spans) -> Dict[str, float]:
    """Seconds of ``gaps`` by the program span open at the time. Where
    several are open the innermost counts: the one that started last
    among the spans other than ``train.step`` (what the drain thread is
    inside says more than that the main thread is dispatching a step),
    ``train.step`` where only it is open, ``none`` where nothing is. Of two that started together the shorter is the inner one.
    ``spans`` is what ``on_profilers_clock`` gives."""
    out: Dict[str, float] = {}
    if gaps:  # the ring holds the whole run, the gaps a few steps of it
        first, last = gaps[0][0], gaps[-1][1]
        spans = [sp for sp in spans if sp[1] < last and sp[2] > first]
    for lo, hi in gaps:
        over = [sp for sp in spans if sp[1] < hi and sp[2] > lo]
        cuts = sorted({lo, hi, *(t for sp in over for t in sp[1:]
                                 if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [sp for sp in over if sp[1] <= a and sp[2] >= b]
            phase = [sp for sp in open_ if sp[0] != STEP] or open_
            who = (max(phase, key=lambda sp: (sp[1], -sp[2]))[0]
                   if phase else "none")
            out[who] = out.get(who, 0.0) + (b - a) / 1e9
    return out


def overlap_s(gaps, intervals) -> float:
    """Seconds of ``gaps`` that lie inside ``intervals`` (ns pairs, in
    order and disjoint, as a chip's step programs are)."""
    total = 0
    for lo, hi in gaps:
        total += sum(max(0, min(hi, b) - max(lo, a)) for a, b in intervals
                     if a < hi and b > lo)
    return total / 1e9
