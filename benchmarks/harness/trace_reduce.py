"""From a profiler trace to numbers. The benchmark's own reduction: every
PR computes the same number the same way.

A trace here is plain data::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

``load`` makes one from the ``.xplane.pb`` the JAX profiler writes (read
with ``jax.profiler.ProfileData``, nothing else) or from such a dict kept
as JSON (the small recorded trace beside this file).

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per executed HLO op (a ``while`` holds its body's ops
nested inside its own interval) and whose line ``XLA Modules`` holds one
event per executed program. Host threads are lines of ``/host:CPU``;
the benchmark's ``TraceAnnotation``s land there under the names it gave.

Run ``python -m benchmarks.harness.trace_reduce <trace> [--sample OUT]``
to look at a trace by hand, or to cut a small sample of it.
"""

import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

Event = Tuple[str, int, int]  # name, start_ns, dur_ns


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    if path.endswith(".json") or path.endswith(".json.gz"):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> List[dict]:
    """The chips' planes, by device number."""
    found = [(int(m.group(1)), p) for p in trace["planes"]
             if (m := DEVICE_PLANE.match(p["name"]))]
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def line_events(plane: dict, line_name: str) -> List[Event]:
    out = []
    for line in plane["lines"]:
        if line["name"] == line_name:
            out.extend(tuple(e) for e in line["events"])
    return sorted(out, key=lambda e: (e[1], -e[2]))


def busy_intervals(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """Union of the events' intervals, merged and in order."""
    merged: List[List[int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def self_times(events: List[Event]) -> Dict[str, int]:
    """ns per op name with nested children's time taken out of their
    parent (a ``while`` is charged only what its body's ops leave)."""
    out: Dict[str, int] = {}
    stack: List[List] = []  # [name, end, self]

    def close():
        name, _, own = stack.pop()
        out[name] = out.get(name, 0) + max(0, own)

    for name, start, dur in events:  # sorted by start, longest first
        while stack and start >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        close()
    return out


def annotation_events(trace: dict, prefix: str) -> List[Event]:
    """Host events whose name starts with ``prefix`` (the benchmark's own
    ``TraceAnnotation``s), from every host line."""
    out = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend(tuple(e) for e in line["events"]
                       if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def _attribute(gap: Tuple[int, int], host: List[Event]) -> str:
    """The host annotation that covers most of a device gap."""
    best, best_ns = "unattributed", 0
    for name, start, dur in host:
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best_ns:
            best, best_ns = name, overlap
    return best


def step_events(plane: dict, step_module: str) -> List[Event]:
    """The step program's executions: modules-line events whose name
    holds ``step_module``."""
    return [e for e in line_events(plane, MODULES_LINE)
            if step_module in e[0]]


def reduce_device(plane: dict, *, step_module: str,
                  host: Optional[List[Event]] = None) -> Optional[dict]:
    """One chip's numbers. ``step_module`` is a substring of the step
    program's name on the modules line. Times in seconds. None when the
    plane holds no op."""
    ops = line_events(plane, OPS_LINE)
    if not ops:
        return None
    busy = busy_intervals(ops)
    lo, hi = busy[0][0], busy[-1][1]
    busy_ns = sum(b - a for a, b in busy)
    gaps = [(a2 - b1, (b1, a2))
            for (_, b1), (a2, _) in zip(busy, busy[1:]) if a2 > b1]
    by_host: Dict[str, int] = {}
    for ns, gap in gaps:
        who = _attribute(gap, host or [])
        by_host[who] = by_host.get(who, 0) + ns
    steps = step_events(plane, step_module)
    step_gaps = [max(0, b[1] - (a[1] + a[2]))
                 for a, b in zip(steps, steps[1:])]
    own = self_times(ops)
    coll = [e for e in ops if e[0].lstrip("%").startswith(COLLECTIVES)]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_gaps": sorted(((name, ns / 1e9)
                             for name, ns in by_host.items()),
                            key=lambda t: -t[1]),
        "device_ops": sorted(((name, ns / 1e9) for name, ns in own.items()),
                             key=lambda t: -t[1]),
        "steps": len(steps),
        "step_s": [e[2] / 1e9 for e in steps],
        "step_gap_s": [g / 1e9 for g in step_gaps],
        "collective_s": sum(e[2] for e in coll) / 1e9,
    }


def reduce(trace: dict, *, step_module: str,
           annotation_prefix: str = "bench:") -> List[dict]:
    """``reduce_device`` for every chip in the trace, in device order."""
    host = annotation_events(trace, annotation_prefix)
    out = [reduce_device(p, step_module=step_module, host=host)
           for p in device_planes(trace)]
    return [r for r in out if r is not None]


def sample(trace: dict, seconds: float) -> dict:
    """The first ``seconds`` of device activity, device op and module
    lines and the benchmark's host annotations only: small enough to keep
    beside the code."""
    planes = device_planes(trace)
    starts = [line_events(p, OPS_LINE)[0][1] for p in planes
              if line_events(p, OPS_LINE)]
    lo = min(starts)
    hi = lo + int(seconds * 1e9)

    def cut(events):
        return [[n, s - lo, d] for n, s, d in events
                if s >= lo and s + d <= hi]

    out = []
    for p in planes:
        out.append({"name": p["name"], "lines": [
            {"name": name, "events": cut(line_events(p, name))}
            for name in (OPS_LINE, MODULES_LINE)]})
    out.append({"name": "/host:CPU", "lines": [
        {"name": "annotations",
         "events": cut(annotation_events(trace, "bench:"))}]})
    return {"planes": out}


def main(argv: List[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = find_xplane(path) or path
    trace = load(path)
    for plane in trace["planes"]:
        print(f"plane {plane['name']!r}")
        for line in plane["lines"]:
            events = line["events"]
            total = sum(e[2] for e in events) / 1e9
            print(f"  line {line['name']!r}: {len(events)} events, "
                  f"{total:.4f} s summed")
            by_name: Dict[str, List[int]] = {}
            for name, _, dur in events:
                by_name.setdefault(name, []).append(dur)
            top = sorted(by_name.items(), key=lambda t: -sum(t[1]))[:25]
            for name, durs in top:
                print(f"    {sum(durs) / 1e9:10.6f} s  x{len(durs):<5d} "
                      f"{name[:150]}")
    if "--sample" in argv:
        out = argv[argv.index("--sample") + 1]
        seconds = float(argv[argv.index("--seconds") + 1]) \
            if "--seconds" in argv else 0.5
        opener = gzip.open if out.endswith(".gz") else open
        with opener(out, "wt") as f:
            json.dump(sample(trace, seconds), f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
