"""Of chip 0's idle time inside the traced span, the share during which
the program had a span other than ``train.step`` open: a save's phase on
the main thread, a drain's phase on the drain thread, a restore's. The
program's spans are in the profile as ``dlrover:<name>`` annotations, on
the device's clock (those the profile's end cut off are placed from the
tracer's ring: ``harness/program_spans.py``); the gaps are the ones
``device.idle_pct`` counts on chip 0. A share of attribution, not a cost:
higher is idle time better explained, and what the idle time costs is
``device.idle_pct``. Where the program opens no such span (a steady cell)
it reads 0 over a fraction of a millisecond of idle time.

Also prints the note ``idle_by_program_span``: idle seconds by the
innermost span open at the time (``train.step`` where only the step's
dispatch is open, ``none`` where nothing is), and how many of them lie
inside a step program's execution rather than between two."""

from benchmarks.harness import program_spans, trace_reduce


def read(ctx):
    spans = program_spans.on_profilers_clock(ctx)
    if not spans:
        return None
    raw = ctx["trace_raw"]
    gaps = program_spans.idle_gaps(raw)
    by_span = program_spans.idle_by_span(gaps, spans)
    idle_s = sum(by_span.values())
    planes = trace_reduce.device_planes(raw)  # none in a CPU rehearsal
    steps = [(start, start + dur) for _, start, dur in trace_reduce.step_events(
        planes[0], ctx.get("step_module", "\0"))] if planes else []
    program_spans.note(
        "idle_by_program_span", idle_s=idle_s, spans_placed=len(spans),
        of_them_annotations=len(program_spans.annotations(raw)),
        inside_step_programs_s=program_spans.overlap_s(gaps, steps),
        seconds=dict(sorted(by_span.items(), key=lambda t: -t[1])))
    if not idle_s:
        return 0.0
    named = sum(s for name, s in by_span.items()
                if name not in ("none", program_spans.STEP))
    return 100.0 * named / idle_s
