"""Median duration of the program's ``ckpt.save.plan`` span (the planning
pass of a memory save: flatten, an on-device copy and a device-to-host
dispatch a shard), over the window's own saves: those that start after
the window's first step. From the tracer's ring, host clock."""

from benchmarks.harness import program_spans, stats


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    plans = program_spans.after_window_opened(ctx, spans, "ckpt.save.plan")
    if not plans:
        return None
    return 1e3 * stats.median([program_spans.seconds(sp) for sp in plans])
