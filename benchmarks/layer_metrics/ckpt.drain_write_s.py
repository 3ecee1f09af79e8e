"""Median duration, in seconds, of the program's ``ckpt.drain.shm_write``
span (``write_frame``: every buffer copied into the shm segment and
checksummed, one after the other), over the drains that ran under the
window's steps: that of the save that opens the window and of every save
in it but the last (the last drain, like the set-up save's, has a quiet
device and is left out). From the tracer's ring, host clock. Also prints
the note ``drain_waterfall``: every drain of the run by phase, the write
split into copies and checksums."""

from benchmarks.harness import program_spans, stats


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    phases = program_spans.under_window_steps(
        ctx, spans, "ckpt.drain.shm_write")
    if not phases:
        return None
    program_spans.note("drain_waterfall",
                       drains=program_spans.drain_waterfall(ctx, spans))
    return float(stats.median([program_spans.seconds(sp) for sp in phases]))
