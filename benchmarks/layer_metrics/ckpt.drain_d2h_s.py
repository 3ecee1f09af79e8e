"""Median duration, in seconds, of the program's ``ckpt.drain.d2h_wait``
span (the drain thread's ``np.asarray`` pass over the snapshot's
shards: waiting for the device-to-host copies the save dispatched), over
the drains that ran under the window's steps: that of the save that
opens the window and of every save in it but the last (the last drain,
like the set-up save's, has a quiet device and is left out). From the
tracer's ring, host clock."""

from benchmarks.harness import program_spans, stats


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    phases = program_spans.under_window_steps(
        ctx, spans, "ckpt.drain.d2h_wait")
    if not phases:
        return None
    return float(stats.median([program_spans.seconds(sp) for sp in phases]))
