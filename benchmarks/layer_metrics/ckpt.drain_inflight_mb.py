"""Median of the ``inflight_peak_bytes`` attribute of the program's
``ckpt.drain`` span, in MB (1e6 bytes): the most bytes of the snapshot
that a drain had asked the device-to-host link for and not yet received,
which is what a read-back of the training loop (its loss) can find ahead
of it there; over the drains that ran under the window's steps -- the
same drains as ``ckpt.drain_d2h_s``. A program whose drain does not say
(one that queues the whole snapshot at once) gives nothing. From the
tracer's ring."""

from benchmarks.harness import program_spans, stats


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    peaks = [sp.attrs["inflight_peak_bytes"]
             for sp in program_spans.under_window_steps(
                 ctx, spans, "ckpt.drain")
             if "inflight_peak_bytes" in sp.attrs]
    return float(stats.median(peaks)) / 1e6 if peaks else None
