"""Median of the ``checksum_s`` attribute of the program's
``ckpt.drain.shm_write`` span: the seconds ``write_frame`` spent in CRC32
and Adler32 over the buffers, summed over them (the span's ``copy_s`` is
the copies' share), over the drains that ran under the window's steps --
the same drains as ``ckpt.drain_write_s``. From the tracer's ring."""

from benchmarks.harness import program_spans, stats


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    sums = [sp.attrs["checksum_s"] for sp in program_spans.under_window_steps(
        ctx, spans, "ckpt.drain.shm_write") if "checksum_s" in sp.attrs]
    return float(stats.median(sums)) if sums else None
