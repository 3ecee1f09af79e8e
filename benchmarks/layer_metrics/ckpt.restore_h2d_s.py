"""Seconds in the program's ``ckpt.restore.h2d`` spans of the run's last
restore, one round each ``jax.device_put`` of ``_assemble`` (what the call
itself takes; on the v5e that is nearly the whole transfer: ``load``
returns as ``block_until_ready`` does), summed over the restore pool's
threads: thread-seconds, up to eight times the wall time they cover.
From the tracer's ring."""

from benchmarks.harness import program_spans


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    _, inside = program_spans.last_restore(spans)
    parts = program_spans.named(inside, "ckpt.restore.h2d")
    if not parts:
        return None
    return float(sum(program_spans.seconds(sp) for sp in parts))
