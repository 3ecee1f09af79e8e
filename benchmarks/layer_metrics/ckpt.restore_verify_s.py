"""Duration, in seconds, of the program's ``ckpt.restore.verify`` span in
the run's last restore: the single-threaded checksum pass over the whole
shm frame before a byte of it is used. From the tracer's ring."""

from benchmarks.harness import program_spans


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    _, inside = program_spans.last_restore(spans)
    verify = program_spans.named(inside, "ckpt.restore.verify")
    return float(program_spans.seconds(verify[-1])) if verify else None
