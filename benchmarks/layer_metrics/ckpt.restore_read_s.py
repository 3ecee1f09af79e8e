"""Seconds in the program's ``ckpt.restore.read`` spans of the run's last
restore, one round each read of a saved shard's bytes (a pread from the
shm segment into fresh pages), summed over the restore pool's threads:
thread-seconds, up to eight times the wall time they cover. From the
tracer's ring. Also prints the note ``restore_waterfall``: the restore by
rung, and where the reads and the puts lie in it."""

from benchmarks.harness import program_spans


def read(ctx):
    spans = program_spans.ring(ctx)
    if spans is None:
        return None
    _, inside = program_spans.last_restore(spans)
    parts = program_spans.named(inside, "ckpt.restore.read")
    if not parts:
        return None
    program_spans.note("restore_waterfall",
                       **program_spans.restore_waterfall(spans))
    return float(sum(program_spans.seconds(sp) for sp in parts))
