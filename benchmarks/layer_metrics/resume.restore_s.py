"""Seconds of the program's ``ckpt.restore`` span in the resumed worker:
``Checkpointer.load_checkpoint`` from the shm frame the killed worker
left, in a process that has never restored: a cold mapping of the
segment, fresh staging chunks, the rebuild programs from the compile
cache. ``ckpt.restore_first_s`` is the same call in the process that
wrote the frame. From the tracer's ring the resumed worker hands back."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "restore_s")
