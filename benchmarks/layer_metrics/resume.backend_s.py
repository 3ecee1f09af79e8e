"""Seconds from ``worker.init()`` returned to ``jax.devices()`` answered in
the resumed worker: libtpu starting the runtime on the chip the killed
process left. No line of this repository runs in it, and on the one-chip
machine's shared host it reads 5.3 to 14.8 s from one kill to the next
(PERF.md section 2). The same start is paid by the first worker and by
the node check, earlier in the same ``setup_s``. The worker's own stamps,
host clock."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "backend_s")
