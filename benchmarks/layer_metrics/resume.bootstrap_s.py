"""Seconds from the new worker script's first line to ``worker.init()``
returned: the benchmark's and the program's imports (a warm spare has
numpy and jax already), the compile cache's switch, the profiler's bridge,
the compile watcher, the master's client. The backend's start that
follows is ``resume.backend_s``. The worker's own stamps, host clock."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "bootstrap_s")
