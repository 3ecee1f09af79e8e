"""Seconds of the agent's breakpoint persist of the shm frame that lie
before the relaunch: from ``agent#restart`` (``_restart_workers``, whose
``_stop_workers`` finds the worker dead) to the log line ``breakpoint save
(...): persisted`` that ends ``AsyncCheckpointSaver.save_shm_to_storage``
(frame read, CRC pass, stripes written to ``--ckpt-dir``, the run's
temporary directory on the machine's disk; the commit runs on its own
thread and is not in it), or to the new worker script's first line where
that comes first: today the whole persist, and still the part that holds
the relaunch up once the two run side by side. The breakpoint path opens no span
(``ckpt.persist`` is the save-event path's), so these are the agent's own
stamps on the host clock: PERF.md section 7 names the span a ``tracing``
PR must bring out."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "persist_s")
