"""Seconds from the SIGKILL the benchmark sends to the worker until the agent
records the death: ``agent#worker_fail`` of the agent's event emitter
(``_handle_worker_failure``, reached when the monitor loop's
``Popen.poll``, every ``monitor_interval`` 0.2 s, first answers), put on
the monotonic clock with the offset the benchmark read as it sent the
signal. Nearly all of it is the kernel tearing down a process that holds
some ten gigabytes and the chip: until it has, ``waitpid`` has nothing to
report. It reads 2.7 to 7.0 s from one kill to the next with the shared
host's state (PERF.md section 2). Host clock; the mean over the run's
kills. Also prints the note ``resume_waterfall``: every part of every
kill, the boundaries they were cut at, and the remainder of the wall time
that no part covers."""

from benchmarks.harness import program_spans, resume_path


def read(ctx):
    resume = ctx.get("resume")
    if resume:
        program_spans.note(
            "resume_waterfall", kills=resume_path.waterfall(resume),
            boundaries=resume_path.boundaries(resume),
            digest_dispatch_s=[e.get("digest_dispatch_s")
                               for e in resume["worker"]
                               if e["event"] == "restored"])
    return resume_path.part(ctx, "detect_s")
