"""The seconds of a resume in which this repository's code runs: the sum of
``resume.persist_s``, ``relaunch_s``, ``bootstrap_s``, ``state_s``,
``restore_s`` and ``first_step_s``, that is ``resume.wall_s`` less the
kernel's teardown of the killed process (``resume.detect_s``), libtpu's
start (``resume.backend_s``) and the remainder. Six runs of it spread by
1.4 to 2.6 % where the wall time spreads by 4.7 to 15.7 % (PERF.md section
2): the steadier reading of what a change to the program moved. It is not
what a user waits: where the program shortens a wait of the platform's, or
overlaps one, only ``resume.wall_s`` shows it."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "program_s")
