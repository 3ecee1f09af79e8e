"""What a user waits: seconds from the SIGKILL the benchmark sends to the
first resumed step's loss on the host, both ``time.monotonic()`` of the
one host; the mean over the run's kills. The eight parts
(``resume.detect_s`` to ``resume.first_step_s``) are cut end to end inside
it. It is no end-to-end metric of its own because six runs of it spread by
4.7 to 15.7 % with the shared host's state (PERF.md section 2), and no
bound may be wider than 0.1; the fault lies inside the cell's set-up, so
``setup_s`` holds it whole."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "wall_s")
