"""Mean start-to-start interval of the window's ``train.step`` spans
that start while a ``ckpt.drain.shm_write`` span is open: what a step
costs while the drain thread copies the snapshot into shm and checksums
it (one step is in flight, so start to start is the step's time on the
device plus any hold of the host's loop; an interval that holds a save
is left out). The mean, since the phase holds a few steps for long and
leaves the median steady. Set it against the steady cell's
``step_ms.p90``. From the tracer's ring, host clock. Also prints, in a
note of the metric's name, every such interval and the seconds lost
against the window's steady interval."""

from benchmarks.harness import program_spans


def read(ctx):
    return program_spans.mean_step_ms_under(
        ctx, "ckpt.drain.shm_write", "ckpt.step_ms_in_write")
