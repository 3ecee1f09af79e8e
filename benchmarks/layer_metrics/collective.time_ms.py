"""Summed device time of the collective operations (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute, with their
-start and -done halves) a step, on the chip where it is smallest: a
collective ends on every chip together, so the chip that arrives last
waits for nobody and its time is the collectives' own cost. What the
other chips read beyond it is their wait for that chip (since PR 30 the
one whose experts drew the most pairs); chip 0, which this read before
PR 32, holds the wait in one run and not in the next. Whether the time
is hidden behind compute the sum does not say. None without a trace.

Also prints the note ``collective_ms_by_chip``: every chip's sum a step,
and the longest wait (largest less smallest)."""

from benchmarks.harness import program_spans


def read(ctx):
    chips = [1e3 * c["collective_s"] / c["steps"]
             for c in ctx["trace"] or [] if c["steps"]]
    if not chips:
        return None
    if len(chips) > 1:
        program_spans.note("collective_ms_by_chip", chips=chips,
                           longest_wait_ms=max(chips) - min(chips))
    return min(chips)
