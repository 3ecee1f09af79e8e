"""Median gap on chip 0 between the end of one step program and the
start of the next (the modules line of the profiler trace): what the
host's loop, the trainer's hooks and the dispatch leave the chip waiting
for. Gaps that hold a save are in the median like any other."""

from benchmarks.harness import stats


def read(ctx):
    chips = ctx["trace"]
    if not chips or not chips[0]["step_gap_s"]:
        return None
    return 1e3 * stats.median(chips[0]["step_gap_s"])
