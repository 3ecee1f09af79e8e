"""90th percentile of the interval between successive step completions
in a window that holds saves: the steps under a drain are the slow ones.
Host clock, as ``step_ms.p90`` in the steady cells; no interval holds a
save. Its runs spread by 8 % of the median, too wide for a bound."""

from benchmarks.harness import stats


def read(ctx):
    intervals = ctx["spans"].get("step.interval")
    return 1e3 * stats.percentile(intervals, 90) if intervals else None
