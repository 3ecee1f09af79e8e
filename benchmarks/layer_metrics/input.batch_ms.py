"""Median host time to make one step's batch: random tokens from (seed,
step), ``global_batch_from_local`` onto the mesh, the reshape to
microbatches. The benchmark's own span round its batch maker."""

from benchmarks.harness import stats


def read(ctx):
    spans = ctx["spans"].get("input.batch")
    return 1e3 * stats.median(spans) if spans else None
