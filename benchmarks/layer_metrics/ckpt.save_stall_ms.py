"""Median wall time one ``Checkpointer.save_checkpoint(step, state,
MEMORY)`` blocks the training loop, call to return, from a quiet device:
the benchmark's own span round the call. About twice
``ckpt.save_block_ms``, which the registry starts only at the planning
pass. Five saves a window: too few for an end-to-end bound (PERF.md)."""

from benchmarks.harness import stats


def read(ctx):
    spans = ctx["spans"].get("save.block")
    return 1e3 * stats.median(spans) if spans else None
