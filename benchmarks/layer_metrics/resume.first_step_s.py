"""Seconds from the restored state being on the device to the first step's
loss on the host: the step program's trace and its load from the compile
cache, one step on the device, and the dispatch of the benchmark's leaf
digests ahead of it (``digest_dispatch_s`` in the ``resume_waterfall``
note). The worker's own stamps, host clock."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "first_step_s")
