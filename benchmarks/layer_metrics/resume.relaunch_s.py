"""Seconds from the agent's record of the death to the first line of the new
worker's script, less the persist (``resume.persist_s``): failure report
and diagnosis, the shard leases' recovery, the rendezvous round
(``rdzv.client_round``; ``agent#rendezvous`` in the events file) and the
spawn, from the warm pool's spare interpreter where one is ready. Host
clock."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "relaunch_s")
