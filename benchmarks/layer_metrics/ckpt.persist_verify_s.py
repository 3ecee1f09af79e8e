"""Seconds of the program's ``ckpt.persist.verify`` span under the
agent's breakpoint ``ckpt.persist`` of each kill: the CRC32 and Adler32
pass over every shard of the frame before it may go to the disk
(``SharedMemoryHandler.verify_frame``). The mean over the run's kills,
from the ring of the agent's exit bundle (``harness/agent_spans.py``)."""

from benchmarks.harness import agent_spans


def read(ctx):
    return agent_spans.persist_part(ctx, "verify")
