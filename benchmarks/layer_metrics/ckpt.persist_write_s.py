"""Seconds of the program's ``ckpt.persist.write`` span under the agent's
breakpoint ``ckpt.persist`` of each kill: the frame's stripes and its
manifest link written to ``--ckpt-dir``, the run's temporary directory on
the machine's disk (``manifest.persist_frame``; attrs ``bytes_written``,
``kind``). The mean over the run's kills, from the ring of the agent's
exit bundle (``harness/agent_spans.py``). With ``.read`` and ``.verify``
it is ``resume.persist_s`` seen from inside."""

from benchmarks.harness import agent_spans


def read(ctx):
    return agent_spans.persist_part(ctx, "write")
