"""Seconds of the program's ``ckpt.persist.read`` span under the agent's
breakpoint ``ckpt.persist`` of each kill: the frame lock asked for and
held, the frame read out of the killed worker's shm segment into the
agent's memory (``AsyncCheckpointSaver._persist_one``). The mean over the
run's kills, from the ring of the agent's exit bundle
(``harness/agent_spans.py``). Also prints the note ``fault_arc``: every
kill's span tree, from the poll that saw the death to the resumed
worker's first restore, seconds after the kill."""

from benchmarks.harness import agent_spans, program_spans


def read(ctx):
    arc = agent_spans.fault_arc(ctx)
    if arc is not None:
        program_spans.note("fault_arc", kills=arc)
    return agent_spans.persist_part(ctx, "read")
