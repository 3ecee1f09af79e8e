"""Seconds of the program's ``rdzv.client_round`` span in the agent's
restart after each kill: the join and the poll for the cut world on the
local master, the master's share of ``resume.relaunch_s``. The mean over
the run's kills, from the ring of the agent's exit bundle
(``harness/agent_spans.py``)."""

from benchmarks.harness import agent_spans


def read(ctx):
    return agent_spans.rdzv_s(ctx)
