"""Seconds of the program's ``agent.node_check`` span: the launcher's node
health check (``--network-check``) in set-up, before the first worker and
the fault, a backend start of its own in a child process. From the ring
of the agent's exit bundle (``harness/agent_spans.py``)."""

from benchmarks.harness import agent_spans


def read(ctx):
    return agent_spans.node_check_s(ctx)
