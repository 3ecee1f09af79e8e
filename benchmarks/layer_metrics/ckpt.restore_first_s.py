"""Seconds of the process's first ``load_checkpoint`` from shm to
``block_until_ready``, on the host's clock: the warm-up restore that
``jobs/train.py`` makes once the window has closed and counts as set-up
(traffic ``restore_warmups``). It is what ``restore_s`` was until PR 26:
the timed restore plus the rebuild programs' load from the compile cache
(their compile, in a checkout's first run) and the staging ring's page
faults, which no later restore of the process pays."""


def read(ctx):
    warmups = ctx["job"].get("restore_warmups_s")
    return float(warmups[0]) if warmups else None
