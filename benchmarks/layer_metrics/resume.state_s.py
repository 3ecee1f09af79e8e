"""Seconds from the backend being up to the restore's start in the resumed
worker: mesh, the jitted sharded init from the seed, ``ElasticTrainer``
and the eager ``make_train_state``, as a user's script makes the state it
restores into (``examples/llama_elastic_pretrain.py``). The worker's own
stamps, host clock."""

from benchmarks.harness import resume_path


def read(ctx):
    return resume_path.part(ctx, "state_s")
