"""Agentic-RL rollout plane on the unified multi-role layer (ROADMAP
item 3; reference RLJobBuilder + ROSE's rollout-on-serving scenario).

The pieces, each riding an existing subsystem instead of reinventing it:

- :mod:`dlrover_tpu.rl.buffer` — trajectory-lease ledger: the exactly-once
  shard-lease protocol of the elastic data plane, applied to episodes
  (a dead rollout replica never drops or double-delivers a trajectory);
- :mod:`dlrover_tpu.rl.sync` — learner→replica weight sync over the
  state-movement fabric, with on-policy staleness accounting
  (staleness = learner_version − generation_version, journaled, bounded);
- :mod:`dlrover_tpu.rl.workloads` — the rollout role (a serving-plane
  ContinuousBatcher driving an engine) and the learner role, both unified
  process actors;
- :mod:`dlrover_tpu.rl.trainer` — the task-stream trainer composing
  leases, syncs, training, and ROSE borrow/handback elasticity;
- :mod:`dlrover_tpu.rl.drill` — the seeded end-to-end drill (chaos
  SIGKILLs a rollout replica AND the learner mid-episode) behind
  ``examples/rl_rollout.py`` and ``tests/test_rl_rollout.py``.
"""

from dlrover_tpu.rl.buffer import Trajectory, TrajectoryLedger, content_hash
from dlrover_tpu.rl.sync import POLICY_KEY, StalenessLedger, pull_policy

__all__ = [
    "Trajectory", "TrajectoryLedger", "content_hash",
    "POLICY_KEY", "StalenessLedger", "pull_policy",
]
