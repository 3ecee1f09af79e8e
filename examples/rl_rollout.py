"""Agentic-RL rollout-plane chaos drill — the RL story as one script.

An RL job on the unified layer (RLJobBuilder → UnifiedMaster): rollout
replicas drive a serving-plane ContinuousBatcher to generate episodes,
a learner trains on them through the trajectory-lease ledger, per-step
weight sync rides the state-movement fabric, and ROSE borrow/handback
moves a replica between the rollout fleet and the learner's demand.

Chaos SIGKILLs one rollout replica AND the learner mid-run. The drill
passes only if every episode trains exactly once (seeded content-hash
audit), on-policy staleness stays within the bound, and the whole
kill / steal / sync / borrow / handback story is journaled.

Run: ``python examples/rl_rollout.py`` (CPU, ~10 s; ``--no-chaos``
skips the kills, ``--backend jax`` uses the real cached-decode engine).
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dlrover_tpu.rl.drill import run_rl_drill  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(
        description="agentic-RL rollout-plane chaos drill")
    parser.add_argument("--episodes", type=int, default=10)
    parser.add_argument("--rollout-replicas", type=int, default=3)
    parser.add_argument("--base-active", type=int, default=2)
    parser.add_argument("--backend", default="toy", choices=["toy", "jax"])
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--staleness-bound", type=int, default=2)
    parser.add_argument("--timeout", type=float, default=240.0)
    parser.add_argument("--no-chaos", action="store_true",
                        help="skip the rollout-replica and learner kills")
    args = parser.parse_args()
    result = run_rl_drill(
        episodes=args.episodes,
        rollout_replicas=args.rollout_replicas,
        base_active=args.base_active,
        chaos=not args.no_chaos,
        backend=args.backend,
        seed=args.seed,
        staleness_bound=args.staleness_bound,
        timeout_s=args.timeout,
    )
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
