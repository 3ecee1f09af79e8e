"""The end-to-end rollout-plane chaos drill: a seeded RL job on the
unified layer where a rollout replica AND the learner are SIGKILLed
mid-episode, a learner-demand surge forces the ROSE handback, and the
run must still finish with

- every episode trained EXACTLY once (the ledger audit finds nothing
  lost, nothing double-committed), with delivered token hashes matching
  an independent same-seed regeneration (deterministic engine ⇒ the
  surviving replica's re-generation is byte-identical);
- on-policy staleness ≤ the configured bound for every trajectory;
- the kill / steal / sync / borrow / handback story journaled
  (``unified_failover``, ``rl_lease_requeued``, ``rl_weight_sync``,
  ``serve_scale`` borrow+handback, ``rl_rollout_drained``).

``examples/rl_rollout.py`` is the CLI face; ``tests/test_rl_rollout.py``
runs the same drill in tier-1. The report carries trajectories/s,
weight-sync latency, and max staleness.
"""

import time
from typing import Dict, Optional

from dlrover_tpu.observability.journal import JournalEvent
from dlrover_tpu.rl.buffer import content_hash
from dlrover_tpu.rl.trainer import seeded_prompts
from dlrover_tpu.serving.batcher import ContinuousBatcher
from dlrover_tpu.serving.engine import ToyEngine
from dlrover_tpu.unified.api import RLJobBuilder
from dlrover_tpu.unified.master import UnifiedMaster


def expected_content_hashes(prompts, max_new_tokens: int = 6,
                            slots: int = 4, vocab: int = 97,
                            buckets=(8, 16),
                            backend: str = "toy") -> Dict[int, str]:
    """Independently regenerate every episode on a local engine with the
    drill's parameters — the audit's ground truth. Both engines are pure
    functions of (prompt, position) — ToyEngine by arithmetic, the jax
    engine by seed-deterministic weights the sync never touches — so
    this needs no knowledge of which replica (or which incarnation)
    served each episode."""
    if backend == "jax":
        from dlrover_tpu.serving.engine import build_tiny_engine

        engine = build_tiny_engine(slots=slots, cache_len=48, vocab=64)
    else:
        engine = ToyEngine(slots=slots, vocab=vocab)
    batcher = ContinuousBatcher(engine, buckets=tuple(buckets),
                                prefill_workers=1)
    batcher.start()
    try:
        reqs = [batcher.submit(f"audit-{i}", list(p), max_new_tokens)
                for i, p in enumerate(prompts)]
        out = {}
        for i, req in enumerate(reqs):
            if not req.done.wait(timeout=30.0):
                raise TimeoutError(f"audit episode {i} timed out")
            if req.error:
                raise RuntimeError(f"audit episode {i}: {req.error}")
            out[i] = content_hash(i, req.tokens)
        return out
    finally:
        batcher.stop()


def run_rl_drill(episodes: int = 10, rollout_replicas: int = 3,
                 base_active: int = 2, chaos: bool = True,
                 backend: str = "toy", seed: int = 7,
                 staleness_bound: int = 2, timeout_s: float = 240.0,
                 step_delay_s: float = 0.002,
                 schedule: Optional[Dict[str, int]] = None) -> Dict:
    rl_cfg = {
        "episodes": episodes,
        "seed": seed,
        "backend": backend,
        "base_active": base_active,
        "staleness_bound": staleness_bound,
        "step_delay_s": step_delay_s,
        "max_new_tokens": 6,
        "train_batch": 4,
        "schedule": (
            {"borrow_round": 1, "demand_round": 4, "reborrow_round": 6}
            if schedule is None else dict(schedule)
        ),
    }
    if chaos:
        rl_cfg["chaos"] = {
            # rank 1 dies on its first episode ≥ 3 (mid-generation);
            # the learner dies on the train step that would publish v2
            "rollout_die_episode": 3,
            "rollout_die_rank": 1,
            "learner_die_version": 2,
        }

    job = (
        RLJobBuilder()
        .node_num(1)
        .device_per_node(8)
        .config({"rl": rl_cfg})
        .actor("dlrover_tpu.rl.workloads", "LearnerWorkload")
        .num(1)
        .end()
        .rollout("dlrover_tpu.rl.workloads", "RolloutWorkload")
        .num(rollout_replicas)
        .end()
        .trainer("dlrover_tpu.rl.trainer", "RolloutPlaneTrainer")
        .build()
    )
    master = UnifiedMaster(job, job_name="rl-rollout", max_restarts=3)
    t0 = time.monotonic()
    rc = master.run(timeout_s=timeout_s)
    wall = time.monotonic() - t0

    report = master.trainer.report() if master.trainer is not None else {}
    events = master.journal.events()
    kinds: Dict[str, int] = {}
    for e in events:
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
    serve_dirs = {e["data"].get("direction") for e in events
                  if e["kind"] == JournalEvent.SERVE_SCALE}

    audit = report.get("audit", {})
    expected = expected_content_hashes(seeded_prompts(seed, episodes),
                                       backend=backend)
    got = {int(k): v for k, v in audit.get("hashes", {}).items()}
    hash_match = got == expected

    # goodput attribution on the rl stream: how much wall went to moving
    # weights around instead of generating/training
    sync_s = 0.0
    for e in events:
        if e["kind"] in (JournalEvent.RL_WEIGHT_SYNC,
                         JournalEvent.RL_LEARNER_RESTORED):
            sync_s += float(e["data"].get("duration_s", 0.0))
    goodput = {
        "wall_s": round(wall, 3),
        "weight_move_s": round(sync_s, 3),
        "weight_move_frac": round(sync_s / wall, 4) if wall > 0 else 0.0,
    }

    checks = {
        "completed": rc == 0,
        "none_lost": audit.get("lost") == [],
        "none_duplicated": audit.get("duplicates") == [],
        "hash_match": hash_match,
        "staleness_bounded": (
            report.get("max_staleness", 99) <= staleness_bound
            and report.get("staleness_violations", 99) == 0
        ),
    }
    if chaos:
        checks.update({
            "failovers_journaled":
                kinds.get(JournalEvent.UNIFIED_FAILOVER, 0) >= 2,
            "leases_stolen":
                kinds.get(JournalEvent.RL_LEASE_REQUEUED, 0) >= 1,
            "weights_synced":
                kinds.get(JournalEvent.RL_WEIGHT_SYNC, 0) >= 1,
            "learner_restored":
                kinds.get(JournalEvent.RL_LEARNER_RESTORED, 0) >= 1,
            "rose_cycle": {"borrow", "handback"} <= serve_dirs,
            "drains_journaled":
                kinds.get(JournalEvent.RL_ROLLOUT_DRAINED, 0) >= 1,
        })

    return {
        "ok": all(checks.values()),
        "checks": checks,
        "rc": rc,
        "verdict": master.verdict,
        "report": report,
        "goodput": goodput,
        "journal_kinds": kinds,
        "chaos": chaos,
        "episodes": episodes,
    }
