"""Elastic serving drill — the serve-plane story as one runnable script.

The serving plane (dlrover_tpu/serving/) run closed-loop on one host:

1. a master starts with the serve registry wired into its liveness
   plane; ``LocalReplicaManager`` spawns N decode-replica subprocesses,
   each registering as a SERVE node, heartbeating on the shared plane,
   and continuous-batching generate requests over a preallocated KV
   cache (bucketed prefill, slot reuse, prefill overlapped with decode);
2. a request router load-balances a closed-loop load generator over the
   live replicas from master membership;
3. chaos SIGKILLs one replica mid-traffic — the master's conn-drop
   grace declares the node lost, the router re-routes every in-flight
   request (greedy decode over replica-identical weights makes the
   retry idempotent: ZERO requests lost), and the traffic-driven
   serving autoscaler riding the deadline-paced ``JobAutoScaler`` tick
   restores the replica count;
4. the drill result — tokens/s, TTFT p50/p99, journal-derived serving
   goodput, the kill/re-route/restore journal — prints as ONE JSON line.

Run: ``python examples/serve_elastic.py`` (CPU; add ``--backend jax``
for the real batched cached-decode engine — the default toy backend
keeps the run under ~5 s).
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dlrover_tpu.serving.drill import run_serving_drill  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(
        description="closed-loop elastic serving drill")
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--backend", default="toy", choices=["toy", "jax"])
    parser.add_argument("--requests", type=int, default=24)
    parser.add_argument("--concurrency", type=int, default=4)
    parser.add_argument("--max-new-tokens", type=int, default=6)
    parser.add_argument("--no-kill", action="store_true",
                        help="skip the mid-traffic replica SIGKILL")
    args = parser.parse_args()
    result = run_serving_drill(
        replicas=args.replicas,
        backend=args.backend,
        num_requests=args.requests,
        concurrency=args.concurrency,
        max_new_tokens=args.max_new_tokens,
        kill_mid_traffic=not args.no_kill,
    )
    print(json.dumps(result), flush=True)
    ok = result["lost"] == 0 and result["completed"] == result["requests"]
    if not args.no_kill:
        ok = ok and result["kill_detected"] and result["replicas_restored"]
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
