"""Matmul replay: re-execute a trace's dominant matmuls to tell a slow
chip from a slow input pipeline.

Reference counterpart: xpu_timer's matmul replay
(py_xpu_timer/parse_matmul.py + the brpc DumpKernelTrace consumer), which
re-runs captured CUDA matmuls standalone. TPU redesign: trace events
(engine.cc traceJson / daemon /dump_trace) carry per-event FLOPs and
duration; the replayer picks the top-k ``mm`` events by total time,
reconstructs equivalent-FLOPs bf16 matmuls (the MXU's achieved rate is a
function of arithmetic intensity, which square tiles of matched FLOPs
reproduce), re-executes them on the local chip, and reports recorded vs
replayed TFLOP/s per kernel. A healthy chip replays at >= the recorded
rate; a degraded chip (thermal, HBM faults) does not — the same verdict
the reference's replay gives, without needing exact shape capture.

Timing chains iterations through ``lax.scan`` (one dispatch for the whole
chain) and ends in ``block_until_ready``.

CLI::

    python -m dlrover_tpu.observability.replay trace.json --top-k 5
    python -m dlrover_tpu.observability.replay http://127.0.0.1:18889/dump_trace
"""

import argparse
import json
import time
from typing import Dict, List, Optional

from dlrover_tpu.common.log import logger


def load_trace(source: str) -> List[Dict]:
    """Trace events from a chrome-trace JSON file or a daemon URL."""
    if source.startswith("http://") or source.startswith("https://"):
        import urllib.request

        with urllib.request.urlopen(source, timeout=30) as resp:
            payload = json.loads(resp.read().decode())
    else:
        with open(source) as f:
            payload = json.load(f)
    if isinstance(payload, dict):
        return payload.get("traceEvents", [])
    return payload


def select_matmuls(events: List[Dict], top_k: int = 5) -> List[Dict]:
    """Aggregate ``mm`` events by name; keep the top-k by total duration.

    Returns [{name, count, total_dur_us, mean_dur_us, flops}] — ``flops``
    is the per-call payload recorded via tt_record/span (0 when the
    producer didn't know it; those can't be replayed and are dropped)."""
    agg: Dict[str, Dict] = {}
    for ev in events:
        if ev.get("cat") != "mm":
            continue
        name = ev.get("name", "?")
        a = agg.setdefault(
            name, {"name": name, "count": 0, "total_dur_us": 0.0,
                   "total_flops": 0.0},
        )
        a["count"] += 1
        a["total_dur_us"] += float(ev.get("dur", 0.0))
        a["total_flops"] += float(ev.get("args", {}).get(
            "flops", ev.get("flops", 0.0)
        ))
    picked = sorted(
        (a for a in agg.values() if a["total_flops"] > 0),
        key=lambda a: -a["total_dur_us"],
    )[:top_k]
    for a in picked:
        a["mean_dur_us"] = a["total_dur_us"] / max(1, a["count"])
        # representative per-call work; the flops-WEIGHTED rate
        # (total/total) is what the report compares against — pairing a
        # max-flops call with a mean duration would inflate the recorded
        # rate whenever call shapes vary
        a["flops"] = a["total_flops"] / max(1, a["count"])
    return picked


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def replay_one(flops: float, iters: int = 10, dtype=None) -> Dict:
    """Execute an equivalent-FLOPs bf16 square matmul chain on the local
    device; returns {n, iters, mean_ms, tflops}."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    # square matmul: 2*n^3 flops; round to the 128-lane MXU tile. Capped:
    # matmuls >= ~2k already saturate the MXU, so a faithful-FLOPs replay
    # of a huge kernel adds minutes and GBs without changing the achieved
    # rate (CPU smoke runs cap harder — they only check plumbing)
    on_tpu = jax.default_backend() == "tpu"
    cap = 4096 if on_tpu else 512
    n = max(256, _round_up(int(round((flops / 2.0) ** (1.0 / 3.0))), 128))
    n = min(n, cap)
    # keep total chain work near a fixed budget (~100ms device time) so
    # the measurement dwarfs dispatch noise even when the cap shrank the
    # per-iteration matmul
    target_flops = 2.0e13 if on_tpu else 2.0e10
    iters = max(iters, int(target_flops / (2.0 * n ** 3)) + 1)
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), dtype=dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), dtype=dtype)

    @jax.jit
    def chain(a, b):
        def body(a, _):
            # data dependency serializes the iterations
            return (a @ b) / jnp.float32(n).astype(a.dtype), None

        a, _ = jax.lax.scan(body, a, None, length=iters)
        return jnp.sum(a.astype(jnp.float32))

    chain(a, b).block_until_ready()  # compile + warmup
    t0 = time.perf_counter()
    chain(a, b).block_until_ready()
    per_iter = max(1e-9, time.perf_counter() - t0) / iters
    return {
        "n": n,
        "iters": iters,
        "mean_ms": round(1e3 * per_iter, 4),
        "tflops": round(2.0 * n ** 3 / per_iter / 1e12, 3),
    }


def replay(source: str, top_k: int = 5, iters: int = 10) -> Dict:
    """Replay a trace's dominant matmuls; per kernel report recorded vs
    replayed TFLOP/s and their ratio (>= ~1.0 → the chip still delivers
    the recorded rate; << 1.0 → chip/HBM degradation, look at hardware,
    not the input pipeline)."""
    events = load_trace(source)
    picked = select_matmuls(events, top_k)
    if not picked:
        logger.warning("no replayable mm events (flops payload missing?)")
    report = {"source": source, "kernels": []}
    for a in picked:
        # flops-weighted achieved rate across all calls of this kernel
        recorded_tflops = (
            a["total_flops"] / (a["total_dur_us"] * 1e-6) / 1e12
            if a["total_dur_us"] > 0 else 0.0
        )
        r = replay_one(a["flops"], iters=iters)
        report["kernels"].append({
            "name": a["name"],
            "count": a["count"],
            "recorded_mean_us": round(a["mean_dur_us"], 2),
            "recorded_tflops": round(recorded_tflops, 3),
            "replayed_tflops": r["tflops"],
            "replay_n": r["n"],
            "ratio": round(
                r["tflops"] / recorded_tflops, 3
            ) if recorded_tflops > 0 else None,
        })
    return report


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser("dlrover_tpu matmul replay")
    parser.add_argument(
        "source", help="chrome-trace JSON file or daemon /dump_trace URL",
    )
    parser.add_argument("--top-k", type=int, default=5)
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args(argv)
    print(json.dumps(replay(args.source, args.top_k, args.iters)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
