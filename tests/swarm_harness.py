"""In-process swarm harness: N simulated agents heartbeating one master.

Each simulated agent is the REAL client stack — a
:class:`~dlrover_tpu.agent.master_client.MasterClient` plus a
:class:`~dlrover_tpu.agent.fanin.HeartbeatRouter` — so the tree
formation, aggregator promotion/demotion and fall-back-to-master paths
exercised here are exactly what a production agent runs; only the
training loop around them is simulated. Agents are partitioned
*contiguously* across a bounded pool of driver threads and every client
is used by exactly one thread, so the socket count stays at one per
agent (RPCClient sockets are thread-local).

The driver threads are PERSISTENT for the swarm's lifetime — one thread
dying between rounds would close its partition's thread-local sockets
and fire a storm of spurious connection-lost hooks into the master,
which is neither what a long-lived agent process does nor what these
drills mean to measure.

Used by the tier-1 swarm smoke tests (small worlds) and the
``swarm``-marked 1000+-agent storm tests.

Typical use::

    swarm = Swarm(master.addr, world=256)
    swarm.settle()                      # let the tree form (flat: no-op)
    stats = swarm.beat(rounds=3)        # stats["p99_ms"], stats["errors"]
    swarm.kill_aggregator(swarm.aggregator_ids()[0])
    swarm.close()
"""

import queue
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from dlrover_tpu.agent.fanin import HeartbeatRouter
from dlrover_tpu.agent.master_client import MasterClient

# A 1024-agent swarm in ONE interpreter runs >1000 threads; CPython's
# default 5ms GIL switch interval then adds tens of ms of pure
# thread-scheduling convoy noise to every latency tail — noise a real
# fleet (one process per agent) does not have. Tighten the handoff so
# the measured tails reflect the control plane, not the simulator.
sys.setswitchinterval(0.001)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of an unsorted sequence (q in [0, 100])."""
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = max(0, min(len(ordered) - 1,
                     int(round(q / 100.0 * (len(ordered) - 1)))))
    return ordered[idx]


def make_op_telemetry(rank: int, n: int = 5,
                      mean_us: float = 100.0) -> Dict[str, Any]:
    """A minimal-but-real op-telemetry envelope (one rank per node) so
    swarm beats exercise the master's skew-ingest path, not just
    liveness."""
    from dlrover_tpu.observability.op_telemetry import (
        OpClass,
        OpClassHistogram,
    )

    h = OpClassHistogram()
    for _ in range(n):
        h.observe(mean_us)
    return {str(rank): {
        "seq": n,
        "classes": {OpClass.COMPUTE: h.to_wire()},
        "last_collective": {"name": "psum_grads", "seq": 1},
    }}


class Swarm:
    """A fleet of simulated agents sharing one master address."""

    def __init__(self, master_addr: str, world: int, drivers: int = 16,
                 start_id: int = 0):
        self.world = world
        self.node_ids = list(range(start_id, start_id + world))
        self.routers: Dict[int, HeartbeatRouter] = {
            nid: HeartbeatRouter(MasterClient(master_addr, nid))
            for nid in self.node_ids
        }
        n_drivers = max(1, min(drivers, world))
        # contiguous partitioning: driver d owns one id range, so a tree
        # group's children mostly share a driver and each MasterClient is
        # only ever touched by its one driver thread
        per = (world + n_drivers - 1) // n_drivers
        self.partitions: List[List[int]] = [
            self.node_ids[i:i + per]
            for i in range(0, world, per)
        ]
        self._cmd_qs: List["queue.Queue"] = []
        self._done_q: "queue.Queue" = queue.Queue()
        self._threads: List[threading.Thread] = []
        for i, part in enumerate(self.partitions):
            q: "queue.Queue" = queue.Queue()
            t = threading.Thread(
                target=self._drive, args=(part, q),
                name=f"swarm-driver-{i}", daemon=True,
            )
            t.start()
            self._cmd_qs.append(q)
            self._threads.append(t)

    def _drive(self, ids: List[int], cmd_q: "queue.Queue") -> None:
        while True:
            cmd = cmd_q.get()
            if cmd is None:
                # closing the routers HERE keeps the teardown in the one
                # thread that owns these clients' thread-local sockets
                for nid in ids:
                    self.routers[nid].close()
                return
            rounds, interval_s, telemetry_fn, global_step = cmd
            lat_ms: List[float] = []
            errors = 0
            hints = 0
            for rnd in range(rounds):
                for nid in ids:
                    telemetry = (telemetry_fn(nid, rnd)
                                 if telemetry_fn is not None else None)
                    t0 = time.monotonic()
                    try:
                        resp = self.routers[nid].heartbeat(
                            global_step=global_step + rnd,
                            step_timestamp=time.time(),
                            rdzv_round=0,
                            op_telemetry=telemetry,
                        )
                    except ConnectionError:
                        errors += 1
                        continue
                    lat_ms.append((time.monotonic() - t0) * 1000.0)
                    if resp.backoff_hint_s > 0:
                        hints += 1
                if interval_s > 0 and rnd != rounds - 1:
                    time.sleep(interval_s)
            self._done_q.put((lat_ms, errors, hints))

    # -- heartbeat rounds ---------------------------------------------------

    def beat(
        self,
        rounds: int = 1,
        interval_s: float = 0.0,
        telemetry_fn: Optional[Callable[[int, int], Dict[str, Any]]] = None,
        global_step: int = 0,
    ) -> Dict[str, Any]:
        """Drive ``rounds`` heartbeats for every agent and return latency/
        error stats. ``telemetry_fn(node_id, round)`` optionally attaches
        an op-telemetry payload per beat."""
        t_start = time.monotonic()
        for q in self._cmd_qs:
            q.put((rounds, interval_s, telemetry_fn, global_step))
        latencies_ms: List[float] = []
        errors = 0
        hints = 0
        for _ in self._cmd_qs:
            lat, err, hnt = self._done_q.get()
            latencies_ms.extend(lat)
            errors += err
            hints += hnt
        wall_s = time.monotonic() - t_start
        return {
            "beats": len(latencies_ms),
            "errors": errors,
            "wall_s": wall_s,
            "p50_ms": percentile(latencies_ms, 50),
            "p99_ms": percentile(latencies_ms, 99),
            "max_ms": max(latencies_ms) if latencies_ms else 0.0,
            "backoff_hints": hints,
        }

    def settle(self, rounds: int = 4, flush_wait_s: float = 0.0) -> None:
        """Let the tree form: round 1 hands out aggregator roles, round 2
        registers subtree addresses (epoch bump), rounds 3–4 parent the
        children. Flat mode: cheap no-op rounds."""
        for _ in range(rounds):
            self.beat(rounds=1)
        if flush_wait_s > 0:
            time.sleep(flush_wait_s)

    # -- tree introspection / chaos hooks -----------------------------------

    def aggregator_ids(self) -> List[int]:
        return sorted(
            nid for nid, r in self.routers.items()
            if r.aggregator is not None and r.aggregator.alive
        )

    def parented_ids(self) -> List[int]:
        """Agents currently beating an aggregator rather than the master."""
        return sorted(
            nid for nid, r in self.routers.items()
            if r._parent_client is not None
        )

    def kill_aggregator(self, node_id: int) -> None:
        """SIGKILL-equivalent for an aggregator-role agent: its subtree
        server and master sockets die without any goodbye RPC (the
        master's on_disconnect hook is the only signal)."""
        agg = self.routers[node_id].aggregator
        assert agg is not None, f"node {node_id} is not an aggregator"
        agg.kill()

    def close(self) -> None:
        for q in self._cmd_qs:
            q.put(None)
        for t in self._threads:
            t.join(timeout=10.0)
