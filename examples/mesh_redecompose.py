"""Elastic mesh re-decomposition drill: 8 hosts → kill 2 → re-form 3×2.

The ISSUE-17 acceptance scenario as one seeded, runnable script:

1. eight REAL host processes each seal their (data=2, fsdp=4, tp=1)
   shard of a toy model into shm and serve it over a ``ReshardService``
   registered in a live ``LocalJobMaster``'s KV, then sit in a stepping
   loop;
2. the master's skew monitor is fed real wire-format op-telemetry
   snapshots (60/40 compute/collective) and the decomposition planner's
   shared step-time EWMA observes the hosts' measured step times at the
   old shape — the two signals the cost model calibrates from;
3. two hosts (ranks 5 and 7) are SIGKILLed mid-step; the world cut runs
   through the SAME ``ReshardCoordinator`` the master wired at
   construction: the planner re-decomposes the 6 survivors as
   **DP×TP = 3×2**, the choice is journaled as an open brain prediction,
   and the versioned ``ParallelConfig`` pipe carries the new shape;
4. the re-formed job restores by **cross-layout live reshard** — one
   real ``CheckpointEngine.load`` on a 6-device (3,1,2) jax mesh (the
   journaled ``reshard_complete`` path) plus per-rank ``restore_regions``
   for every new rank, each verified bit-exact against the canonical
   global state, with an empty checkpoint dir proving **zero storage
   reads**;
5. a paced step loop at the new shape feeds the measured step time back
   through ``observe_step_time``, settling the prediction hit/miss like
   any other brain prediction;
6. a second cut with ``reshard.replan:error`` chaos proves planner
   failure degrades to a same-decomposition reshard, journaled with its
   reason.

Prints ONE JSON line. Run: ``python examples/mesh_redecompose.py``
(CPU; orchestration is the subject, not the chip).
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# the driver hosts the re-formed (3,1,2) mesh: 6 virtual CPU devices
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=6"
).strip()

HOST_SRC = '''
"""One old-world host: seals its (2,4,1) decomposition shard into shm,
serves it over a ReshardService registered in the master KV, then steps.
No jax import — a host is the agent-side survivor, not a worker."""
import json, sys, time
import numpy as np

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.ckpt.reshard import ReshardService, region_for_coords
from dlrover_tpu.ckpt.shm_handler import SharedMemoryHandler, shm_name
from dlrover_tpu.parallel.replan import Decomposition, default_leaf_spec

master_addr, job, rank_s, step_s, base_s, log_path = sys.argv[1:7]
rank, step, base = int(rank_s), int(step_s), float(base_s)

GLOBALS = {
    "['w']": (np.arange(48 * 8, dtype=np.float32).reshape(48, 8) * 0.5
              - 7.0),
    "['b']": np.arange(48, dtype=np.float32) * -0.25,
}
src = Decomposition(data=2, fsdp=4, tp=1)
coords = src.coords(rank)

leaves, blocks, offset = [], [], 0
for path, arr in GLOBALS.items():
    spec = default_leaf_spec(arr.shape)
    start, shape = region_for_coords(
        arr.shape, spec, src.axis_sizes(), coords)
    if any(s == 0 for s in shape):
        continue
    sl = tuple(slice(l, l + s) for l, s in zip(start, shape))
    block = np.ascontiguousarray(arr[sl])
    leaves.append({
        "path": path, "kind": "array", "dtype": str(arr.dtype),
        "gshape": list(arr.shape),
        "shards": [{"offset": offset, "nbytes": block.nbytes,
                    "lshape": list(shape), "start": list(start)}],
    })
    blocks.append(block)
    offset += block.nbytes
leaves.append({"path": "['lr']", "kind": "value", "value": 0.125})

shm = SharedMemoryHandler(shm_name(job, rank, 0))
shm.write_frame({
    "step": step, "ts": 0.0, "job": job, "node_rank": rank,
    "local_rank": 0, "rank": rank, "world_size": 8, "leaves": leaves,
}, blocks)

svc = ReshardService(shm_provider=lambda: [shm])
svc.start()
client = MasterClient(master_addr, rank)
svc.register(client, job, rank)

# one measured step at the OLD decomposition (paced toy compute): the
# planner's step-time EWMA is calibrated from what hosts actually report
t0 = time.perf_counter()
time.sleep(base)
dt = time.perf_counter() - t0
with open(log_path, "a") as f:
    f.write(json.dumps({"event": "ready", "rank": rank,
                        "step_time_s": dt}) + "\\n")

while True:  # stepping loop: the SIGKILL lands mid-step here
    time.sleep(base)
    with open(log_path, "a") as f:
        f.write(json.dumps({"event": "stepping", "rank": rank}) + "\\n")
'''

OLD_DECOMP = (2, 4, 1)
KILL_RANKS = (5, 7)
SURVIVORS = (0, 1, 2, 3, 4, 6)


def _globals():
    import numpy as np

    return {
        "['w']": (np.arange(48 * 8, dtype=np.float32).reshape(48, 8)
                  * 0.5 - 7.0),
        "['b']": np.arange(48, dtype=np.float32) * -0.25,
    }


def _read_log(path):
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return out


def _wait(cond, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.1)
    raise TimeoutError(f"timed out waiting for {what}")


def _seed_op_telemetry(master, world, compute_frac=0.6):
    """Two wire-format snapshots per rank → the skew monitor's window
    deltas carry a fleet 60/40 compute/collective split (equal across
    ranks: no spurious straggler verdicts)."""
    def snap(seq, scale):
        return {
            "seq": seq,
            "classes": {
                "compute": {"b": [], "sum": 1e6 * compute_frac * scale,
                            "max": 0.0, "n": 10 * scale},
                "collective": {
                    "b": [], "sum": 1e6 * (1 - compute_frac) * scale,
                    "max": 0.0, "n": 10 * scale},
            },
        }

    for rank in range(world):
        master.skew_monitor.observe(rank, {str(rank): snap(10, 1)})
        master.skew_monitor.observe(rank, {str(rank): snap(20, 2)})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("mesh_redecompose")
    parser.add_argument("--step", type=int, default=42,
                        help="the step every host seals")
    parser.add_argument("--base-step-time", type=float, default=0.05)
    parser.add_argument("--measure-steps", type=int, default=5)
    parser.add_argument("--keep-workdir", action="store_true")
    args = parser.parse_args(argv)

    import numpy as np

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.brain.optimizers import StepTimeModel
    from dlrover_tpu.chaos import configure as chaos_configure
    from dlrover_tpu.chaos import reset_injector
    from dlrover_tpu.ckpt.engine import CheckpointEngine
    from dlrover_tpu.ckpt.reshard import (
        ReshardRestorer,
        needs_from_layout,
    )
    from dlrover_tpu.ckpt.shm_handler import shm_name
    from dlrover_tpu.common.constants import EnvKey, RendezvousName
    from dlrover_tpu.common.multi_process import unlink_shared_memory
    from dlrover_tpu.master.master import LocalJobMaster
    from dlrover_tpu.observability.journal import JournalEvent
    from dlrover_tpu.parallel.replan import (
        Decomposition,
        default_leaf_spec,
    )

    workdir = tempfile.mkdtemp(prefix="dtpu_redecomp_")
    ckpt_dir = os.path.join(workdir, "ckpt")
    log_path = os.path.join(workdir, "hosts.jsonl")
    host_py = os.path.join(workdir, "redecomp_host.py")
    os.makedirs(ckpt_dir)
    with open(host_py, "w") as f:
        f.write(HOST_SRC)

    job = f"redecomp{os.getpid()}"
    old = Decomposition(*OLD_DECOMP)
    globals_ = _globals()
    master = LocalJobMaster(job_name=job, node_num=8, min_nodes=4,
                            max_nodes=8)
    master.prepare()
    # the launch decomposition enters the versioned ParallelConfig pipe
    master.strategy_generator.set_decomposition(*OLD_DECOMP,
                                                reason="launch")
    # the planner's EWMA is the brain advisor's StepTimeModel when the
    # brain is on; this drill runs brainless, so attach a fresh one
    master.mesh_planner.step_time_model = StepTimeModel()
    coordinator = master.rdzv_managers[
        RendezvousName.TRAINING].reshard_coordinator

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)

    def start_host(rank):
        return subprocess.Popen(
            [sys.executable, host_py, master.addr, job, str(rank),
             str(args.step), str(args.base_step_time), log_path],
            env=env, cwd=repo, start_new_session=True,
            stdout=open(os.path.join(workdir, f"host_{rank}.log"), "w"),
            stderr=subprocess.STDOUT,
        )

    hosts = {r: start_host(r) for r in range(8)}
    try:
        # phase 1: all 8 hosts sealed + serving + stepping
        _wait(
            lambda: {r["rank"] for r in _read_log(log_path)
                     if r["event"] == "ready"} == set(range(8)),
            60, "all 8 hosts sealed and registered",
        )
        ready = [r for r in _read_log(log_path) if r["event"] == "ready"]
        old_step_s = float(np.mean([r["step_time_s"] for r in ready]))
        # calibration: measured old-shape step time + fleet op split
        master.mesh_planner.observe_step_time(old, old_step_s)
        _seed_op_telemetry(master, 8, compute_frac=0.6)
        _wait(
            lambda: any(r["event"] == "stepping"
                        for r in _read_log(log_path)),
            30, "hosts stepping",
        )

        # phase 2: SIGKILL 2 of 8 mid-step
        for r in KILL_RANKS:
            os.killpg(os.getpgid(hosts[r].pid), signal.SIGKILL)

        # phase 3: the world cut re-plans the decomposition
        t0 = time.perf_counter()
        cut = coordinator.on_world_cut(
            list(range(8)), list(SURVIVORS), round_=1)
        replan_latency_s = time.perf_counter() - t0
        new = Decomposition.from_wire(cut["new_decomp"])
        predicted = [
            e for e in master.event_journal.events()
            if e["kind"] == JournalEvent.BRAIN_PREDICTED_DECOMPOSITION
        ]
        cfg = master.strategy_generator.config

        # phase 4: cross-layout live reshard, zero storage reads.
        # new rank 0 restores through the REAL engine ladder on a
        # 6-device (3,1,2) jax mesh (journals reshard_start/complete)...
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        os.environ[EnvKey.RDZV_ROUND] = "1"
        devices = np.array(jax.devices()[:6]).reshape(
            new.data, new.fsdp, new.tp)
        mesh = Mesh(devices, ("data", "fsdp", "tp"))
        state = {
            "w": jax.device_put(
                jnp.asarray(globals_["['w']"]),
                NamedSharding(mesh, P("fsdp", "tp"))),
            "b": jax.device_put(
                jnp.asarray(globals_["['b']"]),
                NamedSharding(mesh, P("fsdp"))),
            "lr": 0.125,
        }
        c0 = MasterClient(master.addr, 0)
        engine = CheckpointEngine(
            ckpt_dir, job_name=job, node_rank=0, local_rank=0,
            ipc_socket="/nonexistent", world_size=1, rank=0,
            master_client=c0,
        )
        t0 = time.perf_counter()
        restored, restored_step = engine.load(state)
        engine_reshard_s = time.perf_counter() - t0
        bit_exact = bool(
            np.array_equal(np.asarray(restored["w"]), globals_["['w']"])
            and np.array_equal(np.asarray(restored["b"]),
                               globals_["['b']"])
            and restored["lr"] == 0.125
        )

        # ...and every other new rank pulls exactly its target regions
        # (restore_regions: spec-only needs, no placed state required)
        leaves = {p: (str(a.dtype), a.shape) for p, a in globals_.items()}
        specs = {p: default_leaf_spec(a.shape) for p, a in globals_.items()}
        bytes_moved = regions_verified = 0
        for nr in range(1, new.world):
            needs = needs_from_layout(
                leaves, specs, new.axis_sizes(), [new.coords(nr)])
            restorer = ReshardRestorer(
                job, MasterClient(master.addr, nr), node_rank=nr)
            regions, got_step, stats = restorer.restore_regions(cut, needs)
            bit_exact = bit_exact and got_step == args.step
            for path, need in needs.items():
                for ridx, (rstart, rshape) in enumerate(need.regions):
                    sl = tuple(slice(l, l + s)
                               for l, s in zip(rstart, rshape))
                    if not np.array_equal(regions[path][ridx],
                                          globals_[path][sl]):
                        bit_exact = False
                    regions_verified += 1
            bytes_moved += stats["bytes"]

        # phase 5: measured step time at the NEW shape settles the
        # prediction (paced toy steps; pacing models the fixed-global-
        # batch compute spread plus the smaller ring all-reduce)
        fc, fl = 0.6, 0.4
        ring = lambda n: (n - 1) / n if n > 1 else 0.0  # noqa: E731
        pace = old_step_s * (
            fc * old.world / new.world
            + fl * (ring(new.dp_total) / new.tp)
            / (ring(old.dp_total) / old.tp)
        )
        t0 = time.perf_counter()
        for _ in range(args.measure_steps):
            time.sleep(pace)
        measured_new_s = (time.perf_counter() - t0) / args.measure_steps
        master.mesh_planner.observe_step_time(new, measured_new_s)
        scored = [
            e for e in master.event_journal.events()
            if e["kind"] == JournalEvent.BRAIN_PREDICTION_SCORED
            and e["data"].get("prediction_kind") == "decomposition"
        ]

        # phase 6: planner failure degrades cleanly (chaos site)
        chaos_configure("reshard.replan:error@times=1", seed=17)
        cut2 = coordinator.on_world_cut(
            list(SURVIVORS), list(SURVIVORS)[:5], round_=2)
        reset_injector()
        degraded = [
            e for e in master.event_journal.events()
            if e["kind"] == JournalEvent.RESHARD_REPLAN_DEGRADED
        ]

        # the proof terms: reshard completions vs storage reads
        events = master.event_journal.events()
        reshard_completes = [
            e for e in events if e["kind"] == JournalEvent.RESHARD_COMPLETE
        ]
        storage_restores = [
            e for e in events
            if e["kind"] == JournalEvent.RESTORE_COMPLETE
            and e["data"].get("medium") == "storage"
            and e["data"].get("step", -1) >= 0
        ]
        result = {
            "metric": "mesh_redecompose",
            "old_decomp": list(OLD_DECOMP),
            "new_decomp": cut["new_decomp"],
            "mesh_version": cut.get("mesh_version"),
            "config_mesh": [cfg.mesh_data, cfg.mesh_fsdp, cfg.mesh_tp],
            "killed_ranks": list(KILL_RANKS),
            "replan_latency_s": round(replan_latency_s, 4),
            "predicted_step_s": round(
                predicted[0]["data"]["predicted_step_time_s"], 4),
            "old_shape_predicted_s": round(
                predicted[0]["data"]["old_shape_predicted_s"], 4),
            "measured_old_step_s": round(old_step_s, 4),
            "measured_new_step_s": round(measured_new_s, 4),
            "prediction_outcome": (
                scored[0]["data"]["outcome"] if scored else None),
            "restored_step": restored_step,
            "engine_reshard_s": round(engine_reshard_s, 3),
            "reshard_completes": len(reshard_completes),
            "storage_restores": len(storage_restores),
            "reshard_bytes_remote": sum(
                e["data"].get("bytes_remote", 0)
                for e in reshard_completes),
            "bytes_moved": bytes_moved,
            "regions_verified": regions_verified,
            "bit_exact": bit_exact,
            "ckpt_dir_empty": not any(
                n.startswith("step_") for n in os.listdir(ckpt_dir)),
            "degraded_round2": {
                "happened": bool(degraded),
                "reason": degraded[0]["data"]["reason"]
                if degraded else None,
                "decomp_kept": cut2["new_decomp"] == cut2["old_decomp"],
            },
        }
        print(json.dumps(result))
        return 0
    finally:
        for p in hosts.values():
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        master.stop()
        for r in range(8):
            unlink_shared_memory(shm_name(job, r, 0))
        if not args.keep_workdir:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
