"""Two-agent chaos scenario with a measured training goodput.

The fault-tolerance proof the reference demonstrates with chaos
experiments (docs/tech_report/fault_tolerance_exps.md), as one runnable
script:

1. a master (min_nodes=1, max_nodes=2) and two real agent processes
   train a toy job at world=2; agent 1's worker carries an injected
   per-step compute delay (the chaos plane's ``step.compute`` site),
   and the master's skew monitor must attribute
   ``straggler(rank=1, cause=compute)`` from the op-telemetry uplink —
   journal event + live ``dlrover_skew_ratio`` gauge — while both
   nodes are still alive;
2. one agent is SIGKILLed mid-training — the master's heartbeat monitor
   declares the node dead, shrinks the job elastically, and tells the
   survivor to re-rendezvous; the survivor resumes at world=1 with
   grad-accumulation doubled (fixed global batch) via **checkpoint-free
   live reshard** — the state is pulled from the survivors' sealed shm
   frames (ckpt/reshard.py), and the drill asserts ZERO storage reads
   across every post-fault restore plus a recorded ``reshard`` goodput
   phase;
3. the killed agent comes back, joins the rendezvous, and the world
   scales back to 2;
4. training goodput (productive-span fraction of wall time, the
   BASELINE.json driver metric — reference bar >= 95%) is computed from
   the event streams and printed as ONE JSON line.

Run: ``python examples/chaos_goodput.py`` (CPU; orchestration is the
subject, not the chip).
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

WORKER_SRC = '''
"""Chaos worker: REAL distributed training, not a sleep loop.

Every incarnation bootstraps ``jax.distributed`` through worker.init()
(master-rendezvoused coordinator), builds a dp mesh over the JOINT world
(all processes' devices), and runs a jitted SGD step whose global-batch
mean forces a cross-process reduction — so world formation, re-formation
at a new size after the kill, and collective correctness are all load-
bearing, not simulated. The gradient is exactly 1.0 per step by
construction, so the final weight equals the step count iff no step was
lost or double-applied across shrink/rejoin.
"""
import json, os, sys, time
import numpy as np
from dlrover_tpu import worker
from dlrover_tpu.ckpt import Checkpointer, StorageType
from dlrover_tpu.common.event import TrainEvent, get_emitter

ctx = worker.init()  # initialize_jax_distributed=True: the real path
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ckpt_dir, log_path = sys.argv[1], sys.argv[2]
steps, step_time = int(sys.argv[3]), float(sys.argv[4])
global_batch = int(sys.argv[5])
world = ctx.world_size
# fixed global batch: fewer replicas -> each shards MORE rows of the same
# global batch (the dp resharding folds what grad-accum would stage)
accum = max(1, global_batch // max(1, world))

devices = jax.devices()  # the JOINT world's devices, 1 per process
mesh = Mesh(np.array(devices), ("dp",))
repl = NamedSharding(mesh, P())
data_sh = NamedSharding(mesh, P("dp"))

# collective proof: psum of one 1.0 per device == world size
psum_check = jax.jit(jax.shard_map(
    lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
    in_specs=P("dp"), out_specs=P(),
))
ones = jax.device_put(jnp.ones((len(devices),), jnp.float32), data_sh)
world_check = float(np.asarray(jax.device_get(psum_check(ones)))[0])

D = 8
def loss_fn(w, x):
    # global-batch mean: XLA inserts the cross-process reduction
    return jnp.mean(x @ w)

@jax.jit
def train_step(w, x):
    # one full-global-batch step; x all-ones makes the grad exactly 1.0
    g = jax.grad(loss_fn)(w, x)
    return w + g  # "lr=-1": w increments by exactly 1 per global step

state = {"w": jnp.zeros((D,), jnp.float32), "step": 0}
# single-writer pattern: rank 0 owns the (replicated) state and is the
# only saver — declare the saver group so readiness coordination does not
# wait on ranks that never call save
ckpt = Checkpointer(ckpt_dir, saving_ranks=[0])
state, last = ckpt.load_checkpoint(state)
start = last + 1 if last >= 0 else 0
w = jax.device_put(jnp.asarray(state["w"]), repl)
# identical on every process (device_put requires that multi-process);
# rows/replica = accum * rows-per-micro-batch — fixed global batch
x = jax.device_put(jnp.ones((global_batch, D), jnp.float32), data_sh)
with open(log_path, "a") as f:
    f.write(json.dumps({"event": "segment_start", "rank": ctx.rank,
                        "world": world, "accum": accum, "start": start,
                        "psum": world_check,
                        "w_at_start": float(np.asarray(state["w"])[0]),
                        }) + "\\n")
em = get_emitter(f"worker_{ctx.rank}")
# op-telemetry uplink: TpuTimer spans (pure-python fallback on CPU) feed
# the per-class histograms that publish_step ships to the agent and the
# agent heartbeats to the master's SkewMonitor. The drill schedules a
# step.compute delay fault on agent 1 only, so its worker sleeps inside
# a compute-class span — the master must attribute straggler(rank=1,
# cause=compute) from telemetry alone, mid-drill, before the kill.
from dlrover_tpu.chaos import get_injector
from dlrover_tpu.observability.tpu_timer import KIND_COLL, get_timer
timer = get_timer()
inj = get_injector()
# second fault type: a WEDGED worker (drill --hang-at-step). Rank 0 stops
# stepping OUTSIDE any span (so the stall is unproductive time, honestly
# accounted); its peer then blocks inside the next step's collective. The
# master's hang diagnostician sees the global step stall, broadcasts
# RESTART_WORKER, and the agents soft-restart both workers from the
# checkpoint. The marker file makes the fault one-shot across restarts.
hang_at = int(os.environ.get("DTPU_CHAOS_HANG_AT_STEP", "0"))
hang_marker = os.environ.get("DTPU_CHAOS_HANG_MARKER", "")
for s in range(start, steps):
    with em.span(TrainEvent.TRAINING, step=s, world=world):
        # the injected delay sits in its OWN compute-class span and the
        # psum barrier right after it in a collective span: the slow
        # rank's lost time lands in ITS compute histogram while its
        # peers' matching wait lands in THEIR collective histograms —
        # the separation the skew monitor needs to name the culprit
        with timer.span("injected_compute"):
            if inj is not None:
                inj.fire("step.compute", step=s)
        with timer.span("step_psum", kind=KIND_COLL):
            jax.block_until_ready(psum_check(ones))
        with timer.span("train_step"):
            w = train_step(w, x)
            w.block_until_ready()
        if step_time:
            time.sleep(step_time)  # pace the drill (kill timing)
    ctx.publish_step(s)  # SharedDict: step + op-telemetry snapshot
    if ctx.rank == 0:
        ckpt.save_checkpoint(
            s, {"w": np.asarray(jax.device_get(w)), "step": s},
            StorageType.DISK,
        )
    ctx.report_step(s)
    if (hang_at and hang_marker and s >= hang_at and ctx.rank == 0
            and not os.path.exists(hang_marker)):
        with open(hang_marker, "w") as mf:
            mf.write(str(time.time()))
        with open(log_path, "a") as f:
            f.write(json.dumps({"event": "hang_start", "step": s,
                                "rank": ctx.rank}) + "\\n")
        time.sleep(3600)  # wedged until the watchdog restart kills us
with open(log_path, "a") as f:
    f.write(json.dumps({"event": "done", "rank": ctx.rank, "world": world,
                        "w_final": float(np.asarray(jax.device_get(w))[0]),
                        "psum": world_check}) + "\\n")
'''


def _read_log(log_path):
    if not os.path.exists(log_path):
        return []
    out = []
    with open(log_path) as f:
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
        time.sleep(0.2)
    raise TimeoutError(f"timed out waiting for {what}")


def _scrape_metrics(master):
    """GET /metrics off the master's HTTP server; returns the parsed
    goodput-attribution gauges ({phase: seconds}, wall_seconds, raw_text)
    or (None, None, "") when the scrape fails."""
    import urllib.request

    if master._http_server is None:
        return None, None, ""
    try:
        url = f"http://127.0.0.1:{master._http_server.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as r:
            text = r.read().decode()
    except Exception:  # noqa: BLE001 — drill must report, not die
        return None, None, ""
    phases, wall = {}, None
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name, value = line.rsplit(" ", 1)
        if name == "dlrover_goodput_wall_seconds":
            wall = float(value)
        elif (name.startswith("dlrover_goodput_")
                and name.endswith("_seconds")):
            phases[name[len("dlrover_goodput_"):-len("_seconds")]] = (
                float(value)
            )
    return phases, wall, text


def _merged_goodput(event_dir):
    from dlrover_tpu.common.event import compute_goodput, load_events

    records = []
    for i, name in enumerate(sorted(os.listdir(event_dir))):
        if not name.endswith(".jsonl"):
            continue
        for r in load_events(os.path.join(event_dir, name)):
            # event ids are per-process counters — disambiguate across
            # files so BEGIN/END pairing can't cross streams
            r = dict(r, event_id=(i, r.get("event_id")))
            records.append(r)
    return compute_goodput(records)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("chaos_goodput")
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--step-time", type=float, default=0.15)
    parser.add_argument("--kill-at-step", type=int, default=10)
    parser.add_argument(
        "--hang-at-step", type=int, default=0,
        help="second fault type: rank 0 wedges at this step; the master's "
        "hang diagnostician must detect the stall and restart the "
        "workers (0 = disabled)",
    )
    parser.add_argument("--hang-downtime", type=float, default=4.0)
    parser.add_argument("--global-batch", type=int, default=8)
    parser.add_argument("--keep-workdir", action="store_true")
    args = parser.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    from dlrover_tpu.common.config import get_context
    from dlrover_tpu.master.master import LocalJobMaster

    ctx = get_context()
    ctx.heartbeat_interval_s = 0.5
    ctx.heartbeat_timeout_s = 3.0
    if args.hang_at_step:
        # the hang watchdog must out-wait a normal step but beat the
        # drill's timescale; re-rendezvous resets the PerfMonitor, so
        # recovery windows (no steps yet) can't false-trigger it
        ctx.hang_downtime_s = args.hang_downtime
        ctx.diagnosis_interval_s = 1.0
        ctx.hang_restart_workers = True

    workdir = tempfile.mkdtemp(prefix="dtpu_chaos_")
    event_dir = os.path.join(workdir, "events")
    ckpt_dir = os.path.join(workdir, "ckpt")
    log_path = os.path.join(workdir, "progress.jsonl")
    worker_py = os.path.join(workdir, "chaos_worker.py")
    os.makedirs(event_dir)
    with open(worker_py, "w") as f:
        f.write(WORKER_SRC)

    job = f"chaos{os.getpid()}"
    # the observability spine is part of the drill: the master's /metrics
    # and /events must stay scrapeable through the faults (port 0 = free)
    os.environ.setdefault("DLROVER_TPU_HTTP_PORT", "0")
    # flight recorder: the dead agent must leave a post-mortem bundle here
    bundle_dir = os.path.join(workdir, "bundles")
    os.environ["DLROVER_TPU_TRACE_DIR"] = bundle_dir
    master = LocalJobMaster(
        job_name=job, node_num=2, min_nodes=1, max_nodes=2,
    )
    master.prepare()

    hang_marker = os.path.join(workdir, "hang.marker")

    def start_agent(rank):
        env = dict(os.environ)
        if rank == 1:
            # straggler fault: agent 1's worker sleeps 0.25s inside a
            # compute-class timer span for the first 30 steps of each
            # incarnation (times is per-process). The skew monitor must
            # attribute it from op telemetry BEFORE the kill lands.
            env["DLROVER_FAULT_SCHEDULE"] = \
                "step.compute:delay=0.25@times=30"
        if args.hang_at_step:
            env["DTPU_CHAOS_HANG_AT_STEP"] = str(args.hang_at_step)
            env["DTPU_CHAOS_HANG_MARKER"] = hang_marker
        env.update({
            "JAX_PLATFORMS": "cpu",
            # exactly ONE device per worker process: the joint world's
            # device count must equal the process count for the psum
            # world-check (a test runner's 8-device XLA_FLAGS would leak
            # in otherwise)
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "DLROVER_TPU_EVENT_DIR": event_dir,
            "DLROVER_TPU_HEARTBEAT_INTERVAL_S": "0.5",
            "DLROVER_TPU_HEARTBEAT_TIMEOUT_S": "3",
            # a worker whose peer died has already crashed out of its
            # collective; it lingers only in the distributed client's
            # exit barrier — escalate to SIGKILL fast
            "DLROVER_TPU_WORKER_STOP_GRACE_S": "1",
            "DLROVER_TPU_DIST_SHUTDOWN_S": "5",
        })
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        return subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.agent.run",
                "--nnodes", "1:2", "--node_rank", str(rank),
                "--master_addr", master.addr, "--job_name", job,
                "--nproc_per_node", "1", "--max_restarts", "9",
                "--monitor_interval", "0.1",
                "--ckpt_dir", ckpt_dir,
                worker_py, ckpt_dir, log_path,
                str(args.steps), str(args.step_time),
                str(args.global_batch),
            ],
            env=env, cwd=repo, start_new_session=True,
            stdout=open(
                os.path.join(workdir, f"agent_{rank}.{int(time.time())}.log"),
                "w",
            ),
            stderr=subprocess.STDOUT,
        )

    t_start = time.time()
    segments = []
    agents = {0: start_agent(0), 1: start_agent(1)}
    try:
        # phase 1: both nodes training at world=2
        _wait(
            lambda: sum(
                1 for r in _read_log(log_path)
                if r["event"] == "segment_start" and r["world"] == 2
            ) >= 2,
            90, "both agents training at world=2",
        )
        _wait(
            lambda: master.perf_monitor.completed_global_step
            >= args.kill_at_step,
            90, f"step {args.kill_at_step}",
        )

        # skew attribution: the injected slow rank must surface as a
        # straggler_detected journal verdict naming rank 1 / compute
        # while BOTH nodes are still alive — attribution from telemetry,
        # not from the death the heartbeat monitor sees next
        from dlrover_tpu.observability.journal import JournalEvent

        def _compute_stragglers():
            return [
                e for e in master.event_journal.events()
                if e["kind"] == JournalEvent.STRAGGLER_DETECTED
                and e["data"].get("cause") == "compute"
            ]

        _wait(
            lambda: bool(_compute_stragglers()),
            60, "skew monitor attributes the injected straggler",
        )
        straggler = _compute_stragglers()[0]["data"]
        _, _, skew_text = _scrape_metrics(master)
        skew_ratio_mid = max(
            (float(line.rsplit(" ", 1)[1])
             for line in skew_text.splitlines()
             if line.startswith("dlrover_skew_ratio{")),
            default=0.0,
        )

        # phase 2: kill agent 1 (whole process group: agent + its worker)
        os.killpg(os.getpgid(agents[1].pid), signal.SIGKILL)
        kill_ts = time.time()
        # detection: the master notices the death via the heartbeat
        # connection drop (grace recheck), NOT the heartbeat timeout
        from dlrover_tpu.common.constants import NodeStatus

        _wait(
            lambda: master.job_manager.nodes[1].status == NodeStatus.FAILED
            or master.job_manager.nodes[1].is_released,
            30, "master detects the dead agent",
        )
        detect_s = time.time() - kill_ts
        # the flight recorder auto-captures a node_fault bundle on the
        # same callback that detected the death — a post-mortem artifact
        # exists even though recovery succeeds
        _wait(
            lambda: any(
                "node_fault" in b for b in (
                    os.listdir(bundle_dir)
                    if os.path.isdir(bundle_dir) else []
                )
            ),
            15, "flight-recorder node_fault bundle",
        )
        fault_bundle = os.path.join(bundle_dir, next(
            b for b in sorted(os.listdir(bundle_dir)) if "node_fault" in b
        ))
        _wait(
            lambda: any(
                r["event"] == "segment_start" and r["world"] == 1
                for r in _read_log(log_path)
            ),
            60, "survivor re-rendezvous at world=1",
        )
        # checkpoint-free recovery: the master published the cut record
        # ([0,1] -> [0]) and the survivor must restore by live reshard
        # from the agents' sealed shm frames, never touching storage
        _wait(
            lambda: any(
                e["kind"] == JournalEvent.RESHARD_COMPLETE
                for e in master.event_journal.events()
            ),
            30, "survivor restores via live reshard",
        )
        shrink_s = time.time() - kill_ts
        step_before_rejoin = master.perf_monitor.completed_global_step
        # mid-drill scrape: /metrics must answer while the world is still
        # re-forming, and the gauges must be one consistent snapshot
        mid_phases, mid_wall, _ = _scrape_metrics(master)
        mid_scrape_ok = bool(mid_phases) and mid_wall is not None and (
            abs(sum(mid_phases.values()) - mid_wall) < 1.0
        )

        # phase 3: the node comes back — world scales up again
        agents[1] = start_agent(1)
        _wait(
            lambda: sum(
                1 for r in _read_log(log_path)
                if r["event"] == "segment_start" and r["world"] == 2
            ) >= 4,
            90, "world scaled back to 2",
        )

        # phase 3b (second fault type): rank 0 wedges at --hang-at-step;
        # the master's hang diagnostician must notice the step stall and
        # broadcast a worker restart — the watchdog recovery path, where
        # the SIGKILL above exercised the connection-drop path
        hang_recover_s = None
        if args.hang_at_step:
            _wait(
                lambda: any(
                    r["event"] == "hang_start"
                    for r in _read_log(log_path)
                ),
                # generous: reaching the hang step takes steps*step_time
                60 + args.steps * (args.step_time + 0.6),
                "worker wedge (hang fault)",
            )
            with open(hang_marker) as mf:
                hang_ts = float(mf.read().strip())
            _wait(
                lambda: master.perf_monitor.completed_global_step
                > args.hang_at_step + 1,
                120, "watchdog restart resumed training past the hang",
            )
            hang_recover_s = time.time() - hang_ts

        # phase 4: run to completion (timeout scaled to the drill length)
        _wait(
            lambda: any(
                r["event"] == "done" for r in _read_log(log_path)
            ),
            max(180, args.steps * (args.step_time + 0.6)),
            "training completion",
        )
        for p in agents.values():
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        wall = time.time() - t_start
        # final scrape: the journal's own attribution of the whole drill
        end_phases, end_wall, _ = _scrape_metrics(master)
        end_scrape_ok = bool(end_phases) and end_wall is not None and (
            abs(sum(end_phases.values()) - end_wall) < 1.0
        )
        journal_goodput_pct = (
            round(100.0 * end_phases.get("productive", 0.0) / end_wall, 2)
            if end_scrape_ok and end_wall > 0 else None
        )
        records = _read_log(log_path)
        segments = [r for r in records if r["event"] == "segment_start"]
        dones = [r for r in records if r["event"] == "done"]
        goodput = _merged_goodput(event_dir)
        # checkpoint-free recovery proof: every post-fault restore in the
        # drill (scale-down AND scale-back-up) went through live reshard;
        # storage was never read back (a cold start legitimately probes
        # storage and finds nothing — step stays -1)
        journal_events = master.event_journal.events()
        reshard_completes = [
            e for e in journal_events
            if e["kind"] == JournalEvent.RESHARD_COMPLETE
        ]
        reshard_aborts = [
            e for e in journal_events
            if e["kind"] == JournalEvent.RESHARD_ABORTED
        ]
        storage_restores = [
            e for e in journal_events
            if e["kind"] == JournalEvent.RESTORE_COMPLETE
            and e["data"].get("medium") == "storage"
            and e["data"].get("step", -1) >= 0
        ]
        assert reshard_completes and not storage_restores, (
            f"expected checkpoint-free recovery: "
            f"{len(reshard_completes)} reshard_complete, "
            f"{len(storage_restores)} storage restores"
        )
        reshard_phase_s = (end_phases or {}).get("reshard", 0.0)
        if end_scrape_ok:
            assert reshard_phase_s > 0, (
                "reshard goodput phase missing from /metrics"
            )
        # flight-recorder bundle: traces.json must be a valid chrome
        # trace whose span track includes the rendezvous arc (the kill
        # froze the ring with the world-formation spans still in it)
        bundle_files = sorted(os.listdir(fault_bundle))
        with open(os.path.join(fault_bundle, "traces.json")) as f:
            trace_events = json.load(f)["traceEvents"]
        rdzv_spans = [
            e for e in trace_events
            if e.get("ph") == "X" and e.get("cat") == "span"
            and str(e.get("name", "")).startswith("rdzv.")
        ]
        trace_ids = {
            e["args"]["trace_id"] for e in rdzv_spans
            if "trace_id" in e.get("args", {})
        }
        # the incidents track (timeline.incident_track_events): the
        # bundle was captured AT the fault, so its journal already holds
        # an open incident — the track must parse with >=1 slice
        incident_slices = [
            e for e in trace_events
            if e.get("ph") == "X" and e.get("cat") == "incident"
        ]
        # incident forensics (observability/incidents.py): the drill's
        # fault→recovery episodes as first-class records — the chaos e2e
        # test and bench's recovery section assert MTTR / rung / rollback
        # from these instead of re-deriving them from raw events
        incident_records = [
            inc.to_dict() for inc in master.incident_stitcher.stitch()
        ]
        # this scenario packs one kill + one rejoin into a ~20 s toy job,
        # so the raw fraction is dominated by the fixed recovery cost; the
        # extrapolated figure charges the same measured unproductive time
        # against a 1-hour job — the scale the reference's >=95% goodput
        # bar refers to (its fleet jobs run hours-to-days per fault)
        unproductive = max(0.0, goodput["wall_s"] - goodput["productive_s"])
        result = {
            "metric": "chaos_goodput",
            "goodput_pct": round(100.0 * goodput["goodput"], 2),
            "goodput_1h_extrapolated_pct": round(
                100.0 * (3600.0 - unproductive) / 3600.0, 2
            ),
            "unproductive_s": round(unproductive, 2),
            "wall_s": round(wall, 2),
            "productive_s": round(goodput["productive_s"], 2),
            "detect_s": round(detect_s, 2),
            "shrink_detect_s": round(shrink_s, 2),
            # straggler delay + SIGKILL (+ wedge when enabled)
            "faults_injected": 3 if args.hang_at_step else 2,
            # wedge -> watchdog stall detection -> broadcast restart ->
            # training resumed past the hang step (None = fault disabled)
            "hang_recover_s": (
                round(hang_recover_s, 2) if hang_recover_s else None
            ),
            "step_at_shrink": step_before_rejoin,
            "final_step": master.perf_monitor.completed_global_step,
            # observability spine (journal-derived, via GET /metrics):
            # scrapes must succeed mid-drill AND at the end, with the
            # phase gauges summing to the wall gauge within 1 s
            "metrics_scrape_ok": mid_scrape_ok and end_scrape_ok,
            "phases": (
                {k: round(v, 2) for k, v in end_phases.items()
                 if k != "wall"}
                if end_phases else None
            ),
            "journal_goodput_pct": journal_goodput_pct,
            "journal_events": len(master.event_journal),
            "incidents": incident_records,
            # checkpoint-free elastic resharding (ckpt/reshard.py): both
            # world cuts recovered by pulling state over the host links —
            # storage_restores counts step>=0 storage reads (must be 0)
            "reshard_completes": len(reshard_completes),
            "reshard_aborts": len(reshard_aborts),
            "storage_restores": len(storage_restores),
            "reshard_bytes_remote": sum(
                e["data"].get("bytes_remote", 0)
                for e in reshard_completes
            ),
            "reshard_phase_s": round(reshard_phase_s, 3),
            # skew attribution (op-telemetry uplink -> SkewMonitor): the
            # injected slow rank was named, with cause and ratio, while
            # it was still alive — and the gauge was live on the same
            # mid-drill scrape
            "straggler": {
                k: straggler.get(k) for k in ("rank", "cause", "ratio")
            },
            "skew_ratio_mid": round(skew_ratio_mid, 3),
            "segments": segments,
            # distributed-core proof: every segment's psum equals its
            # world size (real collectives over the joint world), and the
            # final weight equals the step count (grad=1/step by
            # construction — no step lost or doubled across shrink/rejoin)
            # flight recorder (observability/flight_recorder.py): the
            # node death auto-captured a post-mortem bundle whose chrome
            # trace carries the rendezvous arc
            "trace_bundle": os.path.basename(fault_bundle),
            "trace_bundle_files": bundle_files,
            "trace_rdzv_spans": len(rdzv_spans),
            "trace_rdzv_trace_ids": len(trace_ids),
            "trace_incident_slices": len(incident_slices),
            "w_final": max(
                (d.get("w_final", -1.0) for d in dones), default=-1.0
            ),
            "psum_ok": all(
                s.get("psum") == s["world"] for s in segments
            ) and bool(segments),
        }
        print(json.dumps(result))
        return 0
    finally:
        for p in agents.values():
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        master.stop()
        if not args.keep_workdir:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
