"""Job kind ``resume``: the training job of ``jobs/train.py`` as a user
starts it, under the agent, with its worker killed from outside.

    python -m dlrover_tpu.agent.run --standalone --nproc-per-node=1
        --network-check --ckpt-dir <work directory>/persist
        benchmarks/jobs/resume.py <work directory>/env.json

Node check, local master, rendezvous, then this file as the worker script
(its ``__main__``): ``jobs/train.py``'s own phases (``bootstrap`` with
``worker.init()``, ``build_model``, ``check_reference``, ``build_trainer``,
``warm_up``, ``open_checkpointer``, ``window``, ``restores``, ``result``)
with a ``Checkpointer`` that has the agent behind it. The benchmark's own
process (``run`` below) never touches JAX: a process that has touched the
chip keeps it from every child. It starts the agent, reads what the worker
writes, sends the signal and reads the agent's exit code. The work
directory is the run's temporary directory (``tempfile``: ``TMPDIR``), and
the agent's ``--ckpt-dir`` lies in it: the breakpoint persist goes where a
deployment's does, to the machine's disk, one frame a kill.

**The fault lies inside set-up.** The first worker does the set-up of the
flash-save traffic (reference check, warm-up, the first memory save,
waited for) and trains on, reporting every step's loss. As it reports the
loss of the ``kill_after_drained_save_steps``-th step after the newest
save whose drain has ended, this process sends it SIGKILL. The agent
records the death, persists the shm frame, goes through rendezvous again
and starts a worker, which (as ``examples/llama_elastic_pretrain.py``
does) makes its state from the seed, restores into it from shm and takes
its first step: kill to that step's loss on the host, both
``time.monotonic()`` of the one host, is the wall time of a resume, the
per-layer ``resume.wall_s`` (``harness/resume_path.py`` cuts it into its
parts). The resumed worker then warms up, saves, and trains **the measured
window** exactly as the flash-save traffic does (whole save cycles): so
``setup_s``, benchmark process start to window start, holds the whole
resume, and ``tokens_per_s`` is a resumed job's, under the agent. With
``kills`` over 1 a resumed worker that is not the last saves, trains on and
is killed in its turn by the same rule, so that every kill finds a frame
the agent has not persisted yet. After the window a stop file tells the
worker to end, the agent's exit code is read, and what the agent persisted
at each kill is read back from the disk.

Traffic parameters (``benchmarks/traffic/<name>.json``), beside those of
``jobs/train.py`` (``seq``, ``grad_accum``, ``rows_per_replica``,
``save_every_steps``, ``trace_steps``; ``restore_warmups`` and
``restores_after_window`` are 0: the restore this job times is the
resumed worker's):

- ``program_runs_in``: ``"child processes"``, which keeps ``run.py`` off
  the chip (``README.md``);
- ``kill_after_drained_save_steps``: the rule above;
- ``kills``: how many times a run kills and resumes before the window.

``correct``: the window's own (``jobs/train.py``; the reference check is
the first worker's), and for every kill the comparisons of ``compared``
below, each exact: the agent spent one restart a kill and left with exit
code 0; the restore came from shm and gave the step of the newest drained
save; the digest of every restored leaf (the wrap-around sum of its bits,
taken on the device) is the one taken as that save was made, and so is
the digest of every leaf of the frame the agent persisted, read back
through the program's storage reader (manifest chain, every stripe's CRC)
and summed on the host; the loss of the first resumed step is, to the
last bit, the loss the killed worker reported for that step.
"""

import contextlib
import fcntl
import glob
import hashlib
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import types

T_WORKER = time.monotonic()  # a worker's first line: ``worker_started``

# seconds the benchmark waits for the worker's next line before it gives
# the run up: a checkout's first run compiles in set-up and, the resumed
# worker's programs, before the window; a resume has been a minute
WAIT_SETUP_S = 1100
WAIT_WINDOW_S = 600
WAIT_RESUME_S = 600
WAIT_AGENT_EXIT_S = 120
# a rehearsal's steps take milliseconds: paced, so that the signal sent
# after step S + K lands a few steps later and not hundreds, as on the chip
REHEARSAL_STEP_S = 0.05


class ResumeFailure(Exception):
    """The run cannot be measured: a process died or never answered."""


# -- lines a worker writes, read by the benchmark's process -----------------


class Recorder:
    """One JSON object a line, flushed: what is written survives the
    writer's SIGKILL (the kernel has it)."""

    def __init__(self, path, inc):
        self._path, self.inc = path, inc

    def __call__(self, event, **fields):
        fields.setdefault("t", time.monotonic())
        with open(self._path, "a") as f:
            f.write(json.dumps({"event": event, "inc": self.inc, **fields})
                    + "\n")


class Tail:
    """New whole lines of a file another process appends to."""

    def __init__(self, path):
        self._path, self._at, self._part = path, 0, ""

    def read(self):
        try:
            with open(self._path) as f:
                f.seek(self._at)
                text = self._part + f.read()
                self._at = f.tell()
        except FileNotFoundError:
            return []
        *lines, self._part = text.split("\n")
        return [json.loads(line) for line in lines if line]


# -- the benchmark's process ------------------------------------------------


def send_kill(pid):
    """The fault. A function of its own so that a test can plant a second
    fault beside it (``benchmarks/tests/test_resume.py``)."""
    os.kill(pid, signal.SIGKILL)


def run(env) -> dict:
    """``env`` as ``jobs/train.py``'s, with ``family`` ``None``: the
    worker loads it."""
    with contextlib.ExitStack() as cleanup:
        return _run(env, cleanup)


def _checkout_lock(cleanup, job_name):
    """One run of a checkout at a time: a second one would take the
    first's frame, socket and chip (tests under several workers; never the
    driver). The file is unlinked by the holder as it ends, so a waiter
    that gets the lock looks whether its file is still the name's."""
    name = f"/dev/shm/dlrtpu_{job_name}.lock"
    while True:
        lock = open(name, "w")
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            if os.fstat(lock.fileno()).st_ino == os.stat(name).st_ino:
                break
        except FileNotFoundError:
            pass
        lock.close()

    def release():
        with contextlib.suppress(OSError):
            os.unlink(name)
        lock.close()

    cleanup.callback(release)


def _run(env, cleanup) -> dict:
    args, traffic, note = env["args"], env["traffic"], env["note"]
    workdir = tempfile.mkdtemp(prefix="dlrover_bench_resume_")
    cleanup.callback(shutil.rmtree, workdir, ignore_errors=True)
    # a name of this checkout's own: two checkouts share nothing, and the
    # agent unlinks what a killed run of the same checkout left in /dev/shm
    job_name = "bench" + hashlib.blake2b(
        env["root"].encode(), digest_size=6).hexdigest() + "r"
    _checkout_lock(cleanup, job_name)

    def unlink_frames():
        for segment in glob.glob(f"/dev/shm/dlrtpu_{job_name}_*"):
            with contextlib.suppress(OSError):
                os.unlink(segment)

    unlink_frames()
    cleanup.callback(unlink_frames)

    kills = traffic["kills"]
    path = lambda name: os.path.join(workdir, name)  # noqa: E731
    # where the agent persists the frame: the run's temporary directory
    persist_dir = path("persist")
    with open(path("env.json"), "w") as f:
        json.dump({
            "args": {"seed": args.seed, "seconds": args.seconds,
                     "trace": args.trace, "rehearsal": args.rehearsal},
            "cell": env["cell"], "fields": env["fields"],
            "traffic": traffic, "root": env["root"],
            "t_start": env["t_start"], "workdir": workdir,
            "ckpt_dir": persist_dir}, f)
    agent_env = dict(os.environ)
    agent_env["DLROVER_TPU_EVENT_DIR"] = path("agent_events")
    argv = [
        sys.executable, "-m", "dlrover_tpu.agent.run", "--standalone",
        "--nproc-per-node=1", "--network-check",
        f"--max-restarts={kills + 1}", "--job-name", job_name,
        "--ckpt-dir", persist_dir, os.path.abspath(__file__),
        path("env.json"),
    ]
    with open(path("agent.log"), "ab") as log:
        agent = subprocess.Popen(  # noqa: S603
            argv, cwd=env["root"], env=agent_env, start_new_session=True,
            stdout=log, stderr=subprocess.STDOUT)

    def stop_everything():
        with contextlib.suppress(ProcessLookupError):
            os.killpg(agent.pid, signal.SIGKILL)
        agent.wait()

    cleanup.callback(stop_everything)

    def agent_log():
        with open(path("agent.log"), errors="replace") as f:
            return f.read()

    tail = Tail(path("worker.jsonl"))
    seen = []  # every line of every worker so far

    def wait_for(found, seconds, what):
        """The first new line for which ``found(line)`` holds. Notes are
        printed as they come."""
        deadline = time.monotonic() + seconds
        while True:
            for line in tail.read():
                seen.append(line)
                if line["event"] == "note":
                    note(line["kind"], inc=line["inc"], **line["fields"])
                if found(line):
                    return line
            if agent.poll() is not None:
                sys.stderr.write(agent_log()[-6000:])
                raise ResumeFailure(
                    f"the agent left with {agent.returncode} before {what}")
            if time.monotonic() > deadline:
                sys.stderr.write(agent_log()[-6000:])
                raise ResumeFailure(f"no {what} within {seconds} s")
            time.sleep(0.005)

    # -- set-up in the first worker ---------------------------------------
    device = wait_for(lambda e: e["event"] == "backend", WAIT_SETUP_S,
                      "backend in the first worker")
    facts = {k: device[k] for k in ("platform", "kind", "found")}
    if facts["platform"] != "tpu" and not args.rehearsal:
        return {"device": facts}  # run.py refuses it

    # -- kill, resume: still set-up -------------------------------------------
    after = traffic["kill_after_drained_save_steps"]
    if args.rehearsal:
        after = 8  # half of a rehearsal's save cycle (jobs/train.py)
    stamps, walls, compared, saves = [], [], {}, []  # a resume that never
    # comes back ends the run in ``wait_for``, without a result

    def of(event, inc, **match):
        return [e for e in seen if e["event"] == event and e["inc"] == inc
                and all(e.get(key) == v for key, v in match.items())]

    inc = 0  # the worker the next kill is for
    for k in range(1, kills + 1):

        def due(line):
            drained = of("drained", inc)
            return (line["event"] == "step" and line["inc"] == inc and drained
                    and line["step"] >= drained[-1]["step"] + after)

        last = wait_for(due, WAIT_SETUP_S, f"step to kill worker {inc} at")
        saved = of("drained", inc)[-1]["step"]
        pid = of("backend", inc)[0]["pid"]
        stamp = {"t": time.monotonic(),
                 "wall_minus_monotonic": time.time() - time.monotonic()}
        send_kill(pid)
        stamps.append(stamp)
        note("kill", kill=k, pid=pid, after_step=last["step"],
             newest_drained_save=saved)
        first = wait_for(
            lambda e: e["event"] == "first_step" and e["inc"] > inc,
            WAIT_RESUME_S, f"first step after kill {k}")
        walls.append(first["t"] - stamp["t"])
        killed, inc = inc, first["inc"]
        # every comparison is exact: limit 0, or equality
        restore = of("restore", inc)[0]
        before = of("step", killed, step=first["step"])
        made = of("saved", killed, step=saved)
        back = of("restored", inc)
        saves.append((k, saved, made[0]["digests"] if made else None))
        compared.update({
            f"kill{k}.restart_count": [inc, k],
            f"kill{k}.restored_step": [restore["step"], saved],
            f"kill{k}.restored_from_shm": [
                restore["sources"], {"shm": 1, "chain": 0, "replica": 0,
                                     "storage": 0}],
            f"kill{k}.first_resumed_step": [first["step"], saved + 1],
            f"kill{k}.loss_bits": [
                first["loss_hex"],
                before[0]["loss_hex"] if before else None],
            f"kill{k}.leaves_with_another_digest": [
                _differing(saves[-1][2],
                           back[0]["digests"] if back else None), 0],
        })

    # -- the window, in the last worker; then the end -----------------------
    wait_for(lambda e: e["event"] == "result", WAIT_WINDOW_S,
             "window result")
    with open(path("result.json")) as f:
        result = json.load(f)
    open(path("stop"), "w").close()
    try:
        exit_code = agent.wait(WAIT_AGENT_EXIT_S)
    except subprocess.TimeoutExpired:
        exit_code = None
    for line in tail.read():
        seen.append(line)
    agent_events = _agent_events(path("agent_events"))
    compared["agent_exit_code"] = [exit_code, 0]
    compared["agent_restarts"] = [
        sum(r.get("name") == "agent#restart" for r in agent_events), kills]
    # what the agent wrote at each kill, read back now that it has left
    t = time.monotonic()
    for k, saved, digests in saves:
        compared[f"kill{k}.persisted_leaves_with_another_digest"] = [
            _differing(digests, persisted_digests(persist_dir, saved)), 0]
    read_back_s = time.monotonic() - t
    rings = {}
    for name in sorted(glob.glob(path("resumed.*.json"))):
        with open(name) as f:
            resumed = json.load(f)
        rings[str(resumed["inc"])] = resumed["ring"]
    resume = {"kills": stamps,
              "worker": [e for e in seen if e["event"] != "note"],
              "agent_events": agent_events,
              "agent_log": agent_log(), "rings": rings}

    from benchmarks.harness import resume_path

    outside = [e for e in seen if e["event"] == "step"]  # of no window
    not_finite = [e["step"] for e in outside if not e["finite"]]
    ok = all(got == want for got, want in compared.values())
    note("resume", kills=kills, wall_s=walls, compared=compared, ok=ok,
         steps_outside_window=len(outside),
         non_finite_outside_window=not_finite, read_back_s=read_back_s,
         # every run says where its seconds went, traced or not
         waterfall=resume_path.waterfall(resume))
    result["correct"] = bool(result["correct"] and ok and not not_finite)
    result["attempted"] += len(outside) + kills
    result["failed"] += len(not_finite)
    result["compared"] = {**result.get("compared", {}), **compared}
    result["memory_peak_bytes"] = max(
        [result["memory_peak_bytes"]]
        + [e["peak_bytes"] for e in seen if "peak_bytes" in e])
    result["device"] = facts
    result["resume"] = resume
    return result


def persisted_digests(ckpt_dir, step):
    """The wrap-around sum of every leaf's bits, as ``_leaf_digests`` takes
    it on the device, over the frame the agent persisted for ``step``,
    read back through the program's own storage reader
    (``ckpt_saver.load_frames_for_step``: the manifest chain walked, every
    stripe's CRC checked against the stamp the worker's drain put on it)
    and summed here on the host. ``None`` (never equal to a limit) where
    no whole frame of that step comes back. Needs no JAX."""
    import numpy as np

    from dlrover_tpu.ckpt import ckpt_saver, shm_handler

    frames = ckpt_saver.load_frames_for_step(ckpt_dir, step)
    if len(frames) != 1:
        return None
    uint = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}
    digests = []
    for leaf in frames[0]["leaves"]:
        if leaf.get("kind") != "array":
            continue
        total = 0
        for shard in leaf["shards"]:
            if not shard["nbytes"]:
                continue
            width = shard["nbytes"] // math.prod(shard["lshape"])
            bits = np.frombuffer(
                shm_handler.frame_shard_bytes(frames[0], shard),
                dtype=uint[width])
            total += int(bits.sum(dtype=np.uint64))
        digests.append(total & 0xFFFFFFFF)
    return digests


def _differing(made, back):
    """How many leaves' digests differ between a save and its restore;
    ``None`` (never equal to the limit) where either was not taken."""
    if made is None or back is None or len(made) != len(back):
        return None
    return sum(a != b for a, b in zip(made, back))


def _agent_events(directory):
    records = []
    for name in sorted(glob.glob(os.path.join(directory, "events_*.jsonl"))):
        with open(name) as f:
            for line in f:
                with contextlib.suppress(json.JSONDecodeError):
                    records.append(json.loads(line))
    return records


# -- the worker script, started by the agent --------------------------------


def _leaf_digests(jax, jnp, train):
    """The wrap-around sum of every leaf's bits, on the device: one
    uint32 a leaf, a few ms for a state of gigabytes and no transfer of
    it. A flipped bit moves its leaf's sum by a power of two."""
    bits = train.bits_of(jax, jnp)

    def digests(tree):
        return jnp.stack([jnp.sum(bits(x).astype(jnp.uint32))
                          for x in jax.tree.leaves(tree)])

    return jax.jit(digests)


def _peak_bytes(j):
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in j.devices)


def _train_until_killed(j, rec, stop_file):
    """Steps, one in flight, every loss reported with the device's memory
    peak so far (a killed worker hands nothing back), until the process is
    killed. The stop file, or ten save cycles of steps, ends it: the
    benchmark has given the run up, or is gone."""
    in_flight = None
    for _ in range(10 * max(j.every, 1)):
        if os.path.exists(stop_file):
            break
        j.step += 1
        j.state, result = j.trainer.train_step(
            j.state, j.batch_for(j.step))
        previous, in_flight = in_flight, (j.step, result)
        if previous:
            loss = float(previous[1].loss)
            rec("step", step=previous[0], loss_hex=loss.hex(),
                finite=math.isfinite(loss), peak_bytes=_peak_bytes(j))
        if j.args.rehearsal:
            time.sleep(REHEARSAL_STEP_S)
    if in_flight:
        float(in_flight[1].loss)
    raise RuntimeError(f"worker {rec.inc} was to be killed and was not")


def _handed_back(j):
    """What the readers in the benchmark's process cannot reach: the
    tracer's ring, the registry and the device's memory."""
    from dlrover_tpu.observability import tracing
    from dlrover_tpu.observability.registry import get_registry

    tracer = tracing.get_tracer()
    ring = None
    if tracer.enabled and not tracer.dropped():
        ring = [sp.to_dict() for sp in tracer.finished_spans()]
    return {
        "ring": ring, "registry_text": get_registry().render(),
        "memory_peak_bytes": max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in j.devices)}


def worker_main(env_file) -> int:
    with open(env_file) as f:
        env = json.load(f)
    sys.path.insert(0, env["root"])
    from benchmarks import run as bench_run
    from benchmarks.jobs import train

    workdir = env["workdir"]
    path = lambda name: os.path.join(workdir, name)  # noqa: E731
    inc = int(os.getenv("DLROVER_TPU_RESTART_COUNT", "0"))
    rec = Recorder(path("worker.jsonl"), inc=inc)
    rec("start", t=T_WORKER, pid=os.getpid())
    env["args"] = types.SimpleNamespace(**env["args"])
    env["family"] = bench_run.load_family(env["fields"]["family"])
    if env["args"].rehearsal:
        env["fields"] = {**env["fields"], **env["family"].REHEARSAL_FIELDS}
    env["note"] = lambda kind, **fields: rec("note", kind=kind,
                                             fields=fields)
    last = inc >= env["traffic"]["kills"]  # this worker has the window

    from dlrover_tpu.ckpt.checkpointer import Checkpointer

    with contextlib.ExitStack() as cleanup:
        j = train.bootstrap(env)
        if j.worker.restart_count != inc:
            raise RuntimeError(
                f"the environment says restart {inc}, worker.init() "
                f"{j.worker.restart_count}")
        import jax
        import jax.numpy as jnp

        rec("backend", pid=os.getpid(), t_init=j.t_init_returned,
            platform=j.devices[0].platform,
            kind=j.devices[0].device_kind, found=len(jax.devices()))
        if j.devices[0].platform != "tpu" and not j.args.rehearsal:
            return 3
        digests = _leaf_digests(jax, jnp, train)
        train.build_model(j)
        if inc == 0:
            train.check_reference(j)
            rec("reference", ok=j.reference_ok, compared=j.compared)
        else:  # the first worker's, of the same weights and tokens
            j.reference_ok, j.compared = _first_workers_reference(
                path("worker.jsonl"))
        train.build_trainer(j)
        if inc > 0:
            ckpt = Checkpointer(env["ckpt_dir"])
            t = time.monotonic()
            j.state, restored = ckpt.load_checkpoint(j.state)
            jax.block_until_ready(j.state)
            rec("restore", t_begun=t, step=restored,
                sources=_restore_counts())
            j.step = max(restored, 0)
            # dispatched ahead of the step that donates the state away,
            # read once the step's loss is on the host
            t = time.monotonic()
            state_digests = digests(j.state)
            digest_dispatch_s = time.monotonic() - t
            j.step += 1
            j.state, first = j.trainer.train_step(
                j.state, j.batch_for(j.step))
            loss = float(first.loss)
            t = time.monotonic()
            rec("restored", step=restored,
                digest_dispatch_s=digest_dispatch_s,
                digests=[int(d) for d in state_digests])
            rec("step", step=j.step, loss_hex=loss.hex(),
                finite=math.isfinite(loss), peak_bytes=_peak_bytes(j))
            rec("first_step", t=t, step=j.step, loss_hex=loss.hex())
            with open(path(f"resumed.{inc}.json"), "w") as f:
                json.dump({"inc": inc, **_handed_back(j)}, f)
        else:
            ckpt = Checkpointer(env["ckpt_dir"])
        # as the flash-save traffic's set-up: steps until none compiles,
        # then a save that is waited for (the first worker's faults the
        # frame's pages in; a resumed one finds them)
        train.warm_up(j)
        rec("saved", step=j.step, digests=[int(d) for d in digests(j.state)])
        train.open_checkpointer(j, ckpt)
        rec("drained", step=j.step)
        if not last:
            _train_until_killed(j, rec, path("stop"))
        train.window(j, cleanup)
        train.restores(j)  # none: waits for the last save's drain
        result = {**train.result(j), **_handed_back(j),
                  "fields": env["fields"]}
        with open(path("result.json"), "w") as f:
            json.dump(result, f)
        rec("result")
        while not os.path.exists(path("stop")):
            time.sleep(0.01)
    rec("done")
    return 0


def _first_workers_reference(lines_file):
    """``(ok, compared)`` of the first worker's reference check."""
    with open(lines_file) as f:
        for line in f:
            event = json.loads(line)
            if event["event"] == "reference":
                return event["ok"], event["compared"]
    raise RuntimeError("the first worker wrote no reference check")


def _restore_counts():
    from dlrover_tpu.observability.registry import get_registry

    hist = get_registry().histogram(
        "dlrover_ckpt_restore_seconds", labelnames=("source",))
    return {s: hist.labels(source=s).count
            for s in ("shm", "chain", "replica", "storage")}


if __name__ == "__main__":
    sys.exit(worker_main(sys.argv[1]))
