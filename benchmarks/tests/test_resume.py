"""The kill-resume cell on the CPU: its rehearsal end to end, its readers on
hand-made events, and the faults that must turn ``correct`` false.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_resume.py -q
"""

import copy
import glob
import json
import os
import signal
import sys
import tempfile
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import resume_path  # noqa: E402
from benchmarks.jobs import resume  # noqa: E402

CELL = "mistral-7b.kill-resume"
KILLS = bench_run.load_json("traffic", "kill-resume.json")["kills"]
PARTS = ["resume." + part for part in resume_path.PARTS]
PROGRAM = ["resume." + part for part in resume_path.PROGRAM]

# -- the readers on a hand-made run -------------------------------------------

WALL = 1_700_000_000.0   # time.time() as the signal is sent
KILL = 100.0             # time.monotonic() then


def log_line(wall, message):
    stamp = time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(wall))
    ms = int(round((wall - int(wall)) * 1e3))
    return (f"[{stamp},{ms:03d}] [INFO] [ckpt_saver.py:573:"
            f"save_shm_to_storage] {message}\n")


HAND_MADE = {
    "kills": [{"t": KILL, "wall_minus_monotonic": WALL - KILL}],
    "worker": [
        {"event": "step", "inc": 0, "t": 99.9, "step": 390},
        {"event": "start", "inc": 1, "t": 130.0},
        {"event": "backend", "inc": 1, "t_init": 131.0, "t": 142.0},
        {"event": "restore", "inc": 1, "t_begun": 145.0, "t": 150.0},
        {"event": "first_step", "inc": 1, "t": 153.0},
    ],
    "agent_events": [
        {"name": "agent#rendezvous", "phase": "BEGIN", "ts": WALL - 90},
        {"name": "agent#worker_fail", "phase": "INSTANT", "ts": WALL + 4.2},
        {"name": "agent#restart", "phase": "INSTANT", "ts": WALL + 4.25},
    ],
    "agent_log": (
        log_line(WALL - 80, "node 0 spawned 1 worker(s)")
        + log_line(WALL + 24.25, "breakpoint save (worker failure "
                   "{0: -9}): persisted 1 frame(s) to /tmp/x/ckpt")),
    "rings": {"1": [
        {"name": "ckpt.restore", "start_t": 145.1, "end_t": 149.9},
        {"name": "train.step", "start_t": 150.1, "end_t": 150.2}]},
}
# the eight parts, cut end to end; then the whole and the program's share
EXPECTED = {"resume.detect_s": 4.2, "resume.persist_s": 20.0,
            "resume.relaunch_s": 5.8, "resume.bootstrap_s": 1.0,
            "resume.backend_s": 11.0, "resume.state_s": 3.0,
            "resume.restore_s": 4.8, "resume.first_step_s": 3.0}
TOTALS = {"resume.wall_s": 53.0, "resume.program_s": 37.6}
EVERY = sorted(EXPECTED) + sorted(TOTALS)


def test_every_part_has_its_entry_and_the_expected_list_is_whole():
    bench = bench_run.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert sorted(EXPECTED) == sorted(PARTS)
    assert set(PROGRAM) == set(PARTS) - {"resume.detect_s",
                                         "resume.backend_s"}
    for name in EVERY:
        # the fault lies inside the cell's set-up: that is what they move
        assert entries[name]["moves"] == "setup_s"
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["unit"] == "s"
    # the wall time of a resume is no end-to-end metric (PERF.md section 2)
    assert "resume_s" not in {m["name"] for m in bench["end_to_end"]}
    assert [m["name"] for m in bench_run.metrics_of(
        CELL, bench["end_to_end"])] == ["tokens_per_s", "setup_s"]


@pytest.mark.parametrize("metric", EVERY)
def test_a_part_on_a_hand_made_run(metric, capsys):
    reader = bench_run.load_reader(metric)
    got = reader.read({"resume": HAND_MADE})
    assert got == pytest.approx({**EXPECTED, **TOTALS}[metric], abs=2e-3)
    row = resume_path.waterfall(HAND_MADE)[0]
    # the parts are cut end to end: with the remainder they are the wall
    assert row["remainder_s"] == pytest.approx(0.2, abs=2e-3)
    assert sum(EXPECTED.values()) + row["remainder_s"] == pytest.approx(
        row["wall_s"], abs=2e-3)
    assert row["program_s"] == pytest.approx(
        sum(EXPECTED[name] for name in PROGRAM), abs=2e-3)
    if metric == "resume.detect_s":
        notes = [json.loads(line) for line in capsys.readouterr().out
                 .splitlines()]
        assert [n["note"] for n in notes] == ["resume_waterfall"]
        assert notes[0]["kills"][0]["persist_s"] == pytest.approx(
            20.0, abs=2e-3)


def test_a_persist_that_runs_on_beside_the_new_worker_keeps_the_sum():
    """What PERF.md section 7 queues: the relaunch no longer waits for the
    persist. ``persist_s`` is then the part before the new worker exists,
    no part goes negative, and the parts still make up the wall time."""
    beside = copy.deepcopy(HAND_MADE)
    for event in beside["worker"]:
        if event["inc"] == 1:  # the new worker starts 20 s earlier
            for key in ("t", "t_init", "t_begun"):
                if key in event:
                    event[key] -= 20.0
    for span in beside["rings"]["1"]:
        span["start_t"] -= 20.0
        span["end_t"] -= 20.0
    row = resume_path.waterfall(beside)[0]
    assert row["wall_s"] == pytest.approx(33.0)
    assert row["persist_total_s"] == pytest.approx(20.0, abs=2e-3)
    assert row["persist_s"] == pytest.approx(5.75, abs=2e-3)
    assert row["relaunch_s"] == pytest.approx(0.05, abs=2e-3)
    assert all(row[part] >= 0 for part in resume_path.PARTS)
    assert sum(row[part] for part in resume_path.PARTS) + row[
        "remainder_s"] == pytest.approx(row["wall_s"], abs=2e-3)
    # and one that only begins as the new worker starts holds nothing up
    late = copy.deepcopy(beside)
    late["agent_log"] = log_line(WALL + 31.0, "breakpoint save (x): "
                                 "persisted 1 frame(s) to /y")
    for record in late["agent_events"]:
        if record["name"] == "agent#restart":
            record["ts"] = WALL + 10.0  # the worker's first line: 110.0
    row = resume_path.waterfall(late)[0]
    assert row["persist_s"] == 0.0 and row["persist_total_s"] > 20
    assert row["relaunch_s"] == pytest.approx(5.8, abs=2e-3)


def without(resume_dict, what):
    cut = copy.deepcopy(resume_dict)
    kind, name = what
    if kind == "worker":
        cut["worker"] = [e for e in cut["worker"] if e["event"] != name]
    elif kind == "agent":
        cut["agent_events"] = [r for r in cut["agent_events"]
                               if r["name"] != name]
    elif kind == "log":
        cut["agent_log"] = cut["agent_log"].replace("persisted", name)
    elif kind == "ring":
        cut["rings"] = {}
    elif kind == "key":  # a key of the worker's ``backend`` line
        for event in cut["worker"]:
            event.pop(name, None)
    return cut


# the boundary taken away, and the parts that are cut at it
MISSING = [
    (("key", "t_init"), {"resume.bootstrap_s", "resume.backend_s"}),
    (("agent", "agent#worker_fail"),
     {"resume.detect_s", "resume.relaunch_s"}),
    (("agent", "agent#restart"),
     {"resume.persist_s", "resume.relaunch_s"}),
    (("log", "refused"), {"resume.persist_s", "resume.relaunch_s"}),
    # the persist is counted as far as it lies before the worker's start
    (("worker", "start"),
     {"resume.persist_s", "resume.relaunch_s", "resume.bootstrap_s"}),
    (("worker", "backend"),
     {"resume.bootstrap_s", "resume.backend_s", "resume.state_s"}),
    (("worker", "restore"), {"resume.state_s", "resume.first_step_s"}),
    (("ring", None), {"resume.restore_s"}),
]


@pytest.mark.parametrize("what,silent", MISSING,
                         ids=[str(what[1]) for what, _ in MISSING])
def test_a_missing_boundary_silences_the_parts_cut_at_it(what, silent):
    cut = without(HAND_MADE, what)
    if silent & set(PROGRAM):  # a sum of what is left would be another sum
        silent = silent | {"resume.program_s"}
    for metric in EVERY:
        got = bench_run.load_reader(metric).read({"resume": cut})
        if metric in silent:
            assert got is None, metric
        else:
            assert got == pytest.approx(
                {**EXPECTED, **TOTALS}[metric], abs=2e-3)
    assert resume_path.waterfall(cut)[0]["remainder_s"] is None


def test_no_first_step_and_no_kill_read_nothing():
    cut = without(HAND_MADE, ("worker", "first_step"))
    row = resume_path.waterfall(cut)[0]
    assert row["wall_s"] is None and row["program_s"] is None
    assert row["first_step_s"] is None
    for metric in EVERY:
        assert bench_run.load_reader(metric).read({}) is None
        assert bench_run.load_reader(metric).read(
            {"resume": {**HAND_MADE, "kills": []}}) is None


def test_a_second_kill_is_read_from_its_own_worker_and_the_mean_taken():
    two = copy.deepcopy(HAND_MADE)
    two["kills"].append({"t": 300.0, "wall_minus_monotonic": WALL - KILL})
    two["worker"] += [
        {"event": "start", "inc": 2, "t": 320.0},
        {"event": "backend", "inc": 2, "t_init": 323.0, "t": 330.0},
        {"event": "restore", "inc": 2, "t_begun": 332.0, "t": 336.0},
        {"event": "first_step", "inc": 2, "t": 338.0}]
    two["agent_events"] += [
        {"name": "agent#worker_fail", "ts": WALL + 200.1},
        {"name": "agent#restart", "ts": WALL + 200.2}]
    two["agent_log"] += log_line(
        WALL + 210.2, "breakpoint save (x): persisted 1 frame(s) to /y")
    two["rings"]["2"] = [
        {"name": "ckpt.restore", "start_t": 332.0, "end_t": 335.0}]
    rows = resume_path.waterfall(two)
    assert rows[0]["persist_s"] == pytest.approx(20.0, abs=2e-3)
    assert rows[1]["persist_s"] == pytest.approx(10.0, abs=2e-3)
    assert rows[1]["wall_s"] == pytest.approx(38.0)
    assert rows[1]["program_s"] == pytest.approx(29.9, abs=2e-3)
    assert rows[1]["remainder_s"] == pytest.approx(1.0, abs=2e-3)
    assert bench_run.load_reader("resume.bootstrap_s").read(
        {"resume": two}) == pytest.approx((1.0 + 3.0) / 2)
    assert bench_run.load_reader("resume.backend_s").read(
        {"resume": two}) == pytest.approx((11.0 + 7.0) / 2)


# -- the whole run, and the faults that must fail it --------------------------


@pytest.fixture
def five_minutes():
    """The test's own time limit (the job's waits have theirs)."""
    def late(signum, frame):
        raise TimeoutError("the rehearsal took over 300 s")

    before = signal.signal(signal.SIGALRM, late)
    signal.alarm(300)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


def rehearse(monkeypatch, capsys, tmp_path, trace):
    """``run.py`` in this process, at the rehearsal's sizes. The process
    never touches JAX: the agent's children do."""
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    for name in ("JAX_PLATFORMS", "XLA_FLAGS"):
        monkeypatch.setenv(name, os.environ.get(name, ""))
    monkeypatch.setattr(sys, "path", list(sys.path))
    code = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 35),
                           "--seconds", "1", "--trace", str(trace),
                           "--rehearsal"])
    captured = capsys.readouterr()
    lines = [json.loads(line) for line in captured.out.splitlines()]
    return code, lines, captured.err


def note(lines, kind):
    return next(n for n in lines if n.get("note") == kind)


def test_rehearsal_kills_from_outside_and_the_parts_sum_to_the_wall_time(
        five_minutes, monkeypatch, capsys, tmp_path):
    code, lines, err = rehearse(monkeypatch, capsys, tmp_path, trace=1)
    assert code == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True and out["failed"] == 0
    assert set(EVERY) <= set(out["metrics"])
    assert all(out["metrics"][name]["value"] > 0 for name in EVERY)
    # the kill came from this process, not from the worker or the agent
    kill = note(lines, "kill")
    assert kill["pid"] not in (os.getpid(), 0)
    assert kill["after_step"] >= kill["newest_drained_save"] + 8
    # and before the window: the resumed worker has it, set-up holds the
    # whole resume
    kinds = [n.get("note") for n in lines]
    assert kinds.index("kill") < kinds.index("window")
    assert note(lines, "window")["inc"] == KILLS
    resumed = note(lines, "resume")
    assert resumed["ok"] is True and resumed["kills"] == KILLS
    rows = note(lines, "resume_waterfall")["kills"]
    assert [row["wall_s"] for row in rows] == pytest.approx(
        resumed["wall_s"])
    # the parts are means over the kills, and so is the wall time: cut
    # end to end, they make up what a user waits
    wall_s = sum(resumed["wall_s"]) / KILLS
    assert out["metrics"]["resume.wall_s"]["value"] == pytest.approx(wall_s)
    parts = sum(out["metrics"][name]["value"] for name in PARTS)
    assert parts == pytest.approx(wall_s, rel=0.02)
    assert out["metrics"]["resume.program_s"]["value"] == pytest.approx(
        sum(out["metrics"][name]["value"] for name in PROGRAM))
    # each number compared stands beside its limit: in the line's last
    # key and in the last lines of standard error
    assert list(out)[-1] == "compared"
    per_kill = ("restart_count", "restored_step", "restored_from_shm",
                "first_resumed_step", "loss_bits",
                "leaves_with_another_digest",
                "persisted_leaves_with_another_digest")
    for name in [f"kill{k}.{what}" for k in range(1, KILLS + 1)
                 for what in per_kill] + [
            "agent_exit_code", "agent_restarts", "loss_rel",
            "grad_norm_rel"]:
        value, limit = out["compared"][name]
        assert f"compared {name}: " in err
        if "_rel" not in name:
            assert value == limit, name
    assert err.rstrip().splitlines()[-1].startswith("compared ")
    # nothing is left behind: work directory, frames, the checkout's lock
    assert not glob.glob(str(tmp_path / "dlrover_bench_resume_*"))
    assert not glob.glob("/dev/shm/dlrtpu_bench*r.lock")


def test_set_up_holds_the_resume(five_minutes, monkeypatch, capsys,
                                 tmp_path):
    """An untraced run: ``setup_s`` runs to the window's start in the
    resumed worker, so it is longer than kill -> first resumed step."""
    code, lines, err = rehearse(monkeypatch, capsys, tmp_path, trace=0)
    assert code == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True
    assert sorted(out["metrics"]) == ["setup_s", "tokens_per_s"]
    wall_s = note(lines, "resume")["wall_s"]
    assert out["metrics"]["setup_s"]["value"] > sum(wall_s)
    # the first worker's steps after its save, the resumed one's first
    # step and the kill are counted beside the window's own
    window = note(lines, "window")
    assert out["attempted"] > window["steps"] + window["saves"] + KILLS


def frames():
    return glob.glob("/dev/shm/dlrtpu_bench*r_*")


def flip_bits_in_the_frame():
    """One bit in each of 256 places of the frame's tensor bytes (a page
    apart at the least)."""
    for name in frames():
        with open(name, "r+b") as f:
            meta = int.from_bytes(f.read(8), "little")
            size = os.path.getsize(name)
            for at in range(8 + meta + 4096, size, max(4096, size // 256)):
                f.seek(at)
                byte = f.read(1)
                f.seek(at)
                f.write(bytes([byte[0] ^ 0x10]))


def kill_the_next_worker_too(tmp_path):
    """Wait for the worker the agent starts next and kill it as well."""
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        for name in glob.glob(str(
                tmp_path / "dlrover_bench_resume_*" / "worker.jsonl")):
            with open(name) as f:
                lines = [json.loads(ln) for ln in f if ln.endswith("\n")]
            started = [e for e in lines
                       if e["event"] == "start" and e["inc"] == 1]
            if started:
                os.kill(started[0]["pid"], signal.SIGKILL)
                return
        time.sleep(0.01)
    raise AssertionError("no second worker to kill")


def persisted_payloads(tmp_path):
    return [name for name in glob.glob(
        str(tmp_path / "dlrover_bench_resume_*" / "persist" / "**"),
        recursive=True) if os.path.isfile(name)]


def garble_what_the_agent_persisted(tmp_path):
    """A byte in each of 64 places of the largest file the agent wrote."""
    name = max(persisted_payloads(tmp_path), key=os.path.getsize)
    size = os.path.getsize(name)
    with open(name, "r+b") as f:
        for at in range(size // 2, size, max(1, size // 128)):
            f.seek(at)
            byte = f.read(1)
            f.seek(at)
            f.write(bytes([byte[0] ^ 0x01]))


def empty_what_the_agent_persisted(tmp_path):
    for name in persisted_payloads(tmp_path):
        os.unlink(name)


# the fault, when it is planted (as the kill is sent, or before the
# persisted frame is read back), and the comparisons that must catch it
FAULTS = {
    "a-flipped-bit-in-the-shm-frame": (
        lambda tmp_path: flip_bits_in_the_frame(), "kill",
        ("kill1.restored_step", "kill1.loss_bits",
         "kill1.persisted_leaves_with_another_digest")),
    "a-second-restart": (
        kill_the_next_worker_too, "kill",
        ("kill1.restart_count", "agent_restarts")),
    "a-persist-that-wrote-garbage": (
        garble_what_the_agent_persisted, "read-back",
        ("kill1.persisted_leaves_with_another_digest",)),
    "a-persist-that-wrote-nothing": (
        empty_what_the_agent_persisted, "read-back",
        ("kill1.persisted_leaves_with_another_digest",)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_turns_correct_false(
        fault, five_minutes, monkeypatch, capsys, tmp_path):
    plant, when, caught_by = FAULTS[fault]
    sent, read_back = resume.send_kill, resume.persisted_digests
    planted = []

    def kill_and_plant(pid):
        sent(pid)
        if when == "kill" and not planted:  # the run's first kill alone
            planted.append(pid)
            plant(tmp_path)

    def plant_and_read_back(ckpt_dir, step):
        if when == "read-back" and not planted:
            planted.append(step)
            plant(tmp_path)
        return read_back(ckpt_dir, step)

    monkeypatch.setattr(resume, "send_kill", kill_and_plant)
    monkeypatch.setattr(resume, "persisted_digests", plant_and_read_back)
    code, lines, err = rehearse(monkeypatch, capsys, tmp_path, trace=0)
    assert code == 0, err[-3000:]
    assert planted
    out = lines[-1]
    assert out["correct"] is False
    for name in caught_by:
        value, limit = out["compared"][name]
        assert value != limit, (name, value)
    caught = {name for name, (value, limit) in out["compared"].items()
              if value != limit and "_rel" not in name}
    if when == "read-back":  # the resume itself was sound
        assert caught == set(caught_by)
    # the window itself was sound: only the resume's comparisons failed
    for name in ("compiled_in_window", "non_finite_losses"):
        assert out["compared"][name] == [0, 0]
    assert out["metrics"]["tokens_per_s"]["value"] > 0
