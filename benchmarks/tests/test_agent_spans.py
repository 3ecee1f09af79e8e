"""The readers of the agent's own spans (``harness/agent_spans.py``): on a
hand-made ring, on bundles on disk, and in the kill-resume rehearsal.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_agent_spans.py -q

``tests/test_fault_arc_spans.py`` runs the hand-made cases in tier-1.
"""

import copy
import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import agent_spans  # noqa: E402
from benchmarks.tests.test_resume import (  # noqa: E402,F401 — the fixture
    KILLS,
    five_minutes,
    note,
    rehearse,
)

CELL = "mistral-7b.kill-resume"
METRICS = ["ckpt.persist_read_s", "ckpt.persist_verify_s",
           "ckpt.persist_write_s", "agent.rdzv_s", "agent.node_check_s"]

# -- a hand-made run of two kills ---------------------------------------------


def span(name, start, end, span_id, parent=None, trace="t1", **attrs):
    return {"name": name, "start_t": start, "end_t": end, "span_id": span_id,
            "parent_id": parent, "trace_id": trace, "source": "agent_0",
            "attrs": attrs}


def arc(at, trace, read, verify, write, rdzv):
    """One kill's arc, the death seen ``at`` s after the monotonic zero."""
    p = trace + "."
    persist_end = at + 0.2 + read + verify + write
    return [
        span("agent.worker_failure", at, persist_end + 1.0, p + "f",
             trace=trace, failures={"0": -9}, poll_gap_s=0.2),
        span("agent.failure_report", at + 0.01, at + 0.05, p + "r",
             p + "f", trace, verdict="restart_worker"),
        span("agent.restart_workers", at + 0.1, persist_end + 1.0, p + "w",
             p + "f", trace),
        span("agent.stop_workers", at + 0.1, at + 0.15, p + "s", p + "w",
             trace),
        span("ckpt.persist", at + 0.2, persist_end, p + "p", p + "w", trace,
             trigger="breakpoint", frames=1, bytes=4_802_600_968),
        span("ckpt.persist.read", at + 0.2, at + 0.2 + read, p + "pr",
             p + "p", trace, bytes=4_802_600_968),
        span("ckpt.persist.verify", at + 0.2 + read,
             at + 0.2 + read + verify, p + "pv", p + "p", trace, shards=9,
             bad=0),
        span("ckpt.persist.write", at + 0.2 + read + verify, persist_end,
             p + "pw", p + "p", trace, kind="base"),
        span("ckpt.commit", persist_end, persist_end + 0.01, p + "c",
             p + "p", trace),
        span("rdzv.client_round", persist_end + 0.02,
             persist_end + 0.02 + rdzv, p + "z", p + "w", trace),
        span("agent.spawn", persist_end + 0.05, persist_end + 0.06, p + "n",
             p + "w", trace, warm=[True], pids=[4242]),
    ]


HAND_MADE = {
    "kills": [{"t": 100.0, "wall_minus_monotonic": 1.7e9},
              {"t": 300.0, "wall_minus_monotonic": 1.7e9}],
    "worker": [{"event": "first_step", "inc": 1, "t": 153.0},
               {"event": "first_step", "inc": 2, "t": 338.0}],
    "rings": {"1": [
        span("ckpt.restore", 140.0, 145.0, "t1.x", "t1.n", trace="t1"),
        span("ckpt.restore.shm", 140.5, 144.9, "t1.xs", "t1.x", trace="t1"),
        span("train.step", 150.0, 150.2, "s1", trace="other")]},
    "agent_ring": {"manifest": {"reason": "agent_exit", "spans_dropped": 0},
                   "spans": [
        span("agent.node_check", 10.0, 22.5, "nc", trace="t0", ok=True),
        # a save-event persist in the window is not the breakpoint's
        span("ckpt.persist", 99.0, 99.5, "e", trace="t9", trigger="periodic"),
        span("ckpt.persist.read", 99.0, 99.1, "er", "e", trace="t9"),
    ] + arc(104.0, "t1", read=5.0, verify=3.0, write=6.0, rdzv=0.02)
      + arc(302.0, "t2", read=4.0, verify=2.0, write=5.0, rdzv=0.04)},
}
# the mean over the two kills; the node check is set-up's, once
EXPECTED = {"ckpt.persist_read_s": 4.5, "ckpt.persist_verify_s": 2.5,
            "ckpt.persist_write_s": 5.5, "agent.rdzv_s": 0.03,
            "agent.node_check_s": 12.5}


def read(metric, resume):
    return bench_run.load_reader(metric).read({"resume": resume})


def test_every_new_metric_has_its_entry():
    entries = {m["name"]: m for m in bench_run.load_benchmark()["per_layer"]}
    assert sorted(EXPECTED) == sorted(METRICS)
    for name in METRICS:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "setup_s"
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["unit"] == "s"
    assert {entries[n]["layer"] for n in METRICS[:3]} == {"agent persist"}
    assert {entries[n]["layer"] for n in METRICS[3:]} == {"launcher / agent"}


@pytest.mark.parametrize("metric", METRICS)
def test_a_part_present_is_the_mean_over_the_kills(metric, capsys):
    got = read(metric, copy.deepcopy(HAND_MADE))
    assert got == pytest.approx(EXPECTED[metric], abs=1e-9)
    if metric == "ckpt.persist_read_s":
        notes = [json.loads(line) for line in
                 capsys.readouterr().out.splitlines()]
        assert [n["note"] for n in notes] == ["fault_arc"]
        kills = notes[0]["kills"]
        assert [k["trace_id"] for k in kills] == ["t1", "t2"]
        first = kills[0]["spans"]
        assert first[0]["name"] == "agent.worker_failure"
        assert first[0]["start_s"] == pytest.approx(4.0)
        # the resumed worker's restore and its rung, nothing else of it
        restore = [s for s in first if s["name"].startswith("ckpt.restore")]
        assert [s["name"] for s in restore] == ["ckpt.restore",
                                                "ckpt.restore.shm"]
        assert restore[0]["parent_id"] == "t1.n"
        assert kills[1]["spans"][-1]["name"] == "agent.spawn"


# the span taken away from the second kill, and the metrics it silences
MISSING = [("ckpt.persist.read", {"ckpt.persist_read_s"}),
           ("ckpt.persist.write", {"ckpt.persist_write_s"}),
           ("ckpt.persist", {"ckpt.persist_read_s", "ckpt.persist_verify_s",
                             "ckpt.persist_write_s"}),
           ("rdzv.client_round", {"agent.rdzv_s"}),
           ("agent.node_check", {"agent.node_check_s"})]


@pytest.mark.parametrize("name,silent", MISSING,
                         ids=[name for name, _ in MISSING])
def test_a_kill_without_the_span_reads_none(name, silent):
    cut = copy.deepcopy(HAND_MADE)
    cut["agent_ring"]["spans"] = [
        sp for sp in cut["agent_ring"]["spans"]
        if not (sp["name"] == name and sp["trace_id"] in ("t2", "t0"))]
    for metric in METRICS:
        got = read(metric, cut)
        if metric in silent:
            assert got is None, metric
        else:
            assert got == pytest.approx(EXPECTED[metric], abs=1e-9), metric


def test_a_second_kill_is_read_from_its_own_window():
    one = copy.deepcopy(HAND_MADE)
    one["kills"] = one["kills"][1:]
    one["worker"] = [{"event": "first_step", "inc": 1, "t": 338.0}]
    # kill 1's window now runs from 300 s: only the second arc lies in it
    assert read("ckpt.persist_read_s", one) == pytest.approx(4.0)
    assert read("agent.rdzv_s", one) == pytest.approx(0.04)
    # and a window that closes early leaves the second arc out
    early = copy.deepcopy(HAND_MADE)
    early["worker"][1]["t"] = 301.0
    assert read("ckpt.persist_write_s", early) is None


def test_no_bundle_and_no_kill_read_nothing(tmp_path, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_TRACE_DIR", str(tmp_path))
    for metric in METRICS:
        assert bench_run.load_reader(metric).read({}) is None
        assert read(metric, {**HAND_MADE, "agent_ring": None}) is None
        without = {k: v for k, v in HAND_MADE.items() if k != "agent_ring"}
        assert read(metric, without) is None


def write_bundle(directory, name, monotonic_t, spans):
    path = os.path.join(directory, name)
    os.makedirs(path)
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump({"reason": "agent_exit", "monotonic_t": monotonic_t}, f)
    with open(os.path.join(path, "spans.json"), "w") as f:
        json.dump(spans, f)


def test_the_run_s_bundle_is_found_among_others(tmp_path):
    """An earlier run's bundle (its death before this run's kill), a bundle
    of another reason, and this run's: the last is read."""
    ring = HAND_MADE["agent_ring"]["spans"]
    earlier = [dict(sp, start_t=sp["start_t"] - 90.0,
                    end_t=sp["end_t"] - 90.0) for sp in ring]
    write_bundle(tmp_path, "bundle_agent_0_agent_exit_1_11", 60.0, earlier)
    write_bundle(tmp_path, "bundle_agent_0_unhandled_exception_1_12", 400.0,
                 ring)
    write_bundle(tmp_path, "bundle_agent_0_agent_exit_1_13", 400.0, ring)
    resume = {k: v for k, v in HAND_MADE.items() if k != "agent_ring"}
    found = agent_spans.find_bundle(resume, str(tmp_path))
    assert found["spans"] == ring
    assert found["manifest"]["monotonic_t"] == 400.0
    resume = copy.deepcopy(resume)
    for kill in resume["kills"]:
        kill["t"] += 1000.0  # a run whose kills come after every bundle
    assert agent_spans.find_bundle(resume, str(tmp_path)) is None


# -- the rehearsal ------------------------------------------------------------


def test_rehearsal_reads_the_fault_arc_from_inside(
        five_minutes, monkeypatch, capsys, tmp_path):
    code, lines, err = rehearse(monkeypatch, capsys, tmp_path, trace=1)
    assert code == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True
    assert all(out["metrics"][name]["value"] > 0 for name in METRICS)
    # the persist from inside is the persist the agent's log stamps
    rows = note(lines, "resume_waterfall")["kills"]
    total = sum(row["persist_total_s"] for row in rows) / KILLS
    parts = sum(out["metrics"][name]["value"] for name in METRICS[:3])
    assert parts == pytest.approx(total, abs=0.1)
    assert out["metrics"]["agent.rdzv_s"]["value"] <= \
        out["metrics"]["resume.relaunch_s"]["value"]
    # one trace a kill, from the death the poll saw to the resumed
    # worker's first restore, the restore a child of the spawn
    kills = note(lines, "fault_arc")["kills"]
    assert len(kills) == KILLS
    for kill in kills:
        spans = kill["spans"]
        assert spans[0]["name"] == "agent.worker_failure"
        assert {s["name"] for s in spans} >= {
            "agent.failure_report", "agent.restart_workers",
            "agent.stop_workers", "ckpt.persist", "ckpt.persist.read",
            "ckpt.persist.verify", "ckpt.persist.write", "ckpt.commit",
            "rdzv.client_round", "agent.spawn", "ckpt.restore"}
        by_id = {s["span_id"]: s for s in spans}
        restore = next(s for s in spans if s["name"] == "ckpt.restore")
        assert by_id[restore["parent_id"]]["name"] == "agent.spawn"
    # the death the agent's poll saw, and the agent's event stamp of it
    assert kills[0]["spans"][0]["start_s"] == pytest.approx(
        out["metrics"]["resume.detect_s"]["value"], abs=0.01)
    # the exit bundle kept the whole ring
    manifests = glob.glob(str(tmp_path / "dlrover_tpu_bundles"
                              / "bundle_agent_0_agent_exit_*"
                              / "manifest.json"))
    assert len(manifests) == 1
    with open(manifests[0]) as f:
        assert json.load(f)["spans_dropped"] == 0
