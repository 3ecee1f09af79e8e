"""The fault arc as one trace, from inside the agent.

    JAX_PLATFORMS=cpu python -m pytest tests/test_fault_arc_spans.py -q

A real master, agent and worker: the worker dies, the agent restarts it
(``test_e2e_agent.py::test_crash_restart_resumes_from_checkpoint`` with
memory saves only, so that the agent's breakpoint persist brings the frame
to the disk), and the spans of that arc, in the agent's ring, in the
resumed worker's and in the agent's exit bundle, are one trace. Then the
benchmark's readers of the arc on a hand-made ring
(``benchmarks/tests/test_agent_spans.py``, run here as well).
"""

import glob
import json
import os
import signal
import sys
import time

import pytest

from dlrover_tpu.agent.config import ElasticLaunchConfig
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training import ElasticTrainingAgent
from dlrover_tpu.ckpt.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.common.constants import (
    ConfigKey,
    DiagnosisActionType,
    SpanName,
)
from dlrover_tpu.master.master import LocalJobMaster
from dlrover_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.tests.test_agent_spans import (  # noqa: E402,F401 — run here
    test_a_kill_without_the_span_reads_none,
    test_a_part_present_is_the_mean_over_the_kills,
    test_a_second_kill_is_read_from_its_own_window,
    test_every_new_metric_has_its_entry,
    test_no_bundle_and_no_kill_read_nothing,
    test_the_run_s_bundle_is_found_among_others,
)

SCRIPT = os.path.join(os.path.dirname(__file__), "data",
                      "fault_arc_train.py")
CRASH_AT_STEP = 3
# a 64 MB frame: its read, CRC pass and write outweigh the lock and
# handler round trips before them (1-4 ms)
SIDE = 4096


@pytest.fixture(autouse=True)
def time_limit():
    """Each test's own limit, well inside the suite's."""
    def late(signum, frame):
        raise TimeoutError("the test took over 240 s")

    before = signal.signal(signal.SIGALRM, late)
    signal.alarm(240)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


def run_agent(tmp_path, monkeypatch, crash_step, side):
    """(exit code, the spans of this process's ring, the trace
    directory)."""
    job = f"arc{os.getpid()}"
    trace_dir = tmp_path / "bundles"
    monkeypatch.setenv(ConfigKey.TRACE_DIR, str(trace_dir))
    tracing.reset_tracer()
    master = LocalJobMaster(job_name=job, node_num=1)
    master.prepare()
    ckpt_dir = str(tmp_path / "ckpt")
    config = ElasticLaunchConfig(
        min_nodes=1, max_nodes=1, nproc_per_node=1, job_name=job,
        master_addr=master.addr, max_restarts=3, monitor_interval_s=0.1,
        entrypoint=SCRIPT,
        args=[ckpt_dir, str(tmp_path / "out.txt"), str(tmp_path / "ring")],
        ckpt_dir=ckpt_dir,
        worker_env={"JAX_PLATFORMS": "cpu",
                    "CRASH_AT_STEP": str(crash_step), "W_SIDE": str(side)},
    )
    saver = AsyncCheckpointSaver(
        ckpt_dir=ckpt_dir, node_rank=0, local_world_size=1,
        expected_frames=1)
    agent = ElasticTrainingAgent(
        config, MasterClient(master.addr, 0, 0), ckpt_saver=saver)
    try:
        code = agent.run()
    finally:
        master.stop()
    try:
        tracer = tracing.get_tracer()
        return code, tracer.finished_spans() + tracer.live_spans(), trace_dir
    finally:
        tracing.reset_tracer()


def exit_bundles(trace_dir):
    return glob.glob(str(trace_dir / "bundle_agent_0_agent_exit_*"))


def test_the_fault_arc_is_one_trace_from_the_death_to_the_restore(
        tmp_path, monkeypatch):
    code, spans, trace_dir = run_agent(
        tmp_path, monkeypatch, CRASH_AT_STEP, SIDE)
    assert code == 0
    content = (tmp_path / "out.txt").read_text()
    assert "done w=6.0" in content and "restarts=1" in content
    assert f"start={CRASH_AT_STEP + 1}" in content  # from the shm frame

    failure, = [sp for sp in spans
                if sp.name == SpanName.AGENT_WORKER_FAILURE]
    assert failure.parent_id is None
    assert failure.attrs["failures"] == {0: 7}
    assert failure.attrs["restart_count"] == 0
    # the previous poll that saw the worker alive: one interval before
    assert 0.0 < failure.attrs["poll_gap_s"] < 1.0
    arc = [sp for sp in spans if sp.trace_id == failure.trace_id]

    def child(name, parent):
        found = [sp for sp in arc
                 if sp.name == name and sp.parent_id == parent.span_id]
        assert len(found) == 1, (name, [sp.name for sp in arc])
        sp = found[0]
        assert sp.end_t is not None and sp.start_t >= parent.start_t
        return sp

    report = child(SpanName.AGENT_FAILURE_REPORT, failure)
    assert report.attrs["verdict"] == DiagnosisActionType.RESTART_WORKER
    restart = child(SpanName.AGENT_RESTART_WORKERS, failure)
    stop = child(SpanName.AGENT_STOP_WORKERS, restart)
    persist = child(SpanName.CKPT_PERSIST, restart)
    assert persist.attrs["trigger"] == "breakpoint"
    assert persist.attrs["step"] == CRASH_AT_STEP
    assert persist.attrs["frames"] == 1
    assert persist.attrs["bytes"] > SIDE * SIDE * 4
    read, verify, write = (child(name, persist) for name in (
        SpanName.CKPT_PERSIST_READ, SpanName.CKPT_PERSIST_VERIFY,
        SpanName.CKPT_PERSIST_WRITE))
    assert read.attrs["bytes"] == persist.attrs["bytes"]
    assert verify.attrs["bad"] == 0 and verify.attrs["shards"] >= 1
    assert write.attrs["bytes_written"] >= SIDE * SIDE * 4
    assert write.attrs["kind"] == "base"
    commit = child(SpanName.CKPT_COMMIT, persist)
    rdzv = child(SpanName.RDZV_CLIENT_ROUND, restart)
    spawn = child(SpanName.AGENT_SPAWN, restart)
    assert len(spawn.attrs["pids"]) == len(spawn.attrs["warm"]) == 1
    # in the order they run, each inside the span above it
    assert failure.start_t <= report.start_t <= restart.start_t
    assert stop.end_t <= persist.start_t <= read.start_t
    assert read.end_t <= verify.start_t and verify.end_t <= write.start_t
    assert write.end_t <= persist.end_t <= rdzv.start_t <= spawn.start_t
    assert commit.start_t >= write.end_t
    assert spawn.end_t <= restart.end_t <= failure.end_t
    # the persist's frame work is its three parts; what follows the write
    # (the frame copy freed, the done marker, the commit thread's start,
    # the log line: 15-60 ms here under a loaded test run, whatever the
    # frame's size) stays inside the 0.1 s the chip's readings are held to
    parts = sum(sp.end_t - sp.start_t for sp in (read, verify, write))
    assert parts >= 0.95 * (write.end_t - persist.start_t), parts
    assert persist.end_t - write.end_t < 0.1
    # across the process boundary: the resumed worker's first restore
    # joins the arc under the spawn that started it
    with open(tmp_path / "ring.1") as f:
        ring = json.load(f)
    restores = sorted((sp for sp in ring
                       if sp["name"] == SpanName.CKPT_RESTORE),
                      key=lambda sp: sp["start_t"])
    assert restores[0]["trace_id"] == failure.trace_id
    assert restores[0]["parent_id"] == spawn.span_id
    # and nothing else of the worker does
    assert [sp["name"] for sp in ring
            if sp["trace_id"] == failure.trace_id
            and sp["parent_id"] == spawn.span_id] == [SpanName.CKPT_RESTORE]


def test_the_exit_bundle_holds_the_ring_on_the_host_clock(
        tmp_path, monkeypatch):
    code, spans, trace_dir = run_agent(
        tmp_path, monkeypatch, CRASH_AT_STEP, side=64)
    assert code == 0
    bundle, = exit_bundles(trace_dir)
    with open(os.path.join(bundle, "manifest.json")) as f:
        manifest = json.load(f)
    offset = time.time() - time.monotonic()
    with open(os.path.join(bundle, "spans.json")) as f:
        saved = json.load(f)
    assert manifest["reason"] == "agent_exit"
    assert manifest["spans_dropped"] == 0
    assert "spans.json" in manifest["files"]
    # the two clocks read together place the ring in wall time
    assert abs(manifest["wall_ts"] - manifest["monotonic_t"] - offset) \
        < 0.01
    # every span as the ring holds it, stamps to the last bit
    start = {sp.span_id: sp.start_t for sp in spans}
    assert {sp["name"] for sp in saved} >= {
        SpanName.AGENT_WORKER_FAILURE, SpanName.CKPT_PERSIST_WRITE,
        SpanName.AGENT_SPAWN, SpanName.CKPT_COMMIT}
    for sp in saved:
        assert sp["start_t"] == start[sp["span_id"]], sp["name"]
    assert manifest["trace_t0_monotonic"] == min(
        sp["start_t"] for sp in saved)


def test_no_exit_bundle_without_a_restart(tmp_path, monkeypatch):
    code, spans, trace_dir = run_agent(tmp_path, monkeypatch, -1, side=64)
    assert code == 0
    assert "restarts=0" in (tmp_path / "out.txt").read_text()
    assert not exit_bundles(trace_dir)
    assert not [sp for sp in spans
                if sp.name == SpanName.AGENT_WORKER_FAILURE]
