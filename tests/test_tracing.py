"""Causal tracing + flight recorder tests (docs/design/
tracing_flight_recorder.md).

Covers the tentpole guarantees end to end, all in one process so the
global tracer ring sees both sides of every boundary:

- context propagation across a real RPCServer/RPCClient pair;
- ONE trace_id spanning agent→master→agent: a rendezvous round joins the
  joining agent's client spans to the master's join/world-cut spans, and
  a node-failure broadcast carries the failing agent's context back down
  to survivors in heartbeat action_data;
- ring eviction under overflow;
- the disabled no-op path (DLROVER_TPU_TRACE=0);
- flight-recorder bundle capture, both explicit and via an injected
  chaos fault through ``wrap_fault_reporter``.
"""

import json
import os

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.chaos.injector import FaultInjector, InjectedError, parse_rule
from dlrover_tpu.common.constants import (
    ConfigKey,
    NodeStatus,
    RendezvousName,
    SpanName,
)
from dlrover_tpu.common.rpc import RPCClient, RPCError, RPCServer
from dlrover_tpu.master.master import LocalJobMaster
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.flight_recorder import (
    REASON_CHAOS,
    REASON_CRASH,
    FlightRecorder,
)
from dlrover_tpu.observability.journal import EventJournal, JournalEvent
from dlrover_tpu.observability.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def fresh_tracer(tmp_path, monkeypatch):
    """Every test gets its own tracer ring and a throwaway bundle dir."""
    monkeypatch.setenv(ConfigKey.TRACE_DIR, str(tmp_path / "bundles"))
    tracing.reset_tracer()
    yield
    tracing.reset_tracer()


def spans_named(name, source=None):
    out = []
    for sp in tracing.get_tracer().finished_spans():
        if sp.name != name:
            continue
        if source is not None and sp.source != source:
            continue
        out.append(sp)
    return out


# -- span mechanics ----------------------------------------------------------


def test_span_nesting_and_ring():
    with tracing.span(SpanName.RDZV_CLIENT_ROUND, source="agent_0") as outer:
        assert tracing.current_context() == outer.context
        with tracing.span(SpanName.RDZV_JOIN, source="agent_0") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            inner.add_event("attempt", n=1)
        # inner closed: context restored to outer
        assert tracing.current_context() == outer.context
    assert tracing.current_context() is None
    ring = tracing.get_tracer().finished_spans()
    assert [sp.name for sp in ring] == [
        SpanName.RDZV_JOIN, SpanName.RDZV_CLIENT_ROUND,
    ]
    assert ring[0].events[0]["name"] == "attempt"


def test_ring_eviction_under_overflow(monkeypatch):
    monkeypatch.setenv(ConfigKey.TRACE_RING, "4")
    tracing.reset_tracer()
    for _ in range(10):
        with tracing.span(SpanName.RDZV_JOIN, source="agent_0"):
            pass
    tr = tracing.get_tracer()
    counts = tr.counts()
    assert counts["finished"] == 10
    assert counts["ring"] == 4
    assert counts["dropped"] == 6
    assert tr.dropped() == 6
    # the ring keeps the NEWEST spans (post-mortems care about the end)
    assert len(tr.finished_spans()) == 4


def test_chrome_export_shapes():
    with tracing.span(SpanName.CKPT_SAVE_MEMORY, source="worker_0", step=7):
        tracing.add_span_event(SpanName.EVT_RPC_RETRY, attempt=1)
    events = tracing.to_chrome_events(tracing.get_tracer().finished_spans())
    slices = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    assert len(slices) == 1 and slices[0]["name"] == SpanName.CKPT_SAVE_MEMORY
    assert slices[0]["args"]["step"] == 7
    assert len(instants) == 1 and instants[0]["name"] == SpanName.EVT_RPC_RETRY
    # valid chrome-trace JSON end to end
    json.loads(json.dumps({"traceEvents": events}))


# -- disabled no-op path -----------------------------------------------------


def test_disabled_path_is_noop(monkeypatch):
    monkeypatch.setenv(ConfigKey.TRACE, "0")
    tracing.reset_tracer()
    assert not tracing.enabled()
    s1 = tracing.span(SpanName.RDZV_JOIN, source="agent_0")
    s2 = tracing.span(SpanName.RDZV_WORLD_CUT, source="master")
    # one shared no-op object: no per-call allocation on the hot path
    assert s1 is s2
    with s1:
        assert tracing.inject_wire() is None
        tracing.add_span_event("ignored")  # must not raise
    assert tracing.get_tracer().counts() == {
        "started": 0, "finished": 0, "live": 0, "ring": 0, "dropped": 0,
    }


# -- RPC propagation ---------------------------------------------------------


@pytest.fixture()
def echo_server():
    server = RPCServer(host="127.0.0.1")
    seen = []

    def handler(request):
        ctx = tracing.current_context()
        seen.append(ctx)
        return {"trace_id": ctx.trace_id if ctx else None}

    server.register("echo_ctx", handler)
    server.register("boom", lambda req: 1 / 0)
    server.start()
    yield server, seen
    server.stop()


def test_rpc_carries_context_to_handler(echo_server):
    server, seen = echo_server
    client = RPCClient(f"127.0.0.1:{server.port}")
    with tracing.span(SpanName.RDZV_CLIENT_ROUND, source="agent_0") as sp:
        resp = client.call("echo_ctx")
    assert resp["trace_id"] == sp.trace_id
    # the handler-side context is the caller's (trace_id, span_id)
    assert seen[-1] == sp.context


def test_rpc_without_active_span_sends_no_context(echo_server):
    server, seen = echo_server
    client = RPCClient(f"127.0.0.1:{server.port}")
    resp = client.call("echo_ctx")
    assert resp["trace_id"] is None
    assert seen[-1] is None


def test_rpc_error_names_method_and_trace(echo_server):
    server, _ = echo_server
    client = RPCClient(f"127.0.0.1:{server.port}")
    with tracing.span(SpanName.RDZV_CLIENT_ROUND, source="agent_0") as sp:
        with pytest.raises(RPCError) as err:
            client.call("boom")
    msg = str(err.value)
    assert "rpc boom" in msg
    assert f"trace_id={sp.trace_id}" in msg


# -- one trace_id across agent→master→agent ----------------------------------


@pytest.fixture()
def master():
    m = LocalJobMaster(job_name="trace-test", node_num=2)
    for mgr in m.rdzv_managers.values():
        mgr.update_rdzv_params(2, 2, waiting_timeout=0.05)
    m.prepare()
    yield m
    m.stop()


def test_rendezvous_round_shares_one_trace_id(master):
    c0 = MasterClient(master.addr, 0)
    c1 = MasterClient(master.addr, 1)
    # peer joins first (its own arc), then agent 0 runs a full client
    # round: join + world-wait. The world cut fires on agent 0's poll.
    c1.join_rendezvous(RendezvousName.TRAINING, 1, 1,
                       host="127.0.0.1", free_port=2222)
    with tracing.span(SpanName.RDZV_CLIENT_ROUND, source="agent_0") as round_sp:
        c0.join_rendezvous(RendezvousName.TRAINING, 0, 1,
                           host="127.0.0.1", free_port=1111)
        rnd, _, world, _ = c0.get_comm_world(RendezvousName.TRAINING, 0)
    assert rnd == 1 and sorted(world) == [0, 1]

    tid = round_sp.trace_id
    # agent-side spans of the arc
    assert [sp.trace_id for sp in
            spans_named(SpanName.RDZV_JOIN, "agent_0")] == [tid]
    assert [sp.trace_id for sp in
            spans_named(SpanName.RDZV_WORLD_WAIT, "agent_0")] == [tid]
    # master-side spans ran in the servicer under agent 0's restored
    # context — same trace_id, so the arc crosses the process boundary
    master_joins = spans_named(SpanName.RDZV_JOIN, "master")
    assert tid in {sp.trace_id for sp in master_joins}
    cuts = spans_named(SpanName.RDZV_WORLD_CUT, "master")
    assert [sp.trace_id for sp in cuts] == [tid]
    # and agent 1's join belongs to a DIFFERENT trace (no accidental merge)
    other = {sp.trace_id for sp in master_joins} - {tid}
    assert len(other) == 1


def test_node_fault_trace_rides_back_to_survivors(master):
    """agent→master→agent: the failing agent's trace context crosses up
    into the master's fault-relaunch span and back down to the surviving
    agent inside the heartbeat RESTART_WORKER action."""

    class FakeScaler:
        def relaunch_node(self, node):
            pass

    master.job_manager._scaler = FakeScaler()
    c0 = MasterClient(master.addr, 0)
    c1 = MasterClient(master.addr, 1)
    c0.update_node_status(NodeStatus.RUNNING)
    c1.update_node_status(NodeStatus.RUNNING)

    with tracing.span(SpanName.RDZV_CLIENT_ROUND, source="agent_0") as sp:
        c0.update_node_status(NodeStatus.FAILED)
    tid = sp.trace_id

    # the master's detect→relaunch span joined agent 0's trace
    relaunch = spans_named(SpanName.FAULT_RELAUNCH, "master")
    assert [s.trace_id for s in relaunch] == [tid]

    # the surviving agent's heartbeat reply carries the same context
    resp = c1.heartbeat()
    assert resp.action_type == "restart_worker"
    carried = tracing.extract_wire(resp.action_data.get(tracing.WIRE_KEY))
    assert carried is not None and carried.trace_id == tid

    # an agent-side restart span opened under it completes the arc
    with tracing.activate(carried):
        with tracing.span(SpanName.AGENT_RESTART_WORKERS, source="agent_1"):
            pass
    restart = spans_named(SpanName.AGENT_RESTART_WORKERS, "agent_1")
    assert [s.trace_id for s in restart] == [tid]

    # a node fault auto-captures a master flight-recorder bundle
    bundles = os.listdir(master.flight_recorder.out_dir)
    assert any("node_fault" in b for b in bundles)


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_bundle_contents(tmp_path):
    journal = EventJournal()
    registry = MetricsRegistry()
    journal.record(JournalEvent.RDZV_START, source="master", round=1)
    with tracing.span(SpanName.RDZV_JOIN, source="agent_0"):
        pass
    fr = FlightRecorder("master", out_dir=str(tmp_path / "fr"),
                        journal=journal, registry=registry, cooldown_s=0.0)
    path = fr.capture(REASON_CRASH, extra={"error": "boom"})
    assert path is not None and os.path.isdir(path)
    files = sorted(os.listdir(path))
    assert files == ["config.json", "incidents.json", "journal.json",
                     "manifest.json", "metrics.prom", "spans.json",
                     "stacks.txt", "traces.json"]
    with open(os.path.join(path, "spans.json")) as f:
        assert SpanName.RDZV_JOIN in {sp["name"] for sp in json.load(f)}

    with open(os.path.join(path, "traces.json")) as f:
        traces = json.load(f)
    names = {e.get("name") for e in traces["traceEvents"]}
    assert SpanName.RDZV_JOIN in names

    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["reason"] == REASON_CRASH
    assert manifest["error"] == "boom"
    assert manifest["spans_finished"] >= 1

    # the capture itself is journaled and counted
    events = json.loads(journal.to_json())["events"]
    assert any(e["kind"] == JournalEvent.TRACE_BUNDLE_CAPTURED
               for e in events)
    assert 'dlrover_trace_bundles_total{reason="unhandled_exception"} 1' in (
        registry.render()
    )

    with open(os.path.join(path, "stacks.txt")) as f:
        assert "MainThread" in f.read()


def test_flight_recorder_cooldown_and_force(tmp_path):
    fr = FlightRecorder("agent_0", out_dir=str(tmp_path / "fr"),
                        cooldown_s=60.0)
    assert fr.capture(REASON_CRASH) is not None
    assert fr.capture(REASON_CRASH) is None  # rate-limited
    assert fr.capture(REASON_CRASH, force=True) is not None


def test_injected_fault_triggers_bundle(tmp_path):
    """An injected chaos fault leaves a post-mortem artifact even though
    the code under test recovers — wrap_fault_reporter chains the
    existing reporter and captures REASON_CHAOS."""
    journal = EventJournal()
    fr = FlightRecorder("master", out_dir=str(tmp_path / "fr"),
                        journal=journal, cooldown_s=0.0)
    inj = FaultInjector([parse_rule("rpc.send:error@times=1")])
    reported = []
    inj.set_reporter(fr.wrap_fault_reporter(reported.append))

    with pytest.raises(InjectedError):
        inj.fire("rpc.send", method="heartbeat")

    assert reported and reported[0]["fault"] == "error"
    bundles = os.listdir(str(tmp_path / "fr"))
    assert len(bundles) == 1 and REASON_CHAOS in bundles[0]
    with open(os.path.join(str(tmp_path / "fr"), bundles[0],
                           "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["fault_site"] == "rpc.send"
    assert manifest["fault_kind"] == "error"


def test_http_bundle_handler(tmp_path):
    fr = FlightRecorder("master", out_dir=str(tmp_path / "fr"),
                        cooldown_s=60.0)
    handle = fr.http_handler()
    ctype, body = handle()
    assert ctype == "application/json"
    payload = json.loads(body)
    assert payload["ok"] and os.path.isdir(payload["path"])
    # force=True: a second explicit request ignores the cooldown
    _, body2 = handle()
    assert json.loads(body2)["ok"]
