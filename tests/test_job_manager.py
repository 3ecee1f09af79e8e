"""Relaunch-ladder / pending-strategy / node-unit policy tests
(reference semantics: dist_job_manager.py:905–988, 457–573;
training_node.py:120; per-role managers node/worker.py)."""

import time

from dlrover_tpu.common.constants import (
    JobStage,
    NodeExitReason,
    NodeStatus,
    NodeType,
)
from dlrover_tpu.master.job_manager import (
    JobManager,
    PendingStrategy,
    RolePolicy,
)


class FakeScaler:
    def __init__(self):
        self.relaunched = []
        self.removed = []

    def relaunch_node(self, node):
        self.relaunched.append(node.id)

    def remove_node(self, node):
        self.removed.append(node.id)


def make_manager(n=2, **kw):
    scaler = FakeScaler()
    jm = JobManager("t", n, scaler=scaler, **kw)
    jm._job_stage = JobStage.RUNNING
    for node in jm.nodes.values():
        node.update_status(NodeStatus.RUNNING)
    return jm, scaler


def fail_node(jm, node_id, reason):
    jm.nodes[node_id].exit_reason = reason
    jm.update_node_status(node_id, NodeStatus.FAILED)


def test_fatal_error_never_relaunches():
    jm, scaler = make_manager()
    fail_node(jm, 0, NodeExitReason.FATAL_ERROR)
    assert scaler.relaunched == []
    assert jm.job_stage == JobStage.FAILED


def test_relaunch_always_overrides_fatal():
    jm, scaler = make_manager(relaunch_always=True)
    fail_node(jm, 0, NodeExitReason.FATAL_ERROR)
    assert scaler.relaunched == [0]
    assert jm.job_stage == JobStage.RUNNING


def test_killed_relaunches_past_the_budget():
    jm, scaler = make_manager(max_relaunch=2)
    for _ in range(4):  # more rounds than the budget allows
        fail_node(jm, 0, NodeExitReason.KILLED)
        jm.nodes[0].update_status(NodeStatus.RUNNING)
    assert scaler.relaunched == [0, 0, 0, 0]
    # the counter still advances (fresh pod names) but never aborts
    assert jm.nodes[0].relaunch_count == 4
    assert jm.job_stage == JobStage.RUNNING


def test_generic_failure_consumes_budget_then_aborts():
    jm, scaler = make_manager(max_relaunch=2)
    for _ in range(2):
        fail_node(jm, 0, NodeExitReason.UNKNOWN)
        jm.nodes[0].update_status(NodeStatus.RUNNING)
    assert jm.nodes[0].relaunch_count == 2
    fail_node(jm, 0, NodeExitReason.UNKNOWN)
    assert jm.job_stage == JobStage.FAILED
    assert len(scaler.relaunched) == 2


def test_oom_grows_memory():
    jm, scaler = make_manager()
    jm.nodes[0].config_resource.memory_mb = 1000
    fail_node(jm, 0, NodeExitReason.OOM)
    assert scaler.relaunched == [0]
    assert jm.nodes[0].config_resource.memory_mb == 1500


def test_hardware_error_clears_host_pin():
    jm, scaler = make_manager()
    jm.nodes[0].host = "host-a"
    fail_node(jm, 0, NodeExitReason.HARDWARE_ERROR)
    assert scaler.relaunched == [0]
    assert jm.nodes[0].host == ""


def test_critical_role_fails_job():
    jm, scaler = make_manager(
        role_policies={NodeType.WORKER: RolePolicy(critical=True)},
    )
    fail_node(jm, 0, NodeExitReason.UNKNOWN)
    assert scaler.relaunched == []
    assert jm.job_stage == JobStage.FAILED


def test_unit_relaunch_takes_slice_peers_down():
    # 4 nodes in units of 2: rank 1 dies -> rank 0 relaunches with it,
    # ranks 2/3 are untouched (one ICI slice = one scheduling atom)
    jm, scaler = make_manager(n=4, node_unit=2)
    fail_node(jm, 1, NodeExitReason.UNKNOWN)
    assert sorted(scaler.relaunched) == [0, 1]
    assert jm.nodes[0].status == NodeStatus.PENDING
    assert jm.nodes[0].exit_reason == NodeExitReason.RELAUNCHED
    # the peer's generation advances so its replacement pod gets a fresh
    # name (the scaler's same-name guard would otherwise no-op)
    assert jm.nodes[0].relaunch_count == 1
    assert jm.nodes[2].status == NodeStatus.RUNNING
    # the peer's own FAILED event (scaler killed it) must not trigger a
    # second unit relaunch
    n_before = len(scaler.relaunched)
    jm.nodes[0].update_status(NodeStatus.FAILED)
    jm._handle_node_failure(jm.nodes[0])
    assert len(scaler.relaunched) == n_before
    assert jm.job_stage == JobStage.RUNNING


def test_pending_timeout_skip_releases_node():
    jm, scaler = make_manager(
        n=3, pending_timeout_s=10, pending_strategy=PendingStrategy.SKIP,
        min_nodes=2,
    )
    node = jm.nodes[2]
    node.update_status(NodeStatus.FAILED)
    node.update_status(NodeStatus.PENDING)
    node.create_time = time.monotonic() - 100
    jm.check_pending_nodes()
    assert node.is_released
    assert scaler.removed == [2]
    assert jm.job_stage == JobStage.RUNNING


def test_pending_timeout_fails_job_below_min_nodes():
    jm, scaler = make_manager(
        n=2, pending_timeout_s=10, pending_strategy=PendingStrategy.SKIP,
        min_nodes=2,
    )
    node = jm.nodes[1]
    node.update_status(NodeStatus.FAILED)
    node.update_status(NodeStatus.PENDING)
    node.create_time = time.monotonic() - 100
    jm.check_pending_nodes()
    assert jm.job_stage == JobStage.FAILED


def test_pending_wait_strategy_does_nothing():
    jm, scaler = make_manager(
        n=2, pending_timeout_s=10, pending_strategy=PendingStrategy.WAIT,
    )
    node = jm.nodes[1]
    node.update_status(NodeStatus.FAILED)
    node.update_status(NodeStatus.PENDING)
    node.create_time = time.monotonic() - 100
    jm.check_pending_nodes()
    assert not node.is_released
    assert jm.job_stage == JobStage.RUNNING


def test_stale_heartbeat_before_start_is_not_dead():
    jm, _ = make_manager()
    node = jm.nodes[0]
    node.start_time = time.monotonic()
    node.heartbeat_time = node.start_time - 50  # predates the restart
    jm.check_heartbeats(now=node.start_time + 10_000)
    assert node.status == NodeStatus.RUNNING


def test_heartbeat_timeout_marks_no_heartbeat():
    jm, scaler = make_manager()
    node = jm.nodes[0]
    # on the injectable clock: on a host up for under 400 s the real
    # monotonic clock would make these stamps negative, i.e. "never beat"
    now = 1000.0
    node.start_time = now - 500
    node.heartbeat_time = now - 400
    jm.check_heartbeats(now=now)
    assert node.exit_reason == NodeExitReason.NO_HEARTBEAT
    assert scaler.relaunched == [0]  # budget-consuming relaunch
    assert node.relaunch_count == 1


def test_connection_drop_declares_death_after_grace():
    """A dropped heartbeat connection with no re-contact inside the grace
    marks the node dead — detection in ~conn_drop_grace_s, not the
    heartbeat timeout."""
    from dlrover_tpu.common.config import get_context

    get_context().set("conn_drop_grace_s", 0.1)
    get_context().set("heartbeat_interval_s", 0.05)
    try:
        jm, scaler = make_manager()
        node = jm.nodes[0]
        node.contact_time = time.monotonic()
        jm.report_connection_lost(0)
        time.sleep(0.3)
        assert node.exit_reason == NodeExitReason.NO_HEARTBEAT
        assert scaler.relaunched == [0]
    finally:
        get_context().set("conn_drop_grace_s", 1.0)
        get_context().set("heartbeat_interval_s", 15.0)


def test_connection_drop_with_recontact_is_benign():
    """An agent that reconnects (client retry) within the grace must NOT
    be declared dead."""
    from dlrover_tpu.common.config import get_context

    get_context().set("conn_drop_grace_s", 0.2)
    get_context().set("heartbeat_interval_s", 0.05)
    try:
        jm, _ = make_manager()
        node = jm.nodes[0]
        node.contact_time = time.monotonic()
        jm.report_connection_lost(0)
        jm.record_node_contact(0, running=True)  # reconnected heartbeat
        time.sleep(0.4)
        assert node.status == NodeStatus.RUNNING
        assert node.exit_reason == ""
    finally:
        get_context().set("conn_drop_grace_s", 1.0)
        get_context().set("heartbeat_interval_s", 15.0)


def test_connection_drop_grace_covers_idle_heartbeat_cadence():
    """With a long heartbeat interval, an idle-connection reset must get a
    grace that outlasts the next tick — not the 1s default."""
    from dlrover_tpu.common.config import get_context

    get_context().set("heartbeat_interval_s", 15.0)
    jm, _ = make_manager()
    node = jm.nodes[0]
    node.contact_time = time.monotonic()
    jm.report_connection_lost(0)
    time.sleep(1.5)  # > conn_drop_grace_s default; << 1.5 * interval
    assert node.status == NodeStatus.RUNNING


def test_raw_contact_defuses_drop_recheck():
    """A dedup-replayed frame (handler never runs) still counts as proof
    of life via record_raw_contact."""
    from dlrover_tpu.common.config import get_context

    get_context().set("conn_drop_grace_s", 0.2)
    get_context().set("heartbeat_interval_s", 0.05)
    try:
        jm, _ = make_manager()
        node = jm.nodes[0]
        node.contact_time = time.monotonic()
        jm.report_connection_lost(0)
        jm.record_raw_contact(0)
        time.sleep(0.4)
        assert node.status == NodeStatus.RUNNING
    finally:
        get_context().set("conn_drop_grace_s", 1.0)
        get_context().set("heartbeat_interval_s", 15.0)


def test_mass_connection_drops_share_one_recheck_thread():
    """A whole rack disconnecting at once must coalesce into ONE
    scheduler thread draining the grace heap — not a Timer thread per
    drop — and every un-recontacted node must still be declared dead."""
    import threading as _threading

    from dlrover_tpu.common.config import get_context

    get_context().set("conn_drop_grace_s", 0.2)
    get_context().set("heartbeat_interval_s", 0.05)
    try:
        jm, scaler = make_manager(n=16)
        before = _threading.active_count()
        for node in jm.nodes.values():
            node.contact_time = time.monotonic()
        for node_id in jm.nodes:
            jm.report_connection_lost(node_id)
        # all 16 drops ride the single recheck thread
        assert _threading.active_count() <= before + 1
        time.sleep(0.8)
        for node in jm.nodes.values():
            assert node.exit_reason == NodeExitReason.NO_HEARTBEAT
        assert sorted(scaler.relaunched) == sorted(jm.nodes)
    finally:
        get_context().set("conn_drop_grace_s", 1.0)
        get_context().set("heartbeat_interval_s", 15.0)


def test_oom_override_reaches_pod_spec():
    """The grown memory must actually render into the replacement pod
    (not just the Node object)."""
    from dlrover_tpu.common.node import Node, NodeResource
    from dlrover_tpu.k8s import specs
    from dlrover_tpu.k8s.crd import TpuReplicaSpec

    node = Node(id=0, rank=0, config_resource=NodeResource(memory_mb=6144))
    pod = specs.worker_pod(
        "j", node.id, TpuReplicaSpec(memory_mb=4096), "m:1",
        resource_override=node.config_resource,
    )
    req = pod["spec"]["containers"][0]["resources"]["requests"]
    assert req["memory"] == "6144Mi"


def test_avoid_hosts_render_as_anti_affinity():
    from dlrover_tpu.k8s import specs
    from dlrover_tpu.k8s.crd import TpuReplicaSpec

    pod = specs.worker_pod(
        "j", 0, TpuReplicaSpec(), "m:1", avoid_hosts=["bad-host"],
    )
    terms = pod["spec"]["affinity"]["nodeAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"
    ]["nodeSelectorTerms"]
    assert terms[0]["matchExpressions"][0]["values"] == ["bad-host"]
    assert terms[0]["matchExpressions"][0]["operator"] == "NotIn"


def test_first_heartbeat_then_crash_is_detected():
    """record_node_contact stamps heartbeat AFTER the RUNNING promotion,
    so a node that heartbeats once and dies is still judged dead."""
    jm, scaler = make_manager(n=1)
    jm.nodes[0].status = NodeStatus.INITIAL
    jm.nodes[0].start_time = None
    jm.record_node_contact(0, running=True)
    node = jm.nodes[0]
    assert node.status == NodeStatus.RUNNING
    assert node.heartbeat_time >= node.start_time
    jm.check_heartbeats(now=time.monotonic() + 10_000)
    assert node.exit_reason == NodeExitReason.NO_HEARTBEAT


def test_crash_exit_code_consumes_budget():
    """watcher maps generic crashes to UNKNOWN (budget branch), signal
    kills to KILLED (budget-free)."""
    from dlrover_tpu.k8s.watcher import pod_exit_reason

    def pod(code, reason=None):
        term = {"exitCode": code}
        if reason:
            term["reason"] = reason
        return {"status": {"containerStatuses": [{"state": {
            "terminated": term}}]}}

    assert pod_exit_reason(pod(1)) == NodeExitReason.UNKNOWN
    assert pod_exit_reason(pod(137)) == NodeExitReason.KILLED
    assert pod_exit_reason(pod(143)) == NodeExitReason.KILLED
    assert pod_exit_reason(
        pod(137, "OOMKilled")) == NodeExitReason.OOM


def test_relaunch_resets_pending_clock():
    jm, scaler = make_manager(n=2, pending_timeout_s=10)
    node = jm.nodes[0]
    node.create_time = time.monotonic() - 7200  # job has run for hours
    fail_node(jm, 0, NodeExitReason.PREEMPTED)
    assert node.status == NodeStatus.PENDING
    # freshly relaunched: the pending clock restarted, so the next
    # monitor tick must NOT release it
    jm.check_pending_nodes()
    assert not node.is_released
