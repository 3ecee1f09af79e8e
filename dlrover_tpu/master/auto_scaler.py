"""JobAutoScaler: periodic resource re-planning.

Reference: dlrover/python/master/node/job_auto_scaler.py:58–70 —
``AllreduceTrainingAutoScaler`` periodically collects runtime stats and
executes ``ResourcePlan``s through the scaler. The PS variant is a
non-goal (SURVEY.md §2.7). TPU specifics: resize targets stay node_unit
multiples (slice shape), and a resize also refreshes the rendezvous
min/max so the next re-rendezvous cuts the new world.
"""

import threading
import time
from typing import Dict, List, Optional

from dlrover_tpu.common.constants import NodeStatus, SpanName
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability import tracing
from dlrover_tpu.master.resource import (
    ScalingStats,
    LocalOptimizer,
    ResourceOptimizer,
    ResourcePlan,
)


class JobAutoScaler:
    def __init__(
        self,
        job_manager,
        perf_monitor,
        scaler,
        rdzv_managers: Optional[Dict] = None,
        optimizer: Optional[ResourceOptimizer] = None,
        min_nodes: int = 1,
        max_nodes: int = 1,
        node_unit: int = 1,
        interval_s: float = 30.0,
        straggler_provider=None,
        metrics_sink=None,
        strategy_generator=None,
        hbm_provider=None,
        serving_optimizer=None,
        serving_signals=None,
        serve_scaler=None,
        event_journal=None,
        brain_advisor=None,
    ):
        self._job_manager = job_manager
        self._perf_monitor = perf_monitor
        self._scaler = scaler
        self._rdzv_managers = rdzv_managers or {}
        self._optimizer = optimizer or LocalOptimizer()
        self.min_nodes = min_nodes
        self.max_nodes = max_nodes
        self.node_unit = node_unit
        self.target_nodes = max_nodes
        self._interval_s = interval_s
        self._straggler_provider = straggler_provider or (lambda: [])
        # optional per-tick stats export (e.g. BrainClient.report_metric —
        # feeds the cluster-level history the Brain optimizers learn from)
        self._metrics_sink = metrics_sink
        # paral-config plans flow through the strategy generator → servicer
        # → agent tuner file (the live ParallelConfig path)
        self._strategy_generator = strategy_generator
        self._hbm_provider = hbm_provider or (lambda: None)
        # plan sources (Brain OomGuard/InitAdjust) re-emit the same
        # multiplicative plan every tick until fresh telemetry lands;
        # without a cooldown execute() would compound 0.5^ticks
        self.paral_cooldown_s = 300.0
        self._last_paral_apply = float("-inf")  # the first plan always applies
        # serving plane (serving/autoscaler.py): a traffic-driven optimizer
        # rides the same tick — signals provider feeds it, plans execute
        # through the serve scaler (replica processes/pods, NOT the
        # training world's node count)
        self._serving_optimizer = serving_optimizer
        self._serving_signals = serving_signals or (lambda: None)
        self._serve_scaler = serve_scaler
        self._event_journal = event_journal
        # predictive serve pre-scaling (brain/advisor.py): consulted
        # BEFORE the reactive optimizer so a forecast ramp grows the
        # replica set ahead of the queue actually going deep
        self._brain_advisor = brain_advisor
        # a restore plan re-emits every tick until the replacement
        # registers; journal it once per distinct plan, not per tick
        self._last_serve_plan = None
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="job-auto-scaler", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stopped.set()

    def _loop(self) -> None:
        # deadline pacing: ticks land on the cadence grid regardless of
        # how long planning/execution took, and stop() wakes immediately
        # — a tick that overruns a whole period skips forward instead of
        # bursting to catch up
        next_tick = time.monotonic() + self._interval_s
        while not self._stopped.wait(
            max(0.0, next_tick - time.monotonic())
        ):
            next_tick += self._interval_s
            now = time.monotonic()
            if next_tick <= now:
                next_tick = now + self._interval_s
            try:
                self.tick()
            except Exception:  # noqa: BLE001
                logger.exception("auto-scaler tick failed")

    # -- one planning round ------------------------------------------------

    def collect_stats(self) -> ScalingStats:
        now = time.monotonic()  # vs node.create_time (master-monotonic)
        running = pending = 0
        oldest_pending = 0.0
        for node in self._job_manager.nodes.values():
            if node.status == NodeStatus.RUNNING:
                running += 1
            elif node.status in (NodeStatus.PENDING, NodeStatus.INITIAL):
                pending += 1
                oldest_pending = max(oldest_pending, now - node.create_time)
        return ScalingStats(
            running_nodes=running,
            pending_nodes=pending,
            target_nodes=self.target_nodes,
            min_nodes=self.min_nodes,
            max_nodes=self.max_nodes,
            node_unit=self.node_unit,
            running_speed=self._perf_monitor.running_speed(),
            straggler_nodes=list(self._straggler_provider()),
            hbm_used_frac=self._hbm_provider(),
            oldest_pending_s=oldest_pending,
        )

    def serve_tick(self) -> None:
        """Serving side of the tick: traffic signals → ServePlan →
        serve scaler. Separate from the training plan on purpose — a
        serving grow must never resize the training world."""
        if self._serving_optimizer is None:
            return
        signals = self._serving_signals()
        if signals is None:
            return
        if self._brain_advisor is not None:
            try:
                pre = self._brain_advisor.serve_prescale(signals)
            except Exception:  # noqa: BLE001 — advice must not scale
                logger.exception("brain serve pre-scale failed")
                pre = None
            if pre is not None:
                # clamp to the reactive optimizer's headroom — the brain
                # predicts demand, the operator still bounds capacity
                target = min(pre, self._serving_optimizer.max_replicas)
                if target > signals.target_replicas:
                    logger.info("brain pre-scale → %s replicas", target)
                    if self._event_journal is not None:
                        from dlrover_tpu.observability.journal import (
                            JournalEvent,
                        )

                        self._event_journal.record(
                            JournalEvent.SERVE_SCALE, source="brain",
                            target=target, reason="brain pre-scale",
                        )
                    if self._serve_scaler is not None:
                        self._serve_scaler.scale_to(
                            target, reason="brain pre-scale")
                    return  # predictive plan owns this tick
        plan = self._serving_optimizer.plan(signals)
        if plan.empty():
            self._last_serve_plan = None
            return
        # still EXECUTE a repeated plan (scale_to is idempotent and must
        # re-spawn if an earlier spawn died), but only journal/trace the
        # first emission — a restore re-plans every tick for the whole
        # replacement-startup window
        repeat = (plan.replica_num, plan.reason) == self._last_serve_plan
        self._last_serve_plan = (plan.replica_num, plan.reason)
        if repeat:
            if self._serve_scaler is not None:
                self._serve_scaler.scale_to(plan.replica_num,
                                            reason=plan.reason)
            return
        logger.info("serve auto-scale → %s replicas (%s)",
                    plan.replica_num, plan.reason)
        with tracing.span(SpanName.SERVE_SCALE, source="master",
                          target=plan.replica_num, reason=plan.reason):
            if self._event_journal is not None:
                from dlrover_tpu.observability.journal import JournalEvent

                self._event_journal.record(
                    JournalEvent.SERVE_SCALE, target=plan.replica_num,
                    reason=plan.reason,
                )
            if self._serve_scaler is not None:
                self._serve_scaler.scale_to(plan.replica_num,
                                            reason=plan.reason)

    def tick(self) -> Optional[ResourcePlan]:
        self.serve_tick()
        stats = self.collect_stats()
        if self._metrics_sink is not None:
            try:
                self._metrics_sink(stats)
            except Exception:  # noqa: BLE001 — telemetry must not scale
                logger.warning("auto-scaler metrics sink failed",
                               exc_info=True)
        plan = self._optimizer.plan(stats)
        if plan.empty():
            return None
        self.execute(plan)
        return plan

    def execute(self, plan: ResourcePlan) -> None:
        if plan.paral_config is not None and self._strategy_generator:
            scale = plan.paral_config.micro_batch_scale
            now = time.monotonic()  # cooldown window arithmetic
            if (scale and scale != 1.0
                    and now - self._last_paral_apply
                    >= self.paral_cooldown_s):
                self._last_paral_apply = now
                self._strategy_generator.apply_scale(scale, plan.reason)
        if plan.node_num is None:
            return
        target = max(self.min_nodes, min(self.max_nodes, plan.node_num))
        if target == self.target_nodes:
            return
        logger.info(
            "auto-scale %s → %s nodes (%s)",
            self.target_nodes, target, plan.reason,
        )
        # one trace per applied plan: rdzv-param refresh + the k8s scale
        # call are children of the same arc
        with tracing.span(SpanName.SCALE_APPLY, source="master",
                          target=target, prev=self.target_nodes,
                          reason=str(plan.reason)):
            self.target_nodes = target
            # the next re-rendezvous must cut a world of the new size
            for manager in self._rdzv_managers.values():
                with tracing.span(SpanName.SCALE_RDZV_PARAMS,
                                  source="master", target=target):
                    manager.update_rdzv_params(
                        min_nodes=min(self.min_nodes, target),
                        max_nodes=target,
                        node_unit=self.node_unit,
                    )
            if self._scaler is not None:
                from dlrover_tpu.k8s.scaler import ScalePlan

                self._scaler.scale(ScalePlan(worker_num=target))
