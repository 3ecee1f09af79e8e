"""The SERVE node: a decode replica process.

A replica is master-managed exactly like a worker — it registers (which
types its node ``SERVE``), heartbeats on the shared liveness plane
(conn-drop grace + heartbeat timeout + fan-in backpressure), and serves
``serve_generate``/``serve_drain`` on its own RPC server. Death needs no
cooperation: a SIGKILL closes the heartbeat socket, the master's grace
recheck fails the node, and the node-event callback drops it from the
serve registry while the router re-routes (see master/master.py — a
SERVE death never triggers a training world restart).

:class:`LocalReplicaManager` is the local serve SCALER: replicas as
subprocesses of this host (so a chaos SIGKILL is a real process death),
``scale_to`` the only verb — grow spawns, shrink drains. It is the
``serve_scaler`` the deadline-paced ``JobAutoScaler`` tick executes
serving plans through; production deployments would put a pod scaler
behind the same two methods.

Weight distribution rides the state-movement fabric
(``common/fabric.py``): a replica whose engine carries real params
mounts a ``weights`` provider on its RPC server, and a newly grown
replica warm-starts by striping the exported params from EVERY live
peer at once (:func:`load_weights_from_peers`) instead of rebuilding
from seed — the serving-plane slice of ROADMAP item 2.

Chaos site ``serve.replica`` fires in the replica's heartbeat loop: an
injected error/drop crashes the replica abruptly (no drain, no
deregister) — the replica-kill drill without process machinery.
"""

import argparse
import signal
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import comm, fabric
from dlrover_tpu.common.config import get_context
from dlrover_tpu.common.constants import (
    ChaosSite,
    ConfigKey,
    SpanName,
    env_flag,
)
from dlrover_tpu.common.http_server import HTTPTransportServer
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.rpc import RPCServer
from dlrover_tpu.observability import tracing
from dlrover_tpu.observability.flight_recorder import FlightRecorder
from dlrover_tpu.observability.journal import EventJournal
from dlrover_tpu.observability.registry import get_registry
from dlrover_tpu.serving.batcher import BatcherClosed, ContinuousBatcher
from dlrover_tpu.serving.tail import TailAttributor

SERVE_REPLICA_SITE = ChaosSite.SERVE_REPLICA

# fabric key serving replicas publish their exported params under
WEIGHTS_KEY = "weights/current"


def load_weights_from_peers(engine, peer_addrs, reporter=None,
                            timeout_s: float = 60.0) -> bool:
    """Warm-start ``engine`` from live peer replicas: one striped fabric
    session across every peer that serves :data:`WEIGHTS_KEY`. Returns
    False (engine untouched, seed weights stand) when no peer serves
    weights or the session aborts — growth must never fail on this."""
    if not hasattr(engine, "set_params") or not peer_addrs:
        return False
    t0 = time.monotonic()
    sources = [fabric.FabricSource(addr=a) for a in peer_addrs]
    try:
        _step, blob, stats = fabric.fetch(
            sources, WEIGHTS_KEY, timeout_s=timeout_s, reporter=reporter,
        )
    except fabric.FabricAbort as e:
        logger.info("peer weight load aborted (%s) — keeping seed weights",
                    e.reason)
        return False
    from dlrover_tpu.serving.engine import import_params

    engine.set_params(import_params(blob))
    duration = time.monotonic() - t0
    get_registry().histogram(
        "dlrover_serving_weight_load_seconds",
        "Wall-clock time to warm-start a replica's weights from peers",
    ).observe(duration)
    logger.info(
        "warm-started weights from %s peer(s): %s bytes in %.3fs "
        "(%.1f MB/s)", stats.get("sources"), stats.get("bytes"), duration,
        stats.get("rate_mbps", 0.0),
    )
    return True


class DecodeReplica:
    def __init__(
        self,
        master_addr: str,
        node_id: int,
        engine,
        buckets=(8, 16),
        max_new_cap: int = 64,
        host: str = "127.0.0.1",
        port: int = 0,
        heartbeat_interval_s: Optional[float] = None,
        request_timeout_s: float = 60.0,
        prefill_workers: int = 1,
        on_crash: Optional[Callable[[], None]] = None,
        http_port: int = 0,
    ):
        self.node_id = node_id
        # replica-local observability plane, scrapeable mid-drill like an
        # agent's: a journal for request/prefix/tail events, the tail
        # attributor fed by every batcher completion, and a flight
        # recorder whose bundles embed the worst request waterfalls
        self.journal = EventJournal()
        registry = get_registry()
        self.tail = TailAttributor(
            journal_fn=lambda kind, **data: self.journal.record(
                kind, source=f"replica_{node_id}", **data),
            registry=registry,
        )
        self._batcher = ContinuousBatcher(
            engine, buckets=buckets, max_new_cap=max_new_cap,
            prefill_workers=prefill_workers,
            journal_fn=lambda kind, **data: self.journal.record(
                kind, source=f"replica_{node_id}", **data),
            on_complete=self.tail.observe,
            source=f"replica_{node_id}",
        )
        self.recorder = FlightRecorder(
            source=f"replica_{node_id}", journal=self.journal,
            registry=registry, worst_traces_fn=self.tail.worst_requests,
        )
        self._http_server = HTTPTransportServer(host=host, port=http_port)
        self._http_server.add_get_route(
            "/metrics",
            lambda: ("text/plain; version=0.0.4", registry.render()))
        self._http_server.add_get_route(
            "/events",
            lambda: ("application/json", self.journal.to_json()))
        self._http_server.add_get_route(
            "/debug/bundle", self.recorder.http_handler())
        self._server = RPCServer(host=host, port=port)
        self._server.register_object(self)
        # engines with real params serve them over the striped fabric so
        # grown replicas warm-start from live peers (toy engines don't)
        self._weights_blob: Optional[bytes] = None
        if hasattr(engine, "set_params"):
            self._fabric = fabric.FabricServer(server=self._server)
            self._fabric.register_provider("weights", self._provide_weights)
        self._host = host
        self._client = MasterClient(master_addr, node_id=node_id)
        self._hb_interval_s = (
            get_context().heartbeat_interval_s
            if heartbeat_interval_s is None else heartbeat_interval_s
        )
        self._request_timeout_s = request_timeout_s
        self._stop_evt = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._on_crash = on_crash
        self.crashed = False

    @property
    def addr(self) -> str:
        return f"{self._host}:{self._server.port}"

    @property
    def http_addr(self) -> str:
        """The observability endpoint (GET /metrics, /events,
        /debug/bundle, /healthz) — same contract as an agent's."""
        return f"{self._host}:{self._http_server.port}"

    def _provide_weights(self, rest: str):
        del rest  # one object per replica: weights/current
        blob = self._weights_blob
        if blob is None:
            from dlrover_tpu.serving.engine import export_params

            blob = export_params(self._batcher._engine.params)
            self._weights_blob = blob
        # step 0 / etag 0: weights are immutable for a replica's lifetime
        return 0, len(blob), 0, lambda off, n: blob[off:off + n]

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._server.start()
        self._http_server.start()
        logger.info("replica %s observability http on %s",
                    self.node_id, self.http_addr)
        # warm-start BEFORE registering: this replica is not yet in the
        # membership, so the fetch can only land on live peers
        self._maybe_warm_start()
        self._batcher.start()
        epoch = self._client.serve_register(self.addr,
                                            self._batcher._engine.slots)
        logger.info("replica %s registered at %s (epoch %s)",
                    self.node_id, self.addr, epoch)
        self._hb_thread = threading.Thread(
            target=self._hb_loop, name=f"serve-hb-{self.node_id}",
            daemon=True,
        )
        self._hb_thread.start()

    def _maybe_warm_start(self) -> None:
        engine = self._batcher._engine
        if not hasattr(engine, "set_params"):
            return
        try:
            _epoch, replicas = self._client.serve_replicas()
        except (ConnectionError, RuntimeError) as e:
            logger.info("peer listing for warm start failed: %r", e)
            return
        peers = [r["addr"] for r in replicas if r["node_id"] != self.node_id]
        if peers:
            load_weights_from_peers(engine, peers)

    def _hb_loop(self) -> None:
        # deadline pacing (DLR010 discipline): beats land on the cadence
        # grid regardless of per-beat latency, and stop wakes instantly
        interval = self._hb_interval_s
        next_beat = time.monotonic() + interval
        while not self._stop_evt.wait(max(0.0, next_beat - time.monotonic())):
            next_beat += interval
            now = time.monotonic()
            if next_beat <= now:  # overran a whole period: skip, no burst
                next_beat = now + interval
            from dlrover_tpu.chaos import get_injector

            inj = get_injector()
            try:
                if inj is not None:
                    inj.fire(SERVE_REPLICA_SITE, node_id=self.node_id)
                gauges = {
                    "serve_queue_depth": float(self._batcher.queue_depth()),
                    "serve_active_slots": float(self._batcher.active()),
                }
                engine = self._batcher._engine
                if hasattr(engine, "stats"):  # prefix-caching wrapper:
                    # hit-rate/savings ride the existing heartbeat gauge
                    # channel to the master's telemetry spine
                    st = engine.stats()
                    gauges["serve_prefix_hit_rate"] = float(st["hit_rate"])
                    gauges["serve_prefix_tokens_saved"] = float(
                        st["tokens_saved"])
                resp = self._client.heartbeat(gauges=gauges)
                if resp.action_type == "job_abort":
                    logger.warning("replica %s told to abort", self.node_id)
                    self._stop_evt.set()
            except (ConnectionError, RuntimeError):
                # injected kill (InjectedFault/InjectedError are subtypes)
                # or master unreachable past the heartbeat retry budget:
                # an un-drained, crash-like death either way
                logger.warning("replica %s heartbeat failed — crashing",
                               self.node_id, exc_info=True)
                self.crash()
                return

    def run(self) -> int:
        """Block until drained/aborted (subprocess entrypoint)."""
        self._stop_evt.wait()
        return 17 if self.crashed else 0

    def stop(self) -> None:
        self._stop_evt.set()
        self._batcher.stop()
        self._server.stop()
        self._http_server.stop()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5.0)

    def crash(self) -> None:
        """Abrupt, crash-like death: no drain, no deregister — in-flight
        requests fail and the MASTER discovers the loss through the
        liveness plane, exactly like a SIGKILL."""
        self.crashed = True
        self._stop_evt.set()
        self._server.stop()
        self._http_server.stop()
        self._batcher.stop()
        if self._on_crash is not None:
            self._on_crash()

    # -- RPC surface (the router's data plane) -----------------------------

    def rpc_serve_generate(
        self, req: comm.ServeGenerateRequest
    ) -> comm.ServeGenerateResponse:
        with tracing.span(SpanName.SERVE_GENERATE,
                          source=f"replica_{self.node_id}",
                          request_id=req.request_id) as gspan:
            trace_id = getattr(gspan, "trace_id", None) or ""
            try:
                pending = self._batcher.submit(
                    req.request_id, req.prompt, req.max_new_tokens,
                    rerouted=req.rerouted)
            except BatcherClosed:
                return comm.ServeGenerateResponse(
                    request_id=req.request_id, success=False,
                    message="draining", replica_id=self.node_id)
            except ValueError as e:
                return comm.ServeGenerateResponse(
                    request_id=req.request_id, success=False,
                    message=str(e), replica_id=self.node_id)
            if not pending.done.wait(self._request_timeout_s):
                return comm.ServeGenerateResponse(
                    request_id=req.request_id, success=False,
                    message="timeout", replica_id=self.node_id)
            if pending.error:
                return comm.ServeGenerateResponse(
                    request_id=req.request_id, success=False,
                    message=pending.error, replica_id=self.node_id)
            n_out = max(1, len(pending.tokens) - 1)
            return comm.ServeGenerateResponse(
                request_id=req.request_id, success=True,
                tokens=pending.tokens,
                ttft_s=pending.t_first - pending.enqueue_t,
                tpot_s=(pending.t_done - pending.t_first) / n_out,
                queue_depth=self._batcher.queue_depth(),
                replica_id=self.node_id,
                trace_id=pending.trace_id or trace_id,
            )

    def rpc_serve_drain(self, req: comm.ServeDrainRequest
                        ) -> comm.BaseResponse:
        with tracing.span(SpanName.SERVE_DRAIN,
                          source=f"replica_{self.node_id}",
                          reason=req.reason):
            drained = self._batcher.drain(timeout_s=self._request_timeout_s)
            try:
                self._client.serve_deregister(reason=req.reason or "drain")
            except (ConnectionError, RuntimeError):
                logger.warning("deregister after drain failed",
                               exc_info=True)
            self._stop_evt.set()
            return comm.BaseResponse(success=drained)

    def rpc_serve_ping(self, req: comm.BaseRequest) -> comm.BaseResponse:
        return comm.BaseResponse()


class LocalReplicaManager:
    """Subprocess serve scaler for one host: ``scale_to`` is the verb the
    serving autoscaler executes, ``kill_one`` the chaos hammer."""

    def __init__(
        self,
        master_addr: str,
        live_fn: Callable[[], List[Dict]],
        backend: str = "toy",
        slots: int = 4,
        buckets=(8, 16),
        max_new_cap: int = 16,
        cache_len: int = 48,
        heartbeat_interval_s: float = 0.2,
        seed: int = 0,
        first_node_id: int = 100,
        drain_fn: Optional[Callable[[str], None]] = None,
        step_delay_s: float = 0.0,
        prefill_delay_s: float = 0.0,
        quantize: bool = False,
        prefix_cache: bool = False,
    ):
        self._master_addr = master_addr
        self._live_fn = live_fn
        self._backend = backend
        self._slots = slots
        self._buckets = tuple(buckets)
        self._max_new_cap = max_new_cap
        self._cache_len = cache_len
        self._hb_interval_s = heartbeat_interval_s
        self._seed = seed
        self._next_node_id = first_node_id
        self._drain_fn = drain_fn
        # toy-backend pacing: gives drill traffic a real duration so a
        # mid-traffic kill actually lands mid-traffic
        self._step_delay_s = step_delay_s
        self._prefill_delay_s = prefill_delay_s
        self._quantize = quantize
        self._prefix_cache = prefix_cache
        self._lock = threading.Lock()
        self._procs: Dict[int, subprocess.Popen] = {}
        self._poll_evt = threading.Event()  # pacing only, never set
        self.target = 0

    def spawn(self) -> int:
        with self._lock:
            node_id = self._next_node_id
            self._next_node_id += 1
            cmd = [
                sys.executable, "-m", "dlrover_tpu.serving.replica",
                "--master", self._master_addr,
                "--node-id", str(node_id),
                "--backend", self._backend,
                "--slots", str(self._slots),
                "--buckets", ",".join(str(b) for b in self._buckets),
                "--max-new-cap", str(self._max_new_cap),
                "--cache-len", str(self._cache_len),
                "--hb-interval-s", str(self._hb_interval_s),
                "--seed", str(self._seed),
                "--step-delay-s", str(self._step_delay_s),
                "--prefill-delay-s", str(self._prefill_delay_s),
            ]
            if self._quantize:
                cmd.append("--quantize")
            if self._prefix_cache:
                cmd.append("--prefix-cache")
            # the child inherits the caller's platform (JAX_PLATFORMS):
            # a caller that holds the chip itself exports cpu for them
            self._procs[node_id] = subprocess.Popen(cmd)
        logger.info("spawned replica subprocess node %s", node_id)
        return node_id

    def _alive_ids(self) -> List[int]:
        with self._lock:
            dead = [nid for nid, p in self._procs.items()
                    if p.poll() is not None]
            for nid in dead:
                del self._procs[nid]
            return list(self._procs)

    def scale_to(self, n: int, reason: str = "") -> None:
        self.target = n
        alive = self._alive_ids()
        if len(alive) != n:
            logger.info("serve scale_to %s (%s): %s alive",
                        n, reason or "plan", len(alive))
        for _ in range(n - len(alive)):
            self.spawn()
        # shrink is a DRAIN, newest first (planned scale-down completes
        # all in-flight — the batcher guarantees it replica-side)
        for nid in sorted(alive, reverse=True)[:max(0, len(alive) - n)]:
            self.drain_one(nid, reason=reason or "scale down")

    def drain_one(self, node_id: int, reason: str = "scale down",
                  timeout_s: float = 30.0) -> bool:
        addr = next((r["addr"] for r in self._live_fn()
                     if r["node_id"] == node_id), None)
        if addr is not None and self._drain_fn is not None:
            self._drain_fn(addr)
        elif addr is not None:
            from dlrover_tpu.common.rpc import RPCClient

            RPCClient(addr, timeout_s=timeout_s).call(
                "serve_drain", comm.ServeDrainRequest(reason=reason),
                retries=0,
            )
        with self._lock:
            proc = self._procs.pop(node_id, None)
        if proc is None:
            return True
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            logger.warning("drained replica %s did not exit — killing",
                           node_id)
            proc.kill()
            proc.wait(timeout=5.0)
        return True

    def kill_one(self, node_id: Optional[int] = None) -> Optional[int]:
        """SIGKILL a replica mid-traffic (the chaos scenario). Returns
        the victim's node id."""
        with self._lock:
            victims = [nid for nid, p in self._procs.items()
                       if p.poll() is None]
            if not victims:
                return None
            victim = node_id if node_id in victims else victims[0]
            proc = self._procs[victim]
        logger.warning("chaos: SIGKILL replica %s", victim)
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10.0)
        return victim

    def live_count(self) -> int:
        return len(self._live_fn())

    def wait_live(self, n: int, timeout_s: float = 60.0) -> bool:
        """Wait until the MASTER sees n live replicas (registration is
        the replica's own act — the manager only owns processes)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if len(self._live_fn()) >= n:
                return True
            self._poll_evt.wait(0.05)
        return len(self._live_fn()) >= n

    def stop_all(self, timeout_s: float = 10.0) -> None:
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5.0)


def _build_engine(args):
    from dlrover_tpu.serving.prefix_cache import maybe_wrap_prefix_cache

    if args.backend == "toy":
        from dlrover_tpu.serving.engine import ToyEngine

        engine = ToyEngine(slots=args.slots, vocab=args.vocab,
                           cache_len=args.cache_len,
                           prefill_delay_s=args.prefill_delay_s,
                           step_delay_s=args.step_delay_s)
    else:
        from dlrover_tpu.serving.engine import build_tiny_engine

        engine = build_tiny_engine(
            slots=args.slots, cache_len=args.cache_len, vocab=args.vocab,
            dim=args.dim, n_layers=args.n_layers, seed=args.seed,
            quantize=args.quantize,
        )
    # prefix reuse is an engine property (the batcher consumes the
    # wrapper unchanged); the flag defaults to DLROVER_TPU_SERVE_PREFIX
    return maybe_wrap_prefix_cache(engine,
                                   enabled=args.prefix_cache or None)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser("dlrover_tpu serve replica")
    parser.add_argument("--master", required=True)
    parser.add_argument("--node-id", type=int, required=True)
    parser.add_argument("--backend", default="toy", choices=["toy", "jax"])
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--http-port", type=int, default=0,
                        help="observability endpoint (/metrics /events "
                             "/debug/bundle); 0 = ephemeral")
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--buckets", default="8,16")
    parser.add_argument("--max-new-cap", type=int, default=16)
    parser.add_argument("--cache-len", type=int, default=48)
    parser.add_argument("--vocab", type=int, default=32)
    parser.add_argument("--dim", type=int, default=16)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--hb-interval-s", type=float, default=None)
    parser.add_argument("--step-delay-s", type=float, default=0.0)
    parser.add_argument("--prefill-delay-s", type=float, default=0.0)
    # serving-performance knobs; defaults follow the env so a fleet can
    # be flipped without touching every spawn site
    parser.add_argument(
        "--quantize", action="store_true",
        default=env_flag(ConfigKey.SERVE_QUANT, False),
        help="int8 KV cache in the batched engine (jax backend)")
    parser.add_argument(
        "--prefix-cache", action="store_true",
        default=env_flag(ConfigKey.SERVE_PREFIX, False),
        help="radix prefix-cache reuse across requests")
    args = parser.parse_args(argv)
    replica = DecodeReplica(
        master_addr=args.master,
        node_id=args.node_id,
        engine=_build_engine(args),
        buckets=tuple(int(b) for b in args.buckets.split(",")),
        max_new_cap=args.max_new_cap,
        port=args.port,
        heartbeat_interval_s=args.hb_interval_s,
        http_port=args.http_port,
    )
    replica.start()
    code = replica.run()
    replica.stop()
    return code


if __name__ == "__main__":
    raise SystemExit(main())
