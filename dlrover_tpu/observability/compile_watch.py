"""Compilation watch: compile timing, cache hit/miss, recompile storms.

XLA recompiles are the device plane's silent tax: a jit'd function fed a
new abstract signature (a different batch width, a ragged bucket, a new
dtype) retraces and recompiles, stealing seconds per occurrence with no
exception and no log line. At fleet scale, compile pathologies rank with
memory pressure among unexplained slowdowns. This module rides the
jit/lower/compile paths the trainer (trainer/elastic.py `_build_step`)
and serving engine (serving/engine.py `_note_shape`) already own:

- every first-seen abstract input signature is counted per function
  (``dlrover_compile_total{fn}``), re-uses as hits: a *shadow* of the
  jit cache by the dimensions the call site names, kept for storm
  attribution. It cannot see a retrace those dimensions do not explain
  (the same batch under a new state layout)
- what the backend is really asked to compile is counted and timed where
  jax reports it: one ``jax.monitoring`` listener for
  ``/jax/core/compile/backend_compile_duration``, registered once for
  the process, feeds ``dlrover_compile_requests_total`` (one a request,
  served by the persistent cache or not) and ``dlrover_compile_seconds``
- a sliding window per function detects *storms* — ≥N distinct
  signatures inside the window — and attributes the storm to the
  varying dimension (the dim whose distinct-value count is largest,
  mapped onto the bounded ``MetricLabel.STORM_DIMS`` vocabulary, e.g.
  ragged batch width → ``batch``), journaling
  ``recompile_storm{dim, count, window_s, fn}`` once per episode.

Signatures are structured, not opaque: callers pass the dimensions that
feed tracing (``note("prefill", batch=rows, seq_len=bucket)``), which is
what makes attribution possible — an opaque hash could count storms but
never explain them.
"""

import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from dlrover_tpu.analysis.race_detector import shared
from dlrover_tpu.common.constants import ConfigKey, MetricLabel, env_int
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability.journal import JournalEvent

# ≥ this many distinct signatures for one fn inside the window = storm
# (ConfigKey.COMPILE_STORM_N overrides)
DEFAULT_STORM_THRESHOLD = 6
DEFAULT_STORM_WINDOW_S = 120.0
# distinct-signature history kept per fn (forensics, not detection)
SIG_HISTORY = 256

# signature dimension name -> bounded storm-dim label. Unlisted dims
# (and multi-way ties) fall to "unknown" rather than minting new label
# values — the STORM_DIMS vocabulary is the DLR013 contract.
_DIM_LABELS = {
    "batch": MetricLabel.STORM_DIM_BATCH,
    "rows": MetricLabel.STORM_DIM_BATCH,
    "slots": MetricLabel.STORM_DIM_BATCH,
    "seq_len": MetricLabel.STORM_DIM_SEQ_LEN,
    "bucket": MetricLabel.STORM_DIM_SEQ_LEN,
    "bucket_len": MetricLabel.STORM_DIM_SEQ_LEN,
    "cache_len": MetricLabel.STORM_DIM_SEQ_LEN,
    "prefix_len": MetricLabel.STORM_DIM_SEQ_LEN,
    "dtype": MetricLabel.STORM_DIM_DTYPE,
    "fn": MetricLabel.STORM_DIM_FN,
}


def _storm_threshold() -> int:
    return env_int(ConfigKey.COMPILE_STORM_N, DEFAULT_STORM_THRESHOLD)


# jax's duration event round one request to the backend's compiler,
# whether the persistent cache serves it or not
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatcher:
    """Process-wide compile ledger. Thread-safe: serving threads note
    shapes concurrently with the trainer's retrace (the signature maps
    are ``shared(...)``-registered for the race certification)."""

    def __init__(
        self,
        journal=None,
        registry=None,
        source: str = "worker",
        storm_threshold: Optional[int] = None,
        window_s: float = DEFAULT_STORM_WINDOW_S,
        monotonic: Callable[[], float] = time.monotonic,
    ):
        self._journal = journal
        self._source = source
        self._monotonic = monotonic
        self._threshold = storm_threshold or _storm_threshold()
        self._window_s = window_s
        self._lock = threading.Lock()
        # fn -> set of signature tuples ever seen (the compile cache's
        # shadow: membership = hit)
        self._sigs: Dict[str, set] = shared({}, "compile.watch.sigs")
        # fn -> deque of (first-seen t, sig dims dict) inside-ish window
        self._recent: Dict[str, deque] = {}
        # fn -> storm episode open (re-armed when the window drains)
        self._storm_open: Dict[str, bool] = {}
        self._storm_log: List[Dict[str, Any]] = []
        if registry is None:
            from dlrover_tpu.observability.registry import get_registry

            registry = get_registry()
        self._c_compiles = registry.counter(
            "dlrover_compile_total",
            "Compiles (first-seen abstract signatures) per function",
            labelnames=("fn",),
        )
        self._c_hits = registry.counter(
            "dlrover_compile_cache_hits_total",
            "Signature re-uses (no retrace) per function",
            labelnames=("fn",),
        )
        self._c_requests = registry.counter(
            "dlrover_compile_requests_total",
            "Compilation requests to the backend, served by the "
            "persistent cache or not",
        )
        self._h_seconds = registry.histogram(
            "dlrover_compile_seconds",
            "Backend time of each compilation request (jax's "
            "backend_compile_duration event)",
        )
        self._g_distinct = registry.gauge(
            "dlrover_compile_distinct_signatures",
            "Distinct abstract signatures seen per function since start",
            labelnames=("fn",),
        )
        self._c_storms = registry.counter(
            "dlrover_compile_storms_total",
            "Recompile-storm episodes journaled, by attributed dimension",
            labelnames=("dim",),
        )

    # -- recording ---------------------------------------------------------

    def note(self, fn: str, **dims: Any) -> bool:
        """Record one invocation of jit'd function ``fn`` with the
        dimensions that feed its abstract signature. Returns True when
        the signature is first-seen (a compile / cache miss)."""
        sig = tuple(sorted(dims.items()))
        now = self._monotonic()
        with self._lock:
            seen = self._sigs.setdefault(fn, set())
            if sig in seen:
                self._c_hits.labels(fn=fn).inc()
                return False
            seen.add(sig)
            self._c_compiles.labels(fn=fn).inc()
            self._g_distinct.labels(fn=fn).set(float(len(seen)))
            recent = self._recent.setdefault(fn, deque(maxlen=SIG_HISTORY))
            recent.append((now, dict(dims)))
            storm = self._detect_storm_locked(fn, now)
        if storm is not None:
            self._emit_storm(storm)
        return True

    def compile_requested(self, seconds: float) -> None:
        """One request to the backend's compiler took ``seconds``."""
        self._c_requests.inc()
        self._h_seconds.observe(seconds)

    def compile_requests(self) -> int:
        """Requests to the backend's compiler so far in this process."""
        return int(self._c_requests.value)

    # -- storm detection ---------------------------------------------------

    def _detect_storm_locked(self, fn: str,
                             now: float) -> Optional[Dict[str, Any]]:
        recent = self._recent[fn]
        in_window = [(t, d) for t, d in recent
                     if now - t <= self._window_s]
        if len(in_window) < self._threshold:
            # window drained below half the threshold: episode closes
            if (self._storm_open.get(fn)
                    and len(in_window) <= self._threshold // 2):
                self._storm_open[fn] = False
            return None
        if self._storm_open.get(fn):
            return None  # one journal event per episode, not per compile
        self._storm_open[fn] = True
        dim = self._attribute_locked(in_window)
        storm = {
            "fn": fn,
            "dim": dim,
            "count": len(in_window),
            "window_s": self._window_s,
        }
        self._storm_log.append(dict(storm, t=round(now, 3)))
        return storm

    @staticmethod
    def _attribute_locked(in_window: List[Tuple[float, Dict[str, Any]]]
                          ) -> str:
        """The varying dimension: the signature dim with the most
        distinct values across the window's compiles, mapped onto the
        bounded STORM_DIMS vocabulary."""
        distinct: Dict[str, set] = {}
        for _t, dims in in_window:
            for key, val in dims.items():
                distinct.setdefault(key, set()).add(val)
        best_key, best_n = None, 1
        for key in sorted(distinct):
            n = len(distinct[key])
            if n > best_n:
                best_key, best_n = key, n
        if best_key is None:
            return MetricLabel.STORM_DIM_UNKNOWN
        return _DIM_LABELS.get(best_key, MetricLabel.STORM_DIM_UNKNOWN)

    def _emit_storm(self, storm: Dict[str, Any]) -> None:
        self._c_storms.labels(dim=storm["dim"]).inc()
        logger.warning("recompile storm: %s", storm)
        if self._journal is not None:
            self._journal.record(JournalEvent.RECOMPILE_STORM,
                                 source=self._source, **storm)

    # -- consumers ---------------------------------------------------------

    def compile_count(self, fn: Optional[str] = None) -> int:
        with self._lock:
            if fn is not None:
                return len(self._sigs.get(fn, ()))
            return sum(len(s) for s in self._sigs.values())

    def storms(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(s) for s in self._storm_log]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "distinct_signatures": {fn: len(s)
                                        for fn, s in self._sigs.items()},
                "storms": [dict(s) for s in self._storm_log],
                "threshold": self._threshold,
                "window_s": self._window_s,
            }


_default_watcher: Optional[CompileWatcher] = None
_default_lock = threading.Lock()
_listening = False


def _on_jax_duration(event: str, duration_secs: float, **_: Any) -> None:
    if event == BACKEND_COMPILE_EVENT:
        get_watcher().compile_requested(duration_secs)


def _listen_locked() -> None:
    """Register the one listener that forwards jax's compile events to
    whichever watcher is the process's at the time (jax keeps listeners
    for the life of the process and offers no way to take one back).
    Never the one to import jax: a process that has not imported it
    compiles nothing, and a replica serving a toy engine must not pay
    seconds of import inside its first request. Asked again at every
    ``get_watcher()`` until jax is there."""
    global _listening
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if _listening or monitoring is None:
        return
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _listening = True


def get_watcher() -> CompileWatcher:
    """The process-wide watcher jit call sites note into. Created lazily
    (journal-less) so a bare engine still counts; ``set_watcher`` swaps
    in a journal-wired one at bootstrap."""
    global _default_watcher
    with _default_lock:
        if _default_watcher is None:
            _default_watcher = CompileWatcher()
        _listen_locked()
        return _default_watcher


def set_watcher(watcher: CompileWatcher) -> CompileWatcher:
    global _default_watcher
    with _default_lock:
        _default_watcher = watcher
        _listen_locked()
    return watcher


def reset_watcher() -> None:
    """Test hook: drop the process watcher (pairs with reset_registry)."""
    global _default_watcher
    with _default_lock:
        _default_watcher = None
