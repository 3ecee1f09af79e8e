"""Warm worker spawn pool: pre-imported interpreters for fast restarts.

Elastic recovery latency = detect + stop + re-rendezvous + SPAWN + init +
restore + (cached) recompile. After the persistent compilation cache
(worker.py) removed the recompile term, the largest remaining fixed cost
of a worker restart is interpreter start + importing numpy/jax — seconds
per incarnation, and load-dependent (it was the dominant variance in the
chaos drill's recovery times). The reference doesn't have this problem
shape: its torch workers are forked by torchelastic from an already-warm
parent (elastic_agent/torch/training.py ``_initialize_workers``:856 via
torch ``start_processes``). A JAX worker can't be forked from the agent
(the agent must never initialize a backend), so the TPU-native equivalent
is a pool of PRE-SPAWNED child interpreters that:

1. inherit the job-static environment and pre-import the heavy modules
   (``numpy``, ``jax`` — importing jax does NOT initialize a backend, so
   per-incarnation device/distributed config still applies later);
2. block reading one JSON line from stdin;
3. on release, merge the per-incarnation env (RANK, WORLD_SIZE,
   COORDINATOR_ADDR, RDZV_ROUND, ...) into ``os.environ``, set
   ``sys.argv``, and ``runpy.run_path(script, run_name="__main__")`` —
   semantically the same as ``python script.py args...``.

If the agent dies, the stdin pipe closes and every warm spare exits on
EOF — no orphan interpreters. A pool failure falls back to a cold
``subprocess.Popen`` so warm spawn is strictly an optimization.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from typing import Dict, List, Optional, Sequence

from dlrover_tpu.agent.config import with_package_on_pythonpath
from dlrover_tpu.common.log import logger

# what a warm spare imports before parking on stdin. jax pulls numpy; the
# worker-side framework modules are cheap but save another ~100ms
_DEFAULT_PREIMPORTS = "numpy,jax,dlrover_tpu.worker"

_BOOTSTRAP = r"""
import json, os, runpy, sys
_failed = []
for _m in sys.argv[1].split(","):
    if _m:
        try:
            __import__(_m)
        except Exception as _e:
            _failed.append("%s: %r" % (_m, _e))
if len(sys.argv) > 2 and sys.argv[2]:
    try:  # imports done: tell the pool this spare is ready; a non-empty
        # marker records WHICH pre-imports failed (the spare still works —
        # the worker script imports for real — but delivers no warm-up)
        with open(sys.argv[2], "w") as _f:
            _f.write("; ".join(_failed))
    except OSError:
        pass
_line = sys.stdin.readline()
if not _line:
    sys.exit(0)  # agent gone / pool stopped: retire quietly
_cfg = json.loads(_line)
os.environ.update(_cfg["env"])
# env-var updates don't reach the live interpreter's sys.path — mirror
# PYTHONPATH so the worker script resolves the same packages a cold
# `python script.py` would
for _p in os.environ.get("PYTHONPATH", "").split(os.pathsep):
    if _p and _p not in sys.path:
        sys.path.insert(0, _p)
# `python script.py` puts the SCRIPT's directory at sys.path[0] (so the
# script can import sibling modules); runpy.run_path does not — replicate
sys.path.insert(0, os.path.dirname(os.path.abspath(_cfg["script"])))
sys.argv = [_cfg["script"]] + list(_cfg.get("args", []))
runpy.run_path(_cfg["script"], run_name="__main__")
"""


class WarmWorkerPool:
    """Keeps ``size`` pre-imported interpreters ready to become workers."""

    def __init__(self, size: int, base_env: Optional[Dict[str, str]] = None,
                 preimports: Optional[str] = None):
        self._size = max(1, size)
        # spares must resolve the same dlrover_tpu the agent runs
        self._base_env = with_package_on_pythonpath(
            dict(base_env if base_env is not None else os.environ)
        )
        self._preimports = (
            preimports
            if preimports is not None
            else os.getenv("DLROVER_TPU_WARM_PREIMPORT", _DEFAULT_PREIMPORTS)
        )
        self._spares: List[subprocess.Popen] = []
        self._ready_files: Dict[int, str] = {}  # pid -> marker path
        self._ready_dir = tempfile.mkdtemp(prefix="dtpu_warm_")
        self._lock = threading.Lock()
        self._stopped = False
        self._warned_unwarmed: set = set()

    def _spawn_spare(self) -> Optional[subprocess.Popen]:
        marker = os.path.join(self._ready_dir, uuid.uuid4().hex)
        try:
            proc = subprocess.Popen(  # noqa: S603
                [sys.executable, "-c", _BOOTSTRAP, self._preimports, marker],
                env=self._base_env, stdin=subprocess.PIPE,
            )
        except OSError as e:
            logger.warning("warm spawn pool: spare spawn failed: %r", e)
            return None
        self._ready_files[proc.pid] = marker
        return proc

    def _is_ready(self, proc: subprocess.Popen) -> bool:
        marker = self._ready_files.get(proc.pid)
        return bool(marker) and os.path.exists(marker)

    def ready_count(self) -> int:
        with self._lock:
            return sum(
                1 for p in self._spares
                if p.poll() is None and self._is_ready(p)
            )

    def wait_ready(self, n: int = 1, timeout_s: float = 10.0) -> bool:
        """Block until ``n`` spares finished their imports (bounded).

        The agent gates its FIRST rendezvous join on this: a node joining
        a running job triggers a stop-the-world re-rendezvous for every
        peer, so joining before this host can actually spawn fast converts
        the joiner's import time into global downtime. Waiting here, the
        peers keep training until the cutover is cheap."""
        n = min(n, self._size)
        t0 = time.time()
        deadline = t0 + timeout_s
        ok = False
        while time.time() < deadline:
            with self._lock:
                alive = sum(1 for p in self._spares if p.poll() is None)
            # never wait for more spares than actually exist — a pool
            # that failed to (fully) populate (fork OSError under load)
            # must fall through to cold spawns immediately, not burn the
            # whole gate timeout
            target = min(n, alive)
            if self._stopped or self.ready_count() >= target:
                ok = True
                break
            time.sleep(0.05)
        ok = ok or self.ready_count() >= n
        logger.info(
            "warm spawn pool: %s/%s spares ready after %.1fs%s",
            self.ready_count(), n, time.time() - t0,
            "" if ok else " (timeout — spawning cold)",
        )
        self._log_unwarmed()
        return ok

    def _log_unwarmed(self) -> None:
        """Surface spares whose ready marker records pre-import failures:
        they pass the rendezvous gate but deliver zero warm-up benefit
        (broken env, typo in DLROVER_TPU_WARM_PREIMPORT)."""
        with self._lock:
            markers = dict(self._ready_files)
        for pid, marker in markers.items():
            try:
                with open(marker) as f:
                    failures = f.read().strip()
            except OSError:
                continue
            if failures and pid not in self._warned_unwarmed:
                self._warned_unwarmed.add(pid)
                logger.warning(
                    "warm spawn pool: spare pid=%s is ready but UNWARMED — "
                    "pre-imports failed: %s", pid, failures,
                )

    def prewarm(self) -> None:
        with self._lock:
            if self._stopped:
                return
            self._spares = [p for p in self._spares if p.poll() is None]
            while len(self._spares) < self._size:
                spare = self._spawn_spare()
                if spare is None:
                    return
                self._spares.append(spare)

    def take(self, env: Dict[str, str], script: str,
             args: Sequence[str]) -> Optional[subprocess.Popen]:
        """Release a warm spare into ``script`` with ``env``; returns the
        (now-working) process, or None if no healthy spare is available
        (caller spawns cold). A replacement spare is warmed immediately."""
        with self._lock:
            if self._stopped:
                return None
            alive = []
            for cand in self._spares:
                if cand.poll() is None:
                    alive.append(cand)
                else:
                    logger.warning(
                        "warm spawn pool: spare pid=%s died before use "
                        "(rc=%s)", cand.pid, cand.returncode,
                    )
                    self._ready_files.pop(cand.pid, None)
            # prefer a spare whose imports already finished; else take the
            # oldest still-importing one (still beats a cold start)
            spare = next(
                (p for p in alive if self._is_ready(p)),
                alive[0] if alive else None,
            )
            if spare is None:
                self._spares = []
                return None
            alive.remove(spare)
            self._spares = alive
        try:
            line = json.dumps({
                "env": env, "script": script, "args": list(args),
            })
            spare.stdin.write((line + "\n").encode())
            spare.stdin.flush()
            spare.stdin.close()
        except (OSError, ValueError) as e:
            logger.warning("warm spawn pool: release failed: %r", e)
            spare.kill()
            try:  # reap: an unwaited kill leaves a zombie until agent exit
                spare.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
            return None
        finally:
            self._cleanup_marker(spare)
            self.prewarm()
        return spare

    def _cleanup_marker(self, proc: subprocess.Popen) -> None:
        marker = self._ready_files.pop(proc.pid, None)
        if marker:
            try:
                os.unlink(marker)
            except OSError:
                pass

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            spares, self._spares = self._spares, []
        for p in spares:
            try:
                p.stdin.close()  # EOF: the spare exits on its own
            except (OSError, ValueError):
                pass
            try:
                p.wait(timeout=2)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(self._ready_dir, ignore_errors=True)
