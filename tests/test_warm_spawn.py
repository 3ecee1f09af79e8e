"""Warm spawn pool: pre-imported spares become workers with the right
env/argv; death/fallback paths stay safe (agent/warm_spawn.py)."""

import json
import os
import subprocess
import sys
import time

from dlrover_tpu.agent.warm_spawn import WarmWorkerPool


def _wait_file(path, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            return True
        time.sleep(0.05)
    return False


def test_take_runs_script_with_env_and_argv(tmp_path):
    out = tmp_path / "out.json"
    script = tmp_path / "w.py"
    script.write_text(
        "import json, os, sys\n"
        f"json.dump({{'rank': os.environ.get('TRANK'),"
        f" 'argv': sys.argv[1:], 'name': __name__}},"
        f" open({str(out)!r}, 'w'))\n"
    )
    pool = WarmWorkerPool(size=1, preimports="json")
    try:
        pool.prewarm()
        proc = pool.take({"TRANK": "7"}, str(script), ["--a", "b"])
        assert proc is not None
        assert proc.wait(timeout=30) == 0
        got = json.loads(out.read_text())
        # per-incarnation env merged, argv set, and the script ran as
        # __main__ — indistinguishable from `python w.py --a b`
        assert got == {"rank": "7", "argv": ["--a", "b"],
                       "name": "__main__"}
    finally:
        pool.stop()


def test_replacement_warmed_after_take(tmp_path):
    script = tmp_path / "w.py"
    script.write_text("pass\n")
    pool = WarmWorkerPool(size=1, preimports="")
    try:
        pool.prewarm()
        first = pool.take({}, str(script), [])
        assert first is not None and first.wait(timeout=30) == 0
        # the pool re-warmed a spare, so a second take also succeeds
        second = pool.take({}, str(script), [])
        assert second is not None and second.wait(timeout=30) == 0
        assert second.pid != first.pid
    finally:
        pool.stop()


def test_dead_spare_is_skipped(tmp_path):
    script = tmp_path / "w.py"
    script.write_text("pass\n")
    pool = WarmWorkerPool(size=1, preimports="")
    try:
        pool.prewarm()
        pool._spares[0].kill()
        pool._spares[0].wait()
        # take() skips the corpse; with no healthy spare it returns None
        # (the agent then spawns cold) OR a fresh spare if prewarm won the
        # race — both are healthy outcomes
        proc = pool.take({}, str(script), [])
        if proc is not None:
            assert proc.wait(timeout=30) == 0
    finally:
        pool.stop()


def test_spares_exit_on_pool_stop():
    pool = WarmWorkerPool(size=2, preimports="")
    pool.prewarm()
    spares = list(pool._spares)
    assert len(spares) == 2
    pool.stop()
    for p in spares:
        assert p.poll() is not None  # EOF on stdin retired them


def test_worker_sees_preimported_module(tmp_path):
    """The spare pre-imports modules into sys.modules; the released worker
    script finds them already loaded (the whole point of the pool)."""
    out = tmp_path / "mods.txt"
    script = tmp_path / "w.py"
    script.write_text(
        "import sys\n"
        f"open({str(out)!r}, 'w').write("
        "str('numpy' in sys.modules))\n"
    )
    pool = WarmWorkerPool(size=1, preimports="numpy")
    try:
        pool.prewarm()
        proc = pool.take({}, str(script), [])
        assert proc is not None
        assert proc.wait(timeout=60) == 0
        assert out.read_text() == "True"
    finally:
        pool.stop()


def test_worker_can_import_sibling_module(tmp_path):
    """`python script.py` puts the script's directory at sys.path[0]; the
    bootstrap must replicate that or any training script importing a
    sibling (model.py, data.py) crashes only when warm-spawned."""
    out = tmp_path / "out.txt"
    (tmp_path / "sibmod.py").write_text("VALUE = 42\n")
    script = tmp_path / "w.py"
    script.write_text(
        "import sibmod\n"
        f"open({str(out)!r}, 'w').write(str(sibmod.VALUE))\n"
    )
    pool = WarmWorkerPool(size=1, preimports="")
    try:
        pool.prewarm()
        proc = pool.take({}, str(script), [])
        assert proc is not None
        assert proc.wait(timeout=30) == 0
        assert out.read_text() == "42"
    finally:
        pool.stop()


def test_agent_restart_uses_warm_spawn(tmp_path):
    """e2e through dtpu-run: with warm spawn on (default), a crash-restart
    cycle works and the recovered worker completes — the pool is on the
    real spawn path, not an island."""
    out = tmp_path / "steps.txt"
    script = tmp_path / "train.py"
    script.write_text(
        "import sys\n"
        # before ANY import of our own: jax in sys.modules here proves the
        # interpreter came from the warm pool (a cold `python train.py`
        # starts jax-free)
        "warm = 'jax' in sys.modules\n"
        "import os\n"
        "from dlrover_tpu import worker\n"
        "ctx = worker.init()\n"
        f"path = {str(out)!r}\n"
        "n = sum(1 for _ in open(path)) if os.path.exists(path) else 0\n"
        "with open(path, 'a') as f:\n"
        "    f.write('run warm=%s\\n' % warm)\n"
        "if n == 0:\n"
        "    sys.exit(3)  # first incarnation crashes -> agent restarts\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable, "-m", "dlrover_tpu.agent.run", "--standalone",
            "--nproc_per_node", "1", "--max_restarts", "2",
            "--monitor_interval", "0.1", str(script),
        ],
        env=env, cwd=repo, capture_output=True, text=True, timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    content = out.read_text()
    assert content.count("run") == 2
    # both incarnations actually came from the pool — if take() silently
    # fell back to cold spawns this would read warm=False and the test
    # would be exercising nothing
    assert content.count("warm=True") == 2, content
