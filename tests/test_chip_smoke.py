"""``chip_smoke.py`` at its rehearsal size, through the real agent.

The chip run itself is the builder's and the driver's (``python
chip_smoke.py`` on the machine with the chip). Tier-1 keeps its control
flow honest on the CPU: node check in a child, train, flash-checkpoint to
shm, SIGKILL, restart, restore, compile-cache hit, clean exit — and the
exit codes when a phase fails or no TPU is there.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, timeout=600, **env):
    return subprocess.run(
        [sys.executable, SMOKE, *args], cwd=REPO, timeout=timeout,
        capture_output=True, text=True, env=dict(os.environ, **env),
    )


def test_rehearsal_trains_saves_is_killed_and_resumes(tmp_path):
    cache = tmp_path / "xla_cache"
    proc = _smoke("--rehearsal", JAX_COMPILATION_CACHE_DIR=str(cache))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = proc.stdout
    assert "agent exit code 0 with --network-check" in out
    assert "the agent spent one restart" in out
    assert "restore from shm at step 2: digests equal" in out
    assert "overlapping loss equal" in out
    # the agent handed the caller's cache directory to workers and spares:
    # the second incarnation found the first one's entry there
    assert (f"compile cache hit on the second incarnation's first step "
            f"(1 hit, 0 miss, no new files in {cache})") in out
    assert [f for f in os.listdir(cache) if f.endswith("-cache")]
    last = out.strip().splitlines()[-1]
    assert "tpu" not in last.lower()
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


def test_a_wrong_digest_fails_the_run():
    proc = _smoke("--rehearsal", "--fail", "digest")
    assert proc.returncode != 0
    assert "restored digests differ from the saved ones" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_at_once_without_a_tpu():
    # tier-1's environment has JAX_PLATFORMS=cpu: no chip, no rehearsal
    proc = _smoke(timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert "phase" not in proc.stdout  # nothing ran past the device check


_NODE_CHECK = """
import sys
sys.path.insert(0, {repo!r})
from dlrover_tpu.agent.config import ElasticLaunchConfig
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.diagnosis.node_check_agent import run_node_check
from dlrover_tpu.master.master import LocalJobMaster

master = LocalJobMaster(job_name={job!r}, node_num=1)
master.prepare()
try:
    config = ElasticLaunchConfig(
        job_name={job!r}, master_addr=master.addr,
        worker_env={{"JAX_PLATFORMS": "cpu"}},
    )
    ok = run_node_check(config, MasterClient(master.addr, 0, 0))
finally:
    master.stop()
assert ok, "a healthy node failed its check"
if "jax" in sys.modules:
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized(), (
        "run_node_check initialized a JAX backend in the agent process: "
        "on a TPU host that process now owns the chip and no worker can")
print("NO_BACKEND_IN_CALLER")
"""


def test_node_check_leaves_the_agent_without_a_jax_backend():
    """The agent runs ``run_node_check`` in its own process before it
    forks workers. A chip belongs to one process at a time, so the
    device workload has to run, and end, in a child."""
    proc = subprocess.run(
        [sys.executable, "-c",
         _NODE_CHECK.format(repo=REPO, job=f"nodecheck{os.getpid()}")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "NO_BACKEND_IN_CALLER" in proc.stdout


def test_a_hung_device_check_is_reported_while_partners_still_wait(
        monkeypatch):
    """A wedged runtime never returns from backend init. The child is
    cut, the round fails, and the fault reaches the master before the
    other nodes have given up waiting for the verdict."""
    from dlrover_tpu.diagnosis import node_check, node_check_agent

    assert (node_check.DEVICE_CHECK_TIMEOUT_S
            < node_check_agent._VERDICT_WAIT_S)
    monkeypatch.setattr(node_check, "DEVICE_CHECK_TIMEOUT_S", 0.01)
    with pytest.raises(subprocess.TimeoutExpired):
        node_check.matmul_benchmark_in_child(env=dict(os.environ))
