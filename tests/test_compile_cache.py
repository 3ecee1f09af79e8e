"""Persistent-compilation-cache wiring: elastic restarts must not pay
full recompilation (SURVEY.md §7 hard part b — restart-to-training time
is compile-dominated on TPU), and the caller places the cache:
``JAX_COMPILATION_CACHE_DIR`` is honoured and never overridden; unset, the
cache lives at one fixed path inside the checkout."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKLOAD = """
import sys, time
sys.path.insert(0, {repo!r})
from dlrover_tpu import worker
ctx = worker.init(initialize_jax_distributed=False)
import jax, jax.numpy as jnp

events = {{"hits": 0, "misses": 0}}
def tap(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        events["misses"] += 1
def f(x):
    for _ in range(100):
        x = jnp.sin(x @ x) + jnp.cos(x).T @ x
    return x
x = jnp.ones((96, 96)).block_until_ready()
# from here on only jit(f) compiles: every count below is its own
jax.monitoring.register_event_listener(tap)
jax.jit(f)(x).block_until_ready()
print("HITS", events["hits"])
print("MISSES", events["misses"])
print("ENABLED", int(jax.config.jax_enable_compilation_cache))
print("DIR", jax.config.jax_compilation_cache_dir)
"""


def _run(tmp_path, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("JAX_COMPILATION_CACHE_DIR", "DLROVER_TPU_COMPILE_CACHE"):
        env.pop(name, None)
    env.update(env_overrides)
    proc = subprocess.run(
        [sys.executable, "-c", _WORKLOAD.format(repo=REPO)],
        env=env, capture_output=True, text=True, timeout=180,
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = dict(
        line.split(" ", 1) for line in proc.stdout.splitlines()
        if line.split(" ", 1)[0] in ("HITS", "MISSES", "ENABLED", "DIR")
    )
    assert len(out) == 4, proc.stdout
    return out


def _entries(cache_dir):
    return [f for f in os.listdir(cache_dir) if f.endswith("-cache")]


def test_env_dir_is_honoured_and_a_restarted_worker_hits_it(tmp_path):
    cache = tmp_path / "placed_by_the_caller"
    cold = _run(tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    # jax read the variable itself; worker.init() did not move the cache
    assert cold["DIR"] == str(cache)
    assert _entries(cache), "first process should have populated the cache"
    assert (int(cold["HITS"]), int(cold["MISSES"])) == (0, 1)
    warm = _run(tmp_path, JAX_COMPILATION_CACHE_DIR=str(cache))
    # the restarted process deserializes the executable instead of
    # recompiling (no wall-time assertion: on a loaded CI box trace time
    # noise swamps the saved compile; the counts are the proof)
    assert (int(warm["HITS"]), int(warm["MISSES"])) == (1, 0), warm


def test_default_dir_is_fixed_and_inside_the_checkout(tmp_path):
    from dlrover_tpu.worker import default_compile_cache_dir

    expected = os.path.join(REPO, ".xla_cache")
    assert default_compile_cache_dir() == expected
    # run from another cwd, with another HOME: the path must not follow
    out = _run(tmp_path, HOME=str(tmp_path))
    assert out["DIR"] == expected
    assert _entries(expected)
    assert not os.path.exists(tmp_path / ".cache")


def test_cache_opt_out(tmp_path):
    cache = tmp_path / "never_written"
    out = _run(tmp_path, DLROVER_TPU_COMPILE_CACHE="off",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    assert out["ENABLED"] == "0"
    assert (int(out["HITS"]), int(out["MISSES"])) == (0, 0)
    assert not os.path.exists(cache) or not _entries(cache)
