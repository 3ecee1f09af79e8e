"""Keep the driver entry points green on the CPU mesh."""

import os
import subprocess
import sys

import jax


def test_entry_jittable():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2 and out.ndim == 3
    assert bool(jax.numpy.isfinite(out).all())


def test_dryrun_multichip_8():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_bench_smoke_cpu(tmp_path):
    """bench.py must print exactly one parseable JSON line."""
    import json

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_DIM": "128",
        "BENCH_LAYERS": "2",
        "BENCH_SEQ": "128",
        "BENCH_STEPS": "2",
        "BENCH_CKPT_DIM": "256",
        "BENCH_CKPT_LAYERS": "2",
        "BENCH_CKPT_DIR": str(tmp_path / "bench"),
        # the smoke asserts train+ckpt numbers; the chaos drill has its
        # own e2e (test_chaos_e2e.py) and would dominate the 300 s cap
        "BENCH_SKIP_CHAOS": "1",
        "BENCH_TIME_BUDGET_S": "240",
        # the multi-GB host-scale point is sized for bench hardware; on a
        # CI box with slow cold storage the 3 GB persist alone can eat
        # the whole cap — the smoke only asserts the main device point
        "BENCH_CKPT_SCALE_GB": "0.25",
    })
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # bench prints the full cumulative record, then the compact driver
    # digest as the LAST line — the full record is the one with "detail"
    records = [
        json.loads(ln) for ln in proc.stdout.strip().splitlines()
        if ln.startswith("{")
    ]
    result = next(r for r in reversed(records) if "detail" in r)
    assert {"metric", "value", "unit", "vs_baseline"} <= set(result)
    # headline MFU is 0 on CPU (no published peak); the sub-benches must
    # still carry real numbers
    assert result["value"] >= 0
    assert result["detail"]["train"]["tokens_per_s"] > 0
    assert result["detail"]["ckpt"]["blocking_speedup_vs_sync_disk"] > 0
