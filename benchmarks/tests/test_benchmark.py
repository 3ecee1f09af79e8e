"""CPU checks of the benchmark's own code. Run from the repo root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import flops, peaks, stats, trace_reduce  # noqa: E402

BENCH = bench_run.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
HERE = os.path.join(ROOT, "benchmarks")


def config_fields(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


# -- BENCHMARK.json: every name resolves to its file -------------------------


def test_names_and_units_keep_to_the_contract():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in BENCH["configs"]]
             + [w[k] for w in BENCH["workloads"]
                for k in ("name", "config", "traffic")]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for name in names:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for kind in ("end_to_end", "per_layer", "configs", "workloads"):
        seen = [x["name"] for x in BENCH[kind]]
        assert len(seen) == len(set(seen)), kind
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_resolves_to_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = set()
    for cell in BENCH["workloads"]:
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert cell["chips"] in (1, 4)
        used.add(cell["config"])
        fields = config_fields(cell["config"])
        assert os.path.exists(os.path.join(
            HERE, "families", fields["family"] + ".py"))
        traffic = bench_run.load_json("traffic", cell["traffic"] + ".json")
        assert os.path.exists(os.path.join(
            HERE, "jobs", traffic["job"] + ".py"))
    assert used == set(configs), "a configuration without a cell"
    for entry in configs.values():
        fields = config_fields(entry["name"])
        assert fields["source"] == entry["source"]
        assert fields["reduced"] == entry["reduced"]
        for key in ("assumed", "departures", "deployment", "mesh"):
            assert key in fields, key
        for key in entry["reduced"]:
            assert fields["published"][key] != fields[key]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_every_per_layer_metric_has_a_reader_and_its_arrow():
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    assert "setup_s" in end_to_end
    for m in BENCH["per_layer"]:
        reader = bench_run.load_reader(m["name"])
        assert callable(reader.read) and reader.__doc__
        moved = end_to_end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (
                f"{m['name']} moves {m['moves']}, which {cell} lacks")
    for cell in cells:
        assert len(bench_run.metrics_of(cell, BENCH["end_to_end"])) >= 2
        assert bench_run.metrics_of(cell, BENCH["per_layer"])


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = {"trace": [], "trace_raw": None, "peaks": None,
             "spans": {}, "memory": {"window_peak_bytes": [0]},
             "registry": {k: {"count": 0, "sum": 0.0} for k in (
                 "dlrover_ckpt_save_block_seconds",
                 "dlrover_ckpt_drain_seconds",
                 "dlrover_ckpt_restore_seconds{source=shm}")},
             "job": {}, "fields": {}}
    for m in BENCH["per_layer"]:
        assert bench_run.load_reader(m["name"]).read(empty) is None, m


# -- the yardstick -----------------------------------------------------------


def test_flops_against_hand_counts():
    mistral, mixtral = config_fields("mistral-7b"), config_fields(
        "mixtral-8x7b")
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024          # 41,943,040
    ffn = 3 * 4096 * 14336                             # 176,160,768
    head = 4096 * 32000                                # 131,072,000
    norms = 3 * 4096
    assert flops.param_count(mistral) == attn + ffn + 2 * head + norms
    assert flops.param_count(mistral) == 480_260_096
    assert flops.matmul_params_per_token(mistral) == attn + ffn + head
    router = 4096 * 8
    assert flops.param_count(mixtral) == (
        attn + router + 8 * ffn + 2 * head + norms)
    assert flops.param_count(mixtral) == 1_713_418_240
    # active experts only: top-2 of 8
    assert flops.matmul_params_per_token(mixtral) == (
        attn + router + 2 * ffn + head)
    # causal attention, one layer, per token: 6 score-sized matmuls of
    # 2 * head_dim * (S + 1) / 2 FLOPs a head
    per_token_attn = 6 * 32 * 2 * 128 * (4096 + 1) / 2
    assert flops.train_flops_per_token(mistral, 4096) == pytest.approx(
        6 * (attn + ffn + head) + per_token_attn)
    assert flops.train_flops_per_token(mistral, 4096) / 1e9 == pytest.approx(
        2.196, abs=2e-3)
    assert flops.train_flops_per_token(mixtral, 4096) / 1e9 == pytest.approx(
        3.253, abs=2e-3)


def test_peaks_unknown_device_is_an_error():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


def test_stats():
    values = list(range(1, 102))  # 1..101
    assert stats.percentile(values, 90) == 91
    assert stats.median(values) == 51
    assert stats.percentile([], 90) is None
    assert stats.spread([10, 10, 10, 10]) == 0


SYNTHETIC = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_step_fn(1)", 0, 400], ["jit_step_fn(1)", 500, 400]]},
        {"name": "XLA Ops", "events": [
            ["while.1", 0, 300], ["_fwd_kernel.2", 0, 100],
            ["fusion.3", 100, 150], ["all-reduce-start.1", 300, 100],
            ["while.1", 500, 300], ["_fwd_kernel.2", 500, 100],
            ["fusion.3", 600, 150], ["all-reduce-start.1", 800, 100]]}]},
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench:wait_loss", 350, 200], ["other", 0, 1000]]}]},
]}


def test_trace_reduce_on_a_hand_made_trace():
    (chip,) = trace_reduce.reduce(SYNTHETIC, step_module="step_fn")
    assert chip["window_s"] == pytest.approx(900e-9)
    assert chip["busy_s"] == pytest.approx(800e-9)   # idle 400..500
    assert chip["idle_gaps"] == [("bench:wait_loss", pytest.approx(100e-9))]
    assert chip["steps"] == 2
    assert chip["step_gap_s"] == [pytest.approx(100e-9)]
    plane = trace_reduce.device_planes(SYNTHETIC)[0]
    assert trace_reduce.kernel_seconds(plane, "_fwd_kernel", "step_fn") == (
        pytest.approx(200e-9), 2, 2)
    assert chip["collective_s"] == pytest.approx(200e-9)
    own = dict(chip["device_ops"])
    # the while's 300 ns hold 250 ns of its body's ops: 50 ns are its own
    assert own["while.1"] == pytest.approx(100e-9)
    assert own["fusion.3"] == pytest.approx(300e-9)


RECORDED = os.path.join(HERE, "harness", "recorded_trace.json.gz")
# read once from the recorded trace with this reduction and checked by
# hand against the dump of the same trace (PERF.md section 6, PR 23)
RECORDED_EXPECT = os.path.join(HERE, "harness", "recorded_trace.expect.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_trace_reduce_on_the_recorded_chip_trace():
    with open(RECORDED_EXPECT) as f:
        expect = json.load(f)
    trace = trace_reduce.load(RECORDED)
    (chip,) = trace_reduce.reduce(trace, step_module="step_fn")
    assert chip["steps"] == expect["steps"]
    assert 100 * (1 - chip["busy_s"] / chip["window_s"]) == pytest.approx(
        expect["idle_pct"], rel=1e-9)
    seconds, calls, steps = trace_reduce.kernel_seconds(
        trace_reduce.device_planes(trace)[0], expect["kernel"], "step_fn")
    assert (calls, steps) == (expect["kernel_calls"], expect["steps"])
    assert seconds == pytest.approx(expect["kernel_s"], rel=1e-9)


# -- the reference against the program, tiny widths --------------------------


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_agrees_with_the_program(config):
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import decoder

    fields = config_fields(config)
    family = importlib.import_module(
        "benchmarks.families." + fields["family"])
    fields = {**fields, **family.REHEARSAL_FIELDS, "num_hidden_layers": 2}
    seq = 64
    cfg = family.program_config(fields, seq)
    # float32 weights on both sides: what is left is the two
    # implementations' difference, not bf16 rounding
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        family.init_params(cfg, jax.random.PRNGKey(3)))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, fields["vocab_size"], size=(2, seq + 1), dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(family.loss_fn(cfg, None))(
            params, tokens)
    got_norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    want, want_norm = decoder.loss_and_grad_norm(
        params, tokens, fields, **family.reference_kwargs(fields, seq))
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert float(got_norm) == pytest.approx(float(want_norm), rel=2e-4)
    # and it is no tautology: another RoPE base moves the reference
    moved, _ = decoder.loss_and_grad_norm(
        params, tokens, {**fields, "rope_theta": 50.0},
        **family.reference_kwargs(fields, seq))
    assert abs(float(moved) - float(want)) > 1e-4 * float(want)


# -- the command -------------------------------------------------------------


def run_cell(*argv):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *argv], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)


CASES = [(w["name"], 0) for w in BENCH["workloads"]] + [
    (w["name"], 1) for w in BENCH["workloads"]
    if w["traffic"] != "train-steady"]


@pytest.mark.parametrize("cell,trace", CASES)
def test_rehearsal_ends_in_one_result_line(cell, trace):
    done = run_cell("--workload", cell, "--seed", str(2**31 + 11),
                    "--seconds", "2", "--trace", str(trace), "--rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    workload = next(w for w in BENCH["workloads"] if w["name"] == cell)
    traffic = bench_run.load_json("traffic", workload["traffic"] + ".json")
    restores = [n for n in map(json.loads, done.stdout.splitlines()[:-1])
                if n.get("note") == "restore"]
    assert len(restores) == traffic["restores_after_window"]
    assert all(n["bits_equal"] for n in restores)
    assert out["device"]["platform"] == "cpu"  # a rehearsal says so
    assert out["device"]["count"] == workload["chips"]
    assert "memory_peak_bytes" in out["device"]
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"]
               for m in bench_run.metrics_of(cell, BENCH[kind])}
    assert out["metrics"], "no metric reported"
    for name, metric in out["metrics"].items():
        assert metric["unit"] == allowed[name]
        assert isinstance(metric["value"], float)
    if not trace:
        assert set(out["metrics"]) == set(allowed)
        assert all(m["value"] > 0 for m in out["metrics"].values())
    else:
        assert {"busy_s", "window_s"} <= set(out["device"])


def test_a_cpu_is_refused_without_rehearsal():
    done = run_cell("--workload", BENCH["workloads"][0]["name"], "--seed",
                    "1", "--seconds", "1", "--trace", "0")
    assert done.returncode not in (0, None)
    assert "no TPU" in done.stderr
    assert not any(line.startswith('{"correct"')
                   for line in done.stdout.splitlines())
