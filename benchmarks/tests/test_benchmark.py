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
from benchmarks.harness import (  # noqa: E402
    flops, named_kernels, peaks, stats, trace_reduce)

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


def family_of(fields):
    return bench_run.load_family(fields["family"])


def test_flops_against_hand_counts():
    mistral, mixtral = config_fields("mistral-7b"), config_fields(
        "mixtral-8x7b")
    dense, sparse = family_of(mistral), family_of(mixtral)
    # what jobs/train.py and the readers ask: the family, to the digit
    assert dense.param_count(mistral) == 480_260_096
    assert sparse.param_count(mixtral) == 1_713_418_240
    assert dense.train_flops_per_token(mistral, 4096) == 2_195_742_720
    assert sparse.train_flops_per_token(mixtral, 4096) == 3_252_903_936
    score = 68_736_253_952  # one causal score-sized matmul, 32 heads
    assert dense.flash_attention_flops(mistral, 4096, 1) == (
        2 * score, 5 * score)
    assert sparse.flash_attention_flops(mixtral, 4096, 1) == (
        2 * score, 5 * score)
    # rows and layers scale both, and nothing else does
    assert dense.flash_attention_flops(
        {**mistral, "num_hidden_layers": 3}, 4096, 2) == (
            12 * score, 30 * score)
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


def test_a_family_without_a_name_of_the_contract_is_refused(monkeypatch):
    import types

    assert len(bench_run.FAMILY_CONTRACT) == 9
    whole = family_of(config_fields("mistral-7b"))
    for lacking in bench_run.FAMILY_CONTRACT:
        broken = types.ModuleType("benchmarks.families.broken")
        for name in bench_run.FAMILY_CONTRACT:
            if name != lacking:
                setattr(broken, name, getattr(whole, name))
        monkeypatch.setitem(sys.modules, broken.__name__, broken)
        with pytest.raises(bench_run.FamilyContractError,
                           match=rf"lacks {lacking}:"):
            bench_run.load_family("broken")
    # a fault inside a family's import is no refusal: it keeps its traceback

    def renamed_private(name):
        raise AttributeError("module 'dlrover_tpu.models' has no '_x'")

    monkeypatch.setattr(bench_run.importlib, "import_module", renamed_private)
    with pytest.raises(AttributeError, match="has no '_x'"):
        bench_run.load_family("broken")


# a step program of two microbatches: two forward calls, one dq and one
# dkv each; the second program is cut by the profile's edge
PALLAS_CALL = ' = bf16[] custom-call(), custom_call_target="tpu_custom_call"'
KERNELS = {"planes": [{"name": "/device:TPU:0", "lines": [
    {"name": "XLA Modules", "events": [
        ["jit_step_fn(1)", 0, 1000], ["jit_step_fn(1)", 1000, 1000]]},
    {"name": "XLA Ops", "events": [
        ["%flash_fwd.1" + PALLAS_CALL, 0, 100],
        ["%flash_fwd.2" + PALLAS_CALL, 100, 100],
        ["%flash_bwd_dq.1" + PALLAS_CALL, 300, 150],
        ["%flash_bwd_dkv.1" + PALLAS_CALL, 450, 250],
        ["%flash_bwd_dq.2" + PALLAS_CALL, 700, 150],
        ["%flash_bwd_dkv.2" + PALLAS_CALL, 850, 150],
        ["%fusion.9 = bf16[] fusion(%flash_fwd.1)", 200, 100],
        ["%flash_fwd.1" + PALLAS_CALL, 1000, 100]]}]}]}


def test_flash_rooflines_divide_by_what_the_family_counted():
    """The three readers take the least FLOPs from the job, where
    ``jobs/train.py`` put the family's count: twice the FLOPs (a stack
    run twice) is twice the share; a family that counts none has no
    roofline, never 0 %."""
    job = {"grad_accum": 2, "flash_fwd_flops": 10.0, "flash_bwd_flops": 35.0,
           "rows_per_replica": 1, "seq": 128}
    ctx = {"trace_raw": KERNELS, "peaks": {"bf16_flops_per_s": 1e9},
           "step_module": "step_fn", "job": job,
           "fields": {"num_attention_heads": 2}}
    read = {n: bench_run.load_reader(n).read for n in (
        "flash_fwd_roofline", "flash_bwd_roofline", "flash_attn_roofline")}
    # one whole step: least = 1 step x 2 microbatches x FLOPs / 1e9 /s
    assert read["flash_fwd_roofline"](ctx) == pytest.approx(
        100 * (2 * 10.0 / 1e9) / 200e-9)
    assert read["flash_bwd_roofline"](ctx) == pytest.approx(
        100 * (2 * 35.0 / 1e9) / 700e-9)
    # every Pallas call by the same rule: the cut program is no step,
    # its one call no time (until PR 36 it was counted with both)
    assert read["flash_attn_roofline"](ctx) == pytest.approx(
        100 * (2 * 45.0 / 1e9) / 900e-9)
    twice = {**ctx, "job": {**job, "flash_fwd_flops": 20.0,
                            "flash_bwd_flops": 70.0}}
    for name, reader in read.items():
        assert reader(twice) == pytest.approx(2 * reader(ctx)), name
    none = {**ctx, "job": {**job, "flash_fwd_flops": 0, "flash_bwd_flops": 0}}
    assert all(reader(none) is None for reader in read.values())


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
            ["while.1", 0, 300], ["%_fwd_kernel.2" + PALLAS_CALL, 0, 100],
            ["fusion.3", 100, 150], ["all-reduce-start.1", 300, 100],
            ["while.1", 500, 300], ["%_fwd_kernel.2" + PALLAS_CALL, 500, 100],
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
    assert named_kernels.kernel_seconds(
        plane, ("_fwd_kernel.",), "step_fn") == (pytest.approx(200e-9), 1, 2)
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
    assert expect["kernel"] == named_kernels.PALLAS
    plane = trace_reduce.device_planes(trace)[0]
    seconds, calls, steps = named_kernels.kernel_seconds(
        plane, named_kernels.ANY_KERNEL, "step_fn")
    assert (calls * steps, steps) == (expect["kernel_calls"], expect["steps"])
    assert seconds == pytest.approx(expect["kernel_s"], rel=1e-9)
    # real lines: the kernels carry no name yet (PR 23), their operands do.
    # Through the share's rule the 18 calls read 32 of 32 heads, whole
    whole, _ = named_kernels.whole_step_calls(
        plane, named_kernels.ANY_KERNEL, "step_fn")
    calls = [e for step in whole for e in step]
    assert {named_kernels.query_shape(e[0]) for e in calls} == {
        (1, 32, 4096, 128)}
    fields = config_fields("mistral-7b")
    job = {"rows_per_replica": 1, "seq": 4096}
    assert named_kernels.kernel_share(calls, fields, job) == (
        1.0, (1, 32, 4096, 128), None)
    assert named_kernels.kernel_share(
        calls, fields, {**job, "rows_per_replica": 4})[0] == 0.25


# -- the reference against the program, tiny widths --------------------------


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reference_agrees_with_the_program(config):
    import jax
    import jax.numpy as jnp
    import numpy as np

    fields = config_fields(config)
    family = family_of(fields)
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
    want, want_norm = family.reference(fields, seq)(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert float(got_norm) == pytest.approx(float(want_norm), rel=2e-4)
    # and it is no tautology: another RoPE base moves the reference
    moved, _ = family.reference({**fields, "rope_theta": 50.0}, seq)(
        params, tokens)
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
    notes = list(map(json.loads, done.stdout.splitlines()[:-1]))
    for name, key in (("restore", "restores_after_window"),
                      ("restore_warmup", "restore_warmups")):
        restores = [n for n in notes if n.get("note") == name]
        assert len(restores) == traffic[key]
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


# -- a new architecture is new files only -------------------------------------

LOOPED_FAMILY = '''
"""Not a plain decoder: the llama stack run PASSES times with the same
weights and one more norm, shared, after every pass."""

import jax
import jax.numpy as jnp

from benchmarks.families import llama_dense
from benchmarks.harness import flops
from benchmarks.reference import looped
from dlrover_tpu.models import llama

PASSES = 2
REHEARSAL_FIELDS = llama_dense.REHEARSAL_FIELDS
program_config = llama_dense.program_config


def init_params(config, key):
    params = llama.init_params(config, key)
    # not ones: a norm that does nothing would prove nothing
    params["pass_norm"] = (1 + 0.1 * jax.random.normal(
        jax.random.fold_in(key, 7), (config.dim,))).astype(config.dtype)
    return params


def logical_axes(config):
    axes = llama.param_logical_axes(config)
    return {**axes, "pass_norm": axes["final_norm"]}


def loss_fn(config, mesh):
    c = config

    def loss(params, tokens):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        B, S = inputs.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

        def layer_fn(h, layer):
            h = h + llama._attention(
                llama._rms_norm(h, layer["attn_norm"], c.norm_eps),
                layer, c, positions, mesh)
            h = h + llama._mlp(
                llama._rms_norm(h, layer["ffn_norm"], c.norm_eps), layer)
            return h, None

        x = params["tok_embed"][inputs]
        for _ in range(PASSES):
            x, _ = jax.lax.scan(layer_fn, x, params["layers"])
            x = llama._rms_norm(x, params["pass_norm"], c.norm_eps)
        x = llama._rms_norm(x, params["final_norm"], c.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                            preferred_element_type=jnp.float32)
        return llama.cross_entropy(logits, targets)

    return loss


def reference(fields, seq):
    return lambda params, tokens: looped.loss_and_grad_norm(
        params, tokens, fields, REFERENCE_PASSES)


def param_count(fields):
    return flops.param_count(fields) + fields["hidden_size"]


def train_flops_per_token(fields, seq):
    head = 6.0 * fields["hidden_size"] * fields["vocab_size"]
    layers = flops.train_flops_per_token(fields, seq) - head
    return PASSES * layers + head


def flash_attention_flops(fields, seq, rows):
    fwd, bwd = llama_dense.flash_attention_flops(fields, seq, rows)
    return PASSES * fwd, PASSES * bwd
'''

LOOPED_REFERENCE = '''
"""The looped stack in plain float32, from the library's blocks."""

import jax
import jax.numpy as jnp

from benchmarks.reference import decoder as d


def next_token_loss(params, tokens, f, passes):
    eps = f["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = p["tok_embed"][inputs]
        for _ in range(passes):
            for n in range(f["num_hidden_layers"]):
                layer = jax.tree.map(lambda a: a[n], p["layers"])
                x = x + d._attention(
                    d._rms_norm(x, layer["attn_norm"], eps), layer, f)
                x = x + d._swiglu(d._rms_norm(x, layer["ffn_norm"], eps),
                                  layer["w1"], layer["w3"], layer["w2"])
            x = d._rms_norm(x, p["pass_norm"], eps)
        x = d._rms_norm(x, p["final_norm"], eps)
        logp = jax.nn.log_softmax(x @ p["lm_head"], axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], -1).mean()


def loss_and_grad_norm(params, tokens, f, passes):
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    loss, grads = jax.value_and_grad(next_token_loss)(
        params, tokens, f, passes)
    return loss, jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree.leaves(grads)))
'''


def tracked_state():
    """What git says of the benchmark's tracked files, where this is a
    checkout of git's (the driver's is not)."""
    done = subprocess.run(
        ["git", "status", "--porcelain", "--", "benchmarks",
         "BENCHMARK.json"], cwd=ROOT, capture_output=True, text=True)
    return done.stdout if done.returncode == 0 else None


def benchmark_files():
    skip = ("__pycache__", ".pytest_cache")
    for folder, folders, files in os.walk(HERE):
        folders[:] = [d for d in folders if d not in skip]
        for name in files:
            if not name.endswith(".pyc"):
                yield os.path.relpath(os.path.join(folder, name), ROOT)


@pytest.mark.parametrize("reference_passes,correct", [(2, True), (1, False)])
def test_a_new_architecture_is_new_files_only(
        tmp_path, reference_passes, correct):
    """A family that is no plain decoder joins a copy of the benchmark
    as three new files and two new entries, no file that was there
    edited, and its cell's rehearsal runs to ``correct`` against its own
    reference, parameter count and FLOP counts. With the plain decoder's
    single pass for a reference the same run is not correct: the
    comparison is the family's, and it is no tautology."""
    import filecmp
    import shutil

    before = tracked_state()
    copy = tmp_path / "checkout"
    shutil.copytree(HERE, copy / "benchmarks", ignore=shutil.ignore_patterns(
        "__pycache__", ".pytest_cache", "*.pyc"))
    os.symlink(os.path.join(ROOT, "dlrover_tpu"), copy / "dlrover_tpu")
    # new files
    (copy / "benchmarks/families/looped_llama.py").write_text(
        LOOPED_FAMILY + f"\nREFERENCE_PASSES = {reference_passes}\n")
    (copy / "benchmarks/reference/looped.py").write_text(LOOPED_REFERENCE)
    fields = {**config_fields("mistral-7b"), "name": "looped-test",
              "family": "looped_llama"}
    (copy / "benchmarks/configs/looped-test.json").write_text(
        json.dumps(fields))
    # new entries
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        **bench["configs"][0], "name": "looped-test",
        "file": "benchmarks/configs/looped-test.json"})
    bench["workloads"].append({
        **bench["workloads"][0], "name": "looped-test.train-steady",
        "config": "looped-test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "looped-test.train-steady", "--seed", str(2**31 + 26), "--seconds",
         "2", "--trace", "1", "--rehearsal"], cwd=copy, env=env,
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()]
    out = lines[-1]
    assert out["correct"] is correct, out
    compared = next(x for x in lines if x.get("note") == "reference")
    assert compared["ok"] is correct

    # the harness asked the family, and the family is no plain decoder
    small = {**fields, **family_of(config_fields("mistral-7b"))
             .REHEARSAL_FIELDS}
    model = next(x for x in lines if x.get("note") == "model")
    assert model["params"] == flops.param_count(small) + small["hidden_size"]
    head = 6.0 * small["hidden_size"] * small["vocab_size"]
    assert model["train_flops_per_token"] == (
        2 * (flops.train_flops_per_token(small, 64) - head) + head)
    score = flops.attention_matmul_flops(small, 64, 1)
    assert (model["flash_fwd_flops"], model["flash_bwd_flops"]) == (
        2 * 2 * score, 2 * 5 * score)

    # nothing that was there changed, in the copy or here
    ours = list(benchmark_files())
    same, differ, missing = filecmp.cmpfiles(ROOT, copy, ours, shallow=False)
    assert (differ, missing) == ([], []) and len(same) == len(ours)
    assert tracked_state() == before
