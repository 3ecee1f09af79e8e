"""CPU checks of what the looped configuration brings to the benchmark:
the family's counts against hand counts, its two readers on a hand-made
trace and with nothing to read, its cell's traced rehearsal. Run from the
repo root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.families import looplm  # noqa: E402

BENCH = bench_run.load_benchmark()
CELL = "ouro-2.6b.train-steady"


def fields():
    with open(os.path.join(ROOT, "benchmarks/configs/ouro-2.6b.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    f = fields()
    published = {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 16, "head_dim": 128,
        "intermediate_size": 5632, "vocab_size": 49152,
        "total_ut_steps": 4, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "max_position_embeddings": 65536, "early_exit_threshold": 1,
        "sliding_window": None,
    }
    assert {k: f[k] for k in published} == published
    assert f["reduced"] == ["num_hidden_layers"]
    assert f["published"] == {"num_hidden_layers": 48}
    assert 4 <= f["num_hidden_layers"] <= 8
    assert set(f["layer_types"]) == {"full_attention"}
    assert len(f["layer_types"]) == 48
    config = looplm.program_config(f, 4096)
    assert (config.n_passes, config.n_layers, config.head_dim) == (
        4, f["num_hidden_layers"], 128)
    assert config.remat and config.remat_policy == f["remat_policy"]
    # what the program's family refuses rather than computes wrongly
    for key, value in (("sliding_window", 4096), ("tie_word_embeddings", True),
                       ("layer_types", ["sliding_attention"]),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError):
            looplm.program_config({**f, key: value}, 4096)


def test_counts_against_hand_counts():
    f = {**fields(), "num_hidden_layers": 8}
    attn = 4 * 2048 * 2048                       # 16,777,216
    ffn = 3 * 2048 * 5632                        # 34,603,008
    layer = attn + ffn + 4 * 2048                # four norms: 51,388,416
    assert layer == 51_388_416
    head = 2048 * 49152
    gate = 2048 + 1
    assert looplm.param_count(f) == 8 * layer + 2 * head + 2048 + gate
    assert looplm.param_count(f) == 612_438_017
    # the whole model: the published "2.6B"
    assert looplm.param_count({**f, "num_hidden_layers": 48}) == 2_667_974_657
    # the passes share every weight
    assert looplm.param_count({**f, "total_ut_steps": 1}) == 612_438_017
    score = 16 * 2 * 128 * 4096 * 4097 // 2      # one causal matmul, 16 heads
    assert score == 34_368_126_976
    per_token_attn = 6 * 16 * 128 * 4097         # six of them, a token
    assert looplm.train_flops_per_token(f, 4096) == (
        6 * 4 * (8 * (attn + ffn) + head + 2048) + 4 * 8 * per_token_attn)
    assert looplm.train_flops_per_token(f, 4096) == 13_891_977_216
    assert looplm.flash_attention_flops(f, 4096, 1) == (
        2 * 4 * 8 * score, 5 * 4 * 8 * score)
    assert looplm.flash_attention_flops(f, 4096, 1) == (
        2_199_560_126_464, 5_498_900_316_160)
    # passes, layers and rows scale the kernels' work, and nothing else
    assert looplm.flash_attention_flops(
        {**f, "total_ut_steps": 2, "num_hidden_layers": 3}, 4096, 2) == (
            2 * 2 * 3 * 2 * score, 5 * 2 * 3 * 2 * score)
    assert looplm.REHEARSAL_FIELDS["total_ut_steps"] == 4
    assert looplm.REHEARSAL_FIELDS["num_hidden_layers"] >= 2


# -- the two readers ----------------------------------------------------------

PALLAS = ' = bf16[] custom-call(), custom_call_target="tpu_custom_call"'
LOGITS = "f32[1,8,32]{2,1,0:T(8,128)}"       # seq 8, vocabulary 32
FLAT = "bf16[8,32]{1,0:T(8,128)(2,1)}"


def step(at, fwd_calls, cut=False):
    """One step program of 1000 ns at ``at``: a ``while`` that carries a
    logits-shaped array and holds, nested, a head matmul, a log-sum-exp
    that reads the logits, a gradient matmul whose operand they are, a
    reshape of them, an op that has nothing to do with them, AdamW over
    the vocabulary-sized leaf, and the forward kernel's calls."""
    ops = [
        [f"%while.1 = (s32[], {LOGITS}) while(%tuple.1), body=%b", at, 900],
        [f"%fusion.1 = {LOGITS} fusion(bf16[1,8,16] %h, bf16[16,32] %w)",
         at + 10, 100],
        [f"%reduce.2 = f32[8]{{0}} fusion({LOGITS} %fusion.1)", at + 110, 50],
        [f"%fusion.3 = bf16[16,32]{{1,0}} fusion(bf16[8,16] %h, {FLAT} %d)",
         at + 160, 70],
        [f"%reshape.4 = f32[256]{{0}} reshape({LOGITS} %fusion.1)",
         at + 230, 20],
        ["%fusion.5 = bf16[1,8,16] fusion(bf16[1,8,16] %h)", at + 250, 300],
        ["%fusion.6 = (bf16[16,32], f32[16,32]) fusion(bf16[16,32] %w)",
         at + 900, 60],
    ]
    ops += [[f"%flash_fwd.{n}" + PALLAS, at + 600 + 10 * n, 10]
            for n in range(fwd_calls)]
    return ops[:3] if cut else ops


def trace(steps):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_step_fn(1)", 1000 * n, 1000] for n in range(len(steps))]},
        {"name": "XLA Ops", "events": [e for s in steps for e in s]}]}]}


def ctx_for(raw, **job):
    return {"trace_raw": raw, "step_module": "step_fn",
            "fields": {"vocab_size": 32, "total_ut_steps": 2,
                       "num_hidden_layers": 2},
            "job": {"seq": 8, "rows": 1, "chips": 1, "grad_accum": 2, **job}}


def test_head_ms_counts_the_ops_that_hold_the_logits():
    read = bench_run.load_reader("loop.head_ms").read
    whole = [step(0, 8), step(1000, 8)]
    # matmul, log-sum-exp, gradient matmul, reshape: 240 ns a step; not the
    # while that carries the logits, the unrelated op, or AdamW
    assert read(ctx_for(trace(whole))) == pytest.approx(240e-6)
    # a step program the profile's edge cut is left out, not averaged in
    assert read(ctx_for(trace(whole + [step(2000, 8, cut=True)]))) == (
        pytest.approx(240e-6))
    # another sequence length: nothing here is logits-shaped
    assert read(ctx_for(trace(whole), seq=64)) is None
    # rows of a microbatch may be folded into the sequence axis
    assert read(ctx_for(trace(whole), seq=4, rows=2)) == pytest.approx(
        240e-6)


def test_flash_fwd_reruns_is_calls_over_layer_applications():
    read = bench_run.load_reader("loop.flash_fwd_reruns").read
    # 2 microbatches x 2 passes x 2 layers = 8 applications a step
    assert read(ctx_for(trace([step(0, 8), step(1000, 8)]))) == 1.0
    assert read(ctx_for(trace([step(0, 16), step(1000, 16)]))) == 2.0
    # the cut step holds fewer calls and does not lower the count
    assert read(ctx_for(trace(
        [step(0, 16), step(1000, 16), step(2000, 16, cut=True)]))) == 2.0
    assert read(ctx_for(trace([step(0, 0)]))) is None


@pytest.mark.parametrize("name", ["loop.head_ms", "loop.flash_fwd_reruns"])
def test_a_new_reader_with_nothing_to_read_returns_nothing(name):
    read = bench_run.load_reader(name).read
    empty = {"trace_raw": None, "trace": [], "job": {}, "fields": {},
             "step_module": "step_fn"}
    assert read(empty) is None
    # a trace with no chip in it (a rehearsal's)
    no_chip = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert read(ctx_for(no_chip)) is None
    # a program of the parent's, which has no looped configuration
    dense = ctx_for(trace([step(0, 8)]))
    dense["fields"] = {"hidden_size": 4096}
    assert read(dense) is None
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["layer"] == "trainer"
    assert entry["moves"] == "tokens_per_s"


# -- the cell -----------------------------------------------------------------


def test_traced_rehearsal_of_the_cell():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELL,
         "--seed", str(2**31 + 27), "--seconds", "2", "--trace", "1",
         "--rehearsal"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()]
    out = lines[-1]
    assert out["correct"] is True and out["failed"] == 0
    model = next(x for x in lines if x.get("note") == "model")
    small = {**fields(), **looplm.REHEARSAL_FIELDS}
    assert model["params"] == looplm.param_count(small)
    assert model["train_flops_per_token"] == looplm.train_flops_per_token(
        small, 64)
    warm = next(x for x in lines if x.get("note") == "warmup")["steps"]
    assert [s["compiles"] for s in warm[1:]] == [0] * (len(warm) - 1)
    allowed = {m["name"] for m in bench_run.metrics_of(
        CELL, BENCH["per_layer"])}
    assert set(out["metrics"]) <= allowed
    # no chip: the device's readers, the two new ones among them, are silent
    assert not {"loop.head_ms", "loop.flash_fwd_reruns",
                "train.mfu_pct"} & set(out["metrics"])
    assert {"train.dispatch_ms", "train.compile_requests"} <= set(
        out["metrics"])
