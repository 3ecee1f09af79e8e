"""CPU checks of ``head.ms`` (PR 34), the expert cell's reader of the
output head, on hand-made profiles of four chips in either layout: every
chip makes the whole logits, or a quarter of the vocabulary each. Run
from the repo root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_head_ms.py -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

BENCH = bench_run.load_benchmark()
CELL = "mixtral-8x7b.train-steady"
CHIPS, SEQ, HIDDEN, VOCAB = 4, 8, 40, 64
STEP_NS = 10_000
LAYOUTS = {"whole": 1, "quarter": 4}   # chips that share the vocabulary


def step_ops(at, share, slow=1, cut=False):
    """One step program's ops from ``at``: a ``while`` that carries a
    logits-shaped array and holds, nested, the head's matmul, its
    log-sum-exp, a gradient matmul whose operand the logits are, a
    reshape of them, an op that has nothing to do with them and an
    all-reduce of the statistics; after it AdamW over the embedding
    (``[vocab, hidden]``: never logits-shaped)."""
    v = VOCAB // share
    logits = f"f32[1,{SEQ},{v}]{{2,1,0:T(8,128)}}"
    flat = f"bf16[{SEQ},{v}]{{0,1:T(8,128)(2,1)}}"
    ops = [
        [f"%while.1 = (s32[], {logits}) while(%tuple.1), body=%b", at, 9000],
        [f"%fusion.1 = {logits} fusion(bf16[1,{SEQ},{HIDDEN}] %h, "
         f"bf16[{HIDDEN},{v}] %w)", at + 10, 1000 * slow],
        [f"%select_add_fusion.2 = f32[{SEQ}]{{0}} fusion({logits} %fusion.1)",
         at + 3010, 500],
        [f"%fusion.3 = bf16[{HIDDEN},{v}]{{1,0}} fusion("
         f"bf16[{SEQ},{HIDDEN}] %h, {flat} %d)", at + 3510, 700],
        [f"%reshape.4 = f32[{SEQ * v}]{{0}} reshape({logits} %fusion.1)",
         at + 4210, 200],
        [f"%fusion.5 = bf16[1,{SEQ},{HIDDEN}] fusion("
         f"bf16[1,{SEQ},{HIDDEN}] %h)", at + 4410, 3000],
        [f"%psum.6 = f32[1,{SEQ}]{{1,0}} all-reduce(%bitcast.9)",
         at + 7410, 100],
        [f"%add_convert_fusion.7 = (bf16[{v},{HIDDEN}], f32[{v},{HIDDEN}]) "
         f"fusion(bf16[{v},{HIDDEN}] %e)", at + 9000, 600],
    ]
    return ops[:3] if cut else ops


def trace(layout, steps=3, cut=True, slow_chip=2):
    """Four chips, ``steps`` whole step programs each and, with ``cut``,
    one more that the profile's edge cut after its second head op. Chip
    ``slow_chip``'s head matmul takes three times as long."""
    share = LAYOUTS[layout]
    planes = []
    for chip in range(CHIPS):
        slow = 3 if chip == slow_chip else 1
        ops = [e for n in range(steps)
               for e in step_ops(n * STEP_NS, share, slow)]
        if cut:
            ops += step_ops(steps * STEP_NS, share, slow, cut=True)
        modules = [["jit_step_fn(1)", n * STEP_NS, STEP_NS - 100]
                   for n in range(steps + bool(cut))]
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]})
    return {"planes": planes}


def ctx_for(raw, **fields):
    return {"trace_raw": raw, "step_module": "step_fn",
            "fields": {"vocab_size": VOCAB, "hidden_size": HIDDEN, **fields},
            "job": {"seq": SEQ, "rows": 1, "chips": CHIPS, "grad_accum": 2}}


read = bench_run.load_reader("head.ms").read


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_head_ms_reads_the_heads_ops_in_either_layout(layout, capsys):
    # matmul, log-sum-exp, gradient matmul, reshape: 2400 ns a step, and
    # 4400 on the chip whose matmul is slow: the largest over the chips.
    # Not the while that carries the logits, the unrelated op, the
    # all-reduce or AdamW over the embedding; the cut step is left out
    assert read(ctx_for(trace(layout))) == pytest.approx(4400e-6)
    note = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert note["note"] == "head_ms_by_chip"
    assert note["chips"] == pytest.approx([2400e-6] * 2 + [4400e-6, 2400e-6])
    assert note["ops_a_step"] == [4] * CHIPS
    # without the cut step the same
    assert read(ctx_for(trace(layout, cut=False))) == pytest.approx(4400e-6)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_head_ms_of_another_shape_reads_nothing(layout):
    raw = trace(layout)
    # a vocabulary whose shares the profile does not hold
    assert read(ctx_for(raw, vocab_size=VOCAB * 8)) is None
    # a share by a number of chips that does not divide the cell's is no
    # layout of this cell: 3 of 4
    share = VOCAB // LAYOUTS[layout]
    assert read(ctx_for(raw, vocab_size=share * 3)) is None
    # another sequence length
    other = ctx_for(raw)
    other["job"]["seq"] = SEQ * 2
    assert read(other) is None


def test_where_the_sequence_is_as_long_as_the_width_the_weight_counts():
    """The expert cell's sequence and hidden width are both 4096: the
    head's weight, ``[hidden, vocab / n]``, is logits-shaped there, and
    AdamW over it is counted, in either layout alike."""
    for layout, share in LAYOUTS.items():
        raw = trace(layout, cut=False, slow_chip=None)
        adamw = (f"%add_convert_fusion.8 = (bf16[{SEQ},{VOCAB // share}]) "
                 f"fusion(bf16[{SEQ},{VOCAB // share}] %w)")
        for plane in raw["planes"]:
            plane["lines"][1]["events"] += [
                [adamw, n * STEP_NS + 9700, 150] for n in range(3)]
        assert read(ctx_for(raw)) == pytest.approx(2550e-6), layout


def test_head_ms_with_nothing_to_read_returns_nothing():
    empty = {"trace_raw": None, "trace": [], "job": {}, "fields": {},
             "step_module": "step_fn"}
    assert read(empty) is None
    # a trace with no chip in it (a rehearsal's)
    no_chip = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert read(ctx_for(no_chip)) is None
    # a configuration file without a vocabulary
    bare = ctx_for(trace("whole"))
    del bare["fields"]["vocab_size"]
    assert read(bare) is None
    # one chip of the four holds no such op: no number for the cell
    partial = trace("quarter")
    partial["planes"][3]["lines"][1]["events"] = []
    assert read(ctx_for(partial)) is None


def test_the_entry_is_the_expert_cells_alone():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "head.ms")
    assert entry == {
        "name": "head.ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "parallel",
        "moves": "tokens_per_s", "workloads": [CELL]}
    for cell in (w["name"] for w in BENCH["workloads"]):
        names = {m["name"] for m in bench_run.metrics_of(
            cell, BENCH["per_layer"])}
        assert ("head.ms" in names) == (cell == CELL), cell
    # loop.head_ms keeps its rule and its list
    loop = next(m for m in BENCH["per_layer"] if m["name"] == "loop.head_ms")
    assert loop["workloads"] == ["ouro-2.6b.train-steady"]
