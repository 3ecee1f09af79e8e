"""A flash roofline counts the work its chip was given (PR 36): the share
of a microbatch's attention is read from the kernel calls' own query
operand, ``bf16[rows, heads, seq, head_dim]``, on hand-made traces. Run
from the repo root:

    python3 -m pytest benchmarks/tests/test_kernel_share.py -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import named_kernels  # noqa: E402

FIELDS = {"num_attention_heads": 32}
JOB = {"grad_accum": 2, "seq": 4096, "rows_per_replica": 4,
       "flash_fwd_flops": 10.0, "flash_bwd_flops": 25.0}
READERS = {"flash_fwd_roofline": 10.0, "flash_bwd_roofline": 25.0,
           "flash_attn_roofline": 35.0}
WHOLE = (4, 32, 4096, 128)
# kernel ns a step program at the whole shape: two microbatches of a
# forward, a recomputed forward, a dq and a dkv
NS = {"flash_fwd.": 4 * 400, "flash_bwd_dq.": 2 * 800,
      "flash_bwd_dkv.": 2 * 1200}


def line(name, shape, n=1):
    """A Pallas call's HLO line as XLA writes it: the queries first."""
    q = "bf16[%d,%d,%d,%d]{3,2,1,0:T(8,128)(2,1)}" % shape
    lse = "f32[%d,%d,%d,128]{3,2,1,0:T(8,128)}" % shape[:3]
    return (f"%{name}{n} = ({q}, {lse}) custom-call({q} %bitcast.1, {q} "
            f"%bitcast.2, {q} %bitcast.3), "
            'custom_call_target="tpu_custom_call", '
            f"operand_layout_constraints={{{q}, {q}, {q}}}")


def plane(shape, scale=1.0, chip=0, steps=3, shapes=None):
    """``steps`` step programs (the last one cut by the profile's edge:
    its dkv calls are missing) of flash calls at ``shape``, each taking
    ``scale`` times the whole shape's time. ``shapes`` overrides the
    shape by kernel name."""
    modules, ops = [], []
    for s in range(steps):
        t = s * 100_000
        modules.append(["jit_step_fn(1)", t, 90_000])
        for microbatch in range(2):
            for name, calls in (("flash_fwd.", 2), ("flash_bwd_dq.", 1),
                                ("flash_bwd_dkv.", 1)):
                if name == "flash_bwd_dkv." and s == steps - 1:
                    continue
                for _ in range(calls):
                    dur = int({"flash_fwd.": 400, "flash_bwd_dq.": 800,
                               "flash_bwd_dkv.": 1200}[name] * scale)
                    ops.append([line(name, (shapes or {}).get(name, shape)),
                                t, dur])
                    t += dur + 10
        ops.append(["%fusion.9 = bf16[4,32,4096,128]{3,2,1,0} fusion("
                    "bf16[4,32,4096,128]{3,2,1,0} %flash_fwd.1)", t, 50])
    return {"name": f"/device:TPU:{chip}", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}


def ctx_of(*planes, job=JOB):
    return {"trace_raw": {"planes": list(planes)}, "step_module": "step_fn",
            "peaks": {"bf16_flops_per_s": 1e9}, "fields": FIELDS, "job": job}


def read(metric, ctx):
    return bench_run.load_reader(metric).read(ctx)


def notes(capsys, kind="kernel_share"):
    return [n for n in map(json.loads, capsys.readouterr().out.splitlines())
            if n["note"] == kind]


def whole_reading(metric):
    """By hand: two whole steps x 2 microbatches x the FLOPs, at the peak,
    over the two whole steps' kernel time."""
    ns = {"flash_fwd_roofline": NS["flash_fwd."],
          "flash_bwd_roofline": NS["flash_bwd_dq."] + NS["flash_bwd_dkv."],
          "flash_attn_roofline": sum(NS.values())}[metric]
    return 100.0 * (2 * 2 * READERS[metric] / 1e9) / (2 * ns * 1e-9)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_whole_call_reads_as_before(metric, capsys):
    assert read(metric, ctx_of(plane(WHOLE))) == pytest.approx(
        whole_reading(metric))
    (note,) = notes(capsys)
    assert note["share"] == 1.0 and note["by_chip"] == [1.0]
    assert note["query_operand"] == list(WHOLE) and "why" not in note


# (a) and (c): a quarter of the heads, of the rows or of the sequence at a
# quarter of the time reads the same percentage; (b): at the whole time a
# quarter of it
SPLITS = {"heads": (4, 8, 4096, 128), "rows": (1, 32, 4096, 128),
          "sequence": (4, 32, 1024, 128), "heads_and_rows": (2, 16, 4096, 128)}


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("split", sorted(SPLITS))
def test_a_quarter_of_the_work_is_counted_as_a_quarter(
        metric, split, capsys):
    shape = SPLITS[split]
    as_fast = read(metric, ctx_of(plane(shape, scale=0.25)))
    assert as_fast == pytest.approx(whole_reading(metric))
    (note,) = notes(capsys)
    assert note["share"] == 0.25 and note["query_operand"] == list(shape)
    no_faster = read(metric, ctx_of(plane(shape, scale=1.0)))
    assert no_faster == pytest.approx(whole_reading(metric) / 4)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_on_a_mesh_the_note_gives_every_chips_share(metric, capsys):
    """Chip 0 is the one read; a chip that was given another share shows
    in the note."""
    chips = [plane(SPLITS["heads"], 0.25, chip=n) for n in range(3)]
    chips.append(plane(WHOLE, chip=3))
    assert read(metric, ctx_of(*chips)) == pytest.approx(
        whole_reading(metric))
    (note,) = notes(capsys)
    assert note["by_chip"] == [0.25, 0.25, 0.25, 1.0]


# (d) no second definition stands in
def test_calls_of_one_name_that_disagree_give_no_reading(capsys):
    mixed = plane(WHOLE)
    ops = mixed["lines"][1]["events"]
    first_fwd = next(e for e in ops if e[0].startswith("%flash_fwd."))
    first_fwd[0] = line("flash_fwd.", SPLITS["heads"])
    ctx = ctx_of(mixed)
    assert read("flash_fwd_roofline", ctx) is None
    assert read("flash_attn_roofline", ctx) is None
    assert read("flash_bwd_roofline", ctx) == pytest.approx(
        whole_reading("flash_bwd_roofline"))
    fwd, any_kernel, bwd = notes(capsys)
    for note in (fwd, any_kernel):
        assert note["share"] is None and "differ" in note["why"]
        assert note["query_operand"] == [list(WHOLE), list(SPLITS["heads"])]
    assert bwd["share"] == 1.0


def test_kernels_of_two_names_that_disagree_give_no_reading(capsys):
    """dq at a quarter of the heads, dkv whole: the backward roofline has
    no one share to count by."""
    ctx = ctx_of(plane(WHOLE, shapes={"flash_bwd_dq.": SPLITS["heads"]}))
    assert read("flash_bwd_roofline", ctx) is None
    assert read("flash_attn_roofline", ctx) is None
    assert read("flash_fwd_roofline", ctx) == pytest.approx(
        whole_reading("flash_fwd_roofline"))
    assert [n["share"] for n in notes(capsys)] == [None, None, 1.0]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_call_larger_than_the_family_counts_gives_no_reading(
        metric, capsys):
    ctx = ctx_of(plane(WHOLE), job={**JOB, "rows_per_replica": 2})
    assert read(metric, ctx) is None
    (note,) = notes(capsys)
    assert note["share"] is None and note["query_operand"] == list(WHOLE)
    assert "2 of the 2 x 32 x 4096" in note["why"]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_line_without_a_four_dimensional_operand_is_counted_whole(
        metric, capsys):
    """XLA never writes one; ``tests/test_program_spans.py`` does."""
    bare = plane(WHOLE)
    for e in bare["lines"][1]["events"]:
        if e[0].startswith("%flash_"):
            e[0] = (e[0].split(" = ")[0] + " = bf16[1]{0} custom-call(%x), "
                    'custom_call_target="tpu_custom_call"')
    assert read(metric, ctx_of(bare)) == pytest.approx(whole_reading(metric))
    (note,) = notes(capsys)
    assert note["share"] == 1.0 and note["query_operand"] is None
    assert "counted whole" in note["why"]


def test_any_kernel_without_a_flash_kernel_by_name_gives_no_reading(capsys):
    """``flash_attn_roofline`` takes the share from the flash kernels it
    finds among the Pallas calls; where it finds none, attention's FLOPs
    are set against no kernel time."""
    unnamed = plane(WHOLE)
    for e in unnamed["lines"][1]["events"]:
        e[0] = e[0].replace("%flash_fwd.", "%closed_call.").replace(
            "%flash_bwd_", "%checkpoint_")
    assert read("flash_attn_roofline", ctx_of(unnamed)) is None
    (note,) = notes(capsys)
    assert note["share"] is None and "no flash kernel" in note["why"]


def test_the_query_operand_is_the_first_of_the_operands():
    """Not the result's shape, not a later operand's: a grouped-query
    call whose keys hold fewer heads reads the queries'."""
    q, kv = "bf16[1,8,4096,128]{3,2,1,0}", "bf16[1,2,4096,128]{3,2,1,0}"
    hlo = (f"%flash_bwd_dkv.7 = ({kv}, {kv}) custom-call({q} %a, {kv} %b, "
           f'{kv} %c), custom_call_target="tpu_custom_call"')
    assert named_kernels.query_shape(hlo) == (1, 8, 4096, 128)
    assert named_kernels.query_shape(
        "%flash_fwd.3 = bf16[1]{0} custom-call(%x)") is None
    assert named_kernels.query_shape("%fusion.1 = f32[] fusion()") is None
