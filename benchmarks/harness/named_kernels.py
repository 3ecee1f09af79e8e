"""A Pallas kernel's share of its compute roofline, found by the name
the program gave it, against the work the traced chip was given.

``pl.pallas_call(..., name="flash_fwd")`` names the compiled custom call
``%flash_fwd.<n>``, and a profile names an op's events by its HLO line,
which starts with that name. Other lines mention the name too (the ops
that take the kernel's result as an operand), so the match is on the
line's own name and on the Pallas call target.

The same line carries the call's operands with their shapes, as the chip
holds them. The flash kernels take the queries first,
``bf16[rows, heads, seq, head_dim]``: a layout that gives a chip a
quarter of the heads, of the rows or of the sequence shows it there, and
``kernel_share`` reads it there and from no table of sharding rules.
"""

import re

from benchmarks.harness import flops, program_spans, trace_reduce

PALLAS = 'custom_call_target="tpu_custom_call"'
FLASH_KERNELS = ("flash_fwd.", "flash_bwd_dq.", "flash_bwd_dkv.")
ANY_KERNEL = ("",)  # every Pallas call, whatever its name

_ARRAY_4D = re.compile(r"\b[a-z]+\d+\[(\d+),(\d+),(\d+),(\d+)\]")


def whole_step_calls(plane, prefixes, step_module):
    """(the calls of each whole step program, calls a step): the Pallas
    calls whose own name starts with one of ``prefixes``, as events, one
    list a step program that holds as many of them as the fullest one. A
    step program that holds fewer was cut by the profile's edge (its
    module event is there, some of its ops are not): it is left out with
    its calls."""
    starts = tuple("%" + p for p in prefixes)
    hit = [e for e in trace_reduce.line_events(plane, trace_reduce.OPS_LINE)
           if e[0].startswith(starts) and PALLAS in e[0]]
    by_step = [[e for e in hit if s[1] <= e[1] and e[1] + e[2] <= s[1] + s[2]]
               for s in trace_reduce.step_events(plane, step_module)]
    calls = max(map(len, by_step), default=0)
    return [step for step in by_step if calls and len(step) == calls], calls


def kernel_seconds(plane, prefixes, step_module):
    """(summed device seconds, calls a step, whole steps) of the Pallas
    calls whose own name starts with one of ``prefixes`` and that lie
    inside a whole step program (``whole_step_calls``). Counting a cut
    program as a whole step moves a roofline by a microbatch in 16
    steps, 3 %."""
    whole, calls = whole_step_calls(plane, prefixes, step_module)
    return (sum(e[2] for step in whole for e in step) / 1e9, calls,
            len(whole))


def query_shape(hlo_line):
    """``(rows, heads, seq, head_dim)``: the first four-dimensional
    operand of the call, which for the flash kernels is the queries; None
    where the line names no such operand."""
    _, _, operands = hlo_line.partition("custom-call(")
    found = _ARRAY_4D.search(operands)
    return tuple(int(n) for n in found.groups()) if found else None


def kernel_share(calls, fields, job):
    """(share, shape, why): the share of one microbatch's attention that
    each of ``calls`` (events of flash kernels) was given. The family
    counts ``job["rows_per_replica"]`` rows of
    ``fields["num_attention_heads"]`` heads at ``job["seq"]`` positions;
    the share is rows x heads x seq of a call's queries over that, the
    same whether heads, rows or the sequence were split. It is of a call,
    not of the calls: a forward that remat runs again is time, not work.

    share None, with the reason in ``why``: the calls carry different
    shapes, or one larger than the family counts, or there is no call: no
    second definition stands in. share 1.0 with a ``why``: no call names
    a four-dimensional operand (XLA always writes one; a hand-made line
    may not), counted whole."""
    if not calls:
        return None, None, "no flash kernel by name among the calls"
    shapes = sorted({query_shape(e[0]) for e in calls}, key=str)
    if shapes == [None]:
        return 1.0, None, "no four-dimensional operand: counted whole"
    if len(shapes) > 1:
        return None, shapes, "the calls' query operands differ"
    (shape,) = shapes
    rows, heads, seq, _ = shape
    whole = (job["rows_per_replica"] * fields["num_attention_heads"]
             * job["seq"])
    share = rows * heads * seq / whole
    if share > 1:
        return None, shape, (
            f"rows x heads x seq of a call is {share:g} of the "
            f"{job['rows_per_replica']} x {fields['num_attention_heads']} "
            f"x {job['seq']} the family counts")
    return share, shape, None


def chip_share(ctx, whole_by_chip, kernel):
    """``kernel_share`` of chip 0's flash calls among ``whole_by_chip``
    (every chip's Pallas calls inside whole step programs, a list a
    step); prints the note ``kernel_share``: the share, the operand shape
    it was read from, why where there is something to say, and on a mesh
    every chip's share."""
    flash = tuple("%" + p for p in FLASH_KERNELS)
    by_chip = [kernel_share(
        [e for step in whole for e in step if e[0].startswith(flash)],
        ctx["fields"], ctx["job"]) for whole in whole_by_chip]
    share, shape, why = by_chip[0]
    program_spans.note(
        "kernel_share", kernel=kernel, share=share, query_operand=shape,
        by_chip=[s for s, _, _ in by_chip], **({"why": why} if why else {}))
    return share


def _least_flops(ctx, flops_key):
    """``job[flops_key]``. A job from before PR 26 carries no count and the
    ``rows_per_replica`` of a plain decoder instead, which is what
    ``tests/test_program_spans.py`` still hands the readers: counted by
    ``flops.py`` then. To go with that test's next edit (``PERF.md``
    section 7); ``jobs/train.py`` always gives the count."""
    job = ctx["job"]
    if flops_key in job:
        return job[flops_key]
    fwd, bwd = flops.flash_attention_flops(
        ctx["fields"], job["seq"], job["rows_per_replica"])
    return {"flash_fwd_flops": fwd, "flash_bwd_flops": bwd}[flops_key]


def attention_roofline(ctx, prefixes, flops_keys):
    """100 x least time / kernel time on chip 0. Least time: the FLOPs
    ``ctx["job"][key]`` summed over ``flops_keys`` (``flash_fwd_flops``,
    ``flash_bwd_flops``: what the cell's family counts for one microbatch
    over every call its architecture makes, ``jobs/train.py`` puts them
    there) x the share of a microbatch's attention that chip 0's calls
    were given (``kernel_share``) x microbatches a step x the whole
    steps seen, over the published bf16 peak; the bound is compute: at
    sequence 4096 and head size 128 a call needs some hundreds of FLOPs
    for each byte it must move. Kernel time: the Pallas calls named by
    ``prefixes`` inside whole step programs. None without a trace, a
    peak, such a kernel in it, FLOPs the family counts for it, or a share
    to read. Prints the notes ``kernel_calls`` (calls a step, the steps
    counted, the step programs the profile holds) and ``kernel_share``."""
    if not ctx["trace_raw"] or not ctx["peaks"]:
        return None
    per_microbatch = sum(_least_flops(ctx, key) for key in flops_keys)
    if not per_microbatch:
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    if not planes:
        return None
    by_chip = [whole_step_calls(plane, prefixes, ctx["step_module"])
               for plane in planes]
    whole, calls = by_chip[0]
    seconds = sum(e[2] for step in whole for e in step) / 1e9
    if not seconds:
        return None
    kernel = prefixes[0] or "any Pallas call"
    program_spans.note(
        "kernel_calls", kernel=kernel, a_step=calls, whole_steps=len(whole),
        step_programs=len(trace_reduce.step_events(
            planes[0], ctx["step_module"])))
    share = chip_share(ctx, [w for w, _ in by_chip], kernel)
    if share is None:
        return None
    least = (len(whole) * ctx["job"]["grad_accum"] * per_microbatch * share
             / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / seconds
