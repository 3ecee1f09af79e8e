"""A Pallas kernel's share of its compute roofline, found by the name
the program gave it.

``pl.pallas_call(..., name="flash_fwd")`` names the compiled custom call
``%flash_fwd.<n>``, and a profile names an op's events by its HLO line,
which starts with that name. Other lines mention the name too (the ops
that take the kernel's result as an operand), so the match is on the
line's own name and on the Pallas call target.
"""

from benchmarks.harness import flops, program_spans, trace_reduce

PALLAS = 'custom_call_target="tpu_custom_call"'


def kernel_seconds(plane, prefixes, step_module):
    """(summed device seconds, calls a step, whole steps) of the Pallas
    calls whose own name starts with one of ``prefixes`` and that lie
    inside a whole step program. A step program that holds fewer such
    calls than the others was cut by the profile's edge (its module event
    is there, some of its ops are not): it is left out with its calls.
    ``trace_reduce.kernel_seconds`` counts it as a whole step, which
    moves ``flash_attn_roofline`` by a microbatch in 16 steps, 3 %."""
    starts = tuple("%" + p for p in prefixes)
    hit = [e for e in trace_reduce.line_events(plane, trace_reduce.OPS_LINE)
           if e[0].startswith(starts) and PALLAS in e[0]]
    by_step = [[e for e in hit if s[1] <= e[1] and e[1] + e[2] <= s[1] + s[2]]
               for s in trace_reduce.step_events(plane, step_module)]
    calls = max(map(len, by_step), default=0)
    whole = [step for step in by_step if calls and len(step) == calls]
    return (sum(e[2] for step in whole for e in step) / 1e9, calls,
            len(whole))


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


def attention_roofline(ctx, prefixes, flops_key):
    """100 x least time / kernel time on chip 0. Least time: the FLOPs
    ``ctx["job"][flops_key]`` (``flash_fwd_flops`` or ``flash_bwd_flops``:
    what the cell's family counts for one microbatch over every call its
    architecture makes, ``jobs/train.py`` puts them there) x microbatches
    a step x the whole steps seen, over the published bf16 peak; the
    bound is compute, as for ``flash_attn_roofline``. None without a
    trace, a peak, a kernel of that name in it, or where the family
    counts no FLOPs for it. Prints a note ``kernel_calls``: calls a step,
    the steps counted, and the step programs the profile holds."""
    if not ctx["trace_raw"] or not ctx["peaks"]:
        return None
    per_microbatch = _least_flops(ctx, flops_key)
    if not per_microbatch:
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    if not planes:
        return None
    seconds, calls, steps = kernel_seconds(
        planes[0], prefixes, ctx["step_module"])
    if not seconds:
        return None
    program_spans.note(
        "kernel_calls", kernel=prefixes[0], a_step=calls, whole_steps=steps,
        step_programs=len(trace_reduce.step_events(
            planes[0], ctx["step_module"])))
    least = (steps * ctx["job"]["grad_accum"] * per_microbatch
             / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / seconds
