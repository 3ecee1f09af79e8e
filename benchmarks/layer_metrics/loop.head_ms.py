"""Device milliseconds a step that a looped model's exit heads take on
chip 0: over the whole step programs of the profile, the self times of
the ops whose own HLO line holds a logits-shaped array, ``[seq,
vocab_size]`` of one microbatch's rows with any leading unit dimensions
and any layout. Those are the heads' matmuls, their log-sum-exps, the
two gradient matmuls of each and what reshapes the logits between them,
in every pass and again where the backward pass makes the logits anew;
not AdamW over the two vocabulary-sized leaves, whose lines hold
``[hidden, vocab]``. A ``while`` that carries such an array is no op of
the head and is left out. A step program the profile's edge cut holds
fewer such ops than the others and is left out, as in
``named_kernels.kernel_seconds``. None without a trace or such an op."""

import re

from benchmarks.harness import trace_reduce

CONTROL_FLOW = (" while(", " conditional(", " call(")


def read(ctx):
    job, fields = ctx["job"], ctx["fields"]
    if not ctx["trace_raw"] or "vocab_size" not in fields or "seq" not in job:
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    if not planes:
        return None
    rows = job["rows"] // job["chips"] or 1  # of one microbatch on a chip
    seq, vocab = job["seq"], fields["vocab_size"]
    logits = re.compile(
        rf"\[(?:1,)*(?:{rows},{seq}|{rows * seq}),{vocab}\]")
    ops = trace_reduce.line_events(planes[0], trace_reduce.OPS_LINE)
    by_step = [[e for e in ops if s[1] <= e[1] and e[1] + e[2] <= s[1] + s[2]]
               for s in trace_reduce.step_events(planes[0],
                                                 ctx["step_module"])]

    def is_head(name):
        return bool(logits.search(name)) and not any(
            word in name for word in CONTROL_FLOW)

    counts = [sum(is_head(e[0]) for e in step) for step in by_step]
    most = max(counts, default=0)
    if not most:
        return None
    whole = [step for step, n in zip(by_step, counts) if n == most]
    ns = sum(own for step in whole
             for name, own in trace_reduce.self_times(step).items()
             if is_head(name))
    return ns / 1e6 / len(whole)
