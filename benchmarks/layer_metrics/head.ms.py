"""Device milliseconds a step that the output head takes, on the chip
where it takes longest: ``loop.head_ms``'s rule (over the whole step
programs of the profile, the self times of the ops whose own HLO line
holds a logits-shaped array, control flow left out, a step program the
profile's edge cut left out), with the vocabulary dimension taken as
``vocab_size / n`` for every ``n`` that divides the cell's chips and the
vocabulary. So it reads a program whose chips each make the whole
``[seq, vocab_size]`` logits (the expert cell until PR 33: the same 35 ms
on all four) and one that spreads the vocabulary over them (since PR 34:
``[seq, vocab_size / 4]`` a chip) alike.

Those ops are the head's matmul, its log-sum-exp, the two gradient
matmuls and what reshapes the logits between them. Where the sequence is
as long as the hidden width, as in the expert cell (4096 both), the
head's weight is logits-shaped too, so AdamW over it and its gradient's
f32 accumulation are counted as well: in either layout alike, and they
shrink with the vocabulary's share as the logits do. None without a
trace or such an op.

Also prints the note ``head_ms_by_chip``: every chip's milliseconds a
step and the ops a step it counted."""

import re

from benchmarks.harness import program_spans, trace_reduce

CONTROL_FLOW = (" while(", " conditional(", " call(")


def _chip_ms(plane, step_module, is_head):
    """(ms a whole step, ops a step) of the ops ``is_head`` names."""
    ops = trace_reduce.line_events(plane, trace_reduce.OPS_LINE)
    by_step = [[e for e in ops if s[1] <= e[1] and e[1] + e[2] <= s[1] + s[2]]
               for s in trace_reduce.step_events(plane, step_module)]
    counts = [sum(is_head(e[0]) for e in step) for step in by_step]
    most = max(counts, default=0)
    if not most:
        return None
    whole = [step for step, n in zip(by_step, counts) if n == most]
    ns = sum(own for step in whole
             for name, own in trace_reduce.self_times(step).items()
             if is_head(name))
    return ns / 1e6 / len(whole), most


def read(ctx):
    job, fields = ctx["job"], ctx["fields"]
    if not ctx["trace_raw"] or "vocab_size" not in fields or "seq" not in job:
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    chips = job.get("chips", 1)
    rows = job["rows"] // chips or 1  # of one microbatch on a chip
    seq, vocab = job["seq"], fields["vocab_size"]
    shares = "|".join(str(vocab // n) for n in range(1, chips + 1)
                      if chips % n == 0 and vocab % n == 0)
    logits = re.compile(
        rf"\[(?:1,)*(?:{rows},{seq}|{rows * seq}),(?:{shares})\]")

    def is_head(name):
        return bool(logits.search(name)) and not any(
            word in name for word in CONTROL_FLOW)

    found = [_chip_ms(plane, ctx["step_module"], is_head) for plane in planes]
    if not found or not all(found):
        return None
    if len(found) > 1:
        program_spans.note(
            "head_ms_by_chip", chips=[ms for ms, _ in found],
            ops_a_step=[n for _, n in found])
    return max(ms for ms, _ in found)
