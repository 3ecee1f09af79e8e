"""How unevenly the experts' work lies over the chips of the ``ep``
group: the largest over the chips of ``moe.expert_ms``'s per-chip reading
(``harness/expert_ops.py``) over their mean. 1.0 where every chip does
the same share; the step waits for the largest, so ``1 - 1 / ratio`` of
``moe.expert_ms`` is what an even split would give back. With whole
experts on chips (PR 30 to PR 32) it followed the routing and read 1.61;
since PR 33 every chip computes every routed pair over its own columns,
so it reads about 1.00 whatever the routing does, and guards against a
layout in which a chip waits again. None without a trace, such an op, or
a second chip.

Also prints the note ``expert_ms_by_chip``: each chip's milliseconds a
step, and the ratio in the first and in the last whole step traced
(whether the optimizer pulls the routing apart as the window goes on)."""

from benchmarks.harness import expert_ops, program_spans


def _ratio(values):
    return max(values) / (sum(values) / len(values))


def read(ctx):
    steps = expert_ops.per_chip_step_ms(ctx)
    if not steps or len(steps) < 2:
        return None
    chips = [sum(s) / len(s) for s in steps]
    aligned = len({len(s) for s in steps}) == 1
    program_spans.note(
        "expert_ms_by_chip", chips=chips, whole_steps=len(steps[0]),
        first_step_ratio=_ratio([s[0] for s in steps]) if aligned else None,
        last_step_ratio=_ratio([s[-1] for s in steps]) if aligned else None)
    return _ratio(chips)
