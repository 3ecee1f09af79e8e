"""Device milliseconds a step that the expert FFNs take on the chip on
which they take longest: the largest over the chips of the reading that
``harness/expert_ops.py`` defines (the self times, over whole step
programs, of the ops whose HLO line holds an array with a dimension of
``intermediate_size`` and none of ``hidden_size``). Since PR 30 a chip
computes the pairs routed to its own experts and the group waits for
the slowest in the layer's all-reduce, so the largest is what the step
pays; chip 0, which this read before PR 32, is the hot chip in one run
and a cold one in the next. ``moe.hot_chip_ratio`` sets it against the
chips' mean. None without a trace or such an op."""

from benchmarks.harness import expert_ops


def read(ctx):
    chips = expert_ops.per_chip_ms(ctx)
    return max(chips) if chips else None
