"""Device milliseconds a step that the expert FFNs take on the chip on
which they take longest: the largest over the chips of the reading that
``harness/expert_ops.py`` defines (the self times, over whole step
programs, of the ops whose HLO line holds an array with a dimension of
the expert width a chip holds, ``intermediate_size`` over
``job["expert_mlp_shards"]``, and none of ``hidden_size``): the grouped
matmuls, which ``moe.gmm_ms`` reads by name, and the SiLU-and-product
fusions between them. From PR 30 to PR 32 a chip computed the pairs
routed to its own experts and the group waited for the slowest in the
layer's all-reduce, so the largest is what the step pays. Since PR 33
every chip computes every routed pair over a quarter of each expert's
columns and the chips read alike (from PR 33 to PR 35 the rule looked for
the whole width and read nothing). ``moe.hot_chip_ratio`` sets it against
the chips' mean. None without a trace or such an op."""

from benchmarks.harness import expert_ops


def read(ctx):
    chips = expert_ops.per_chip_ms(ctx)
    return max(chips) if chips else None
