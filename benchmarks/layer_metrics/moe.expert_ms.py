"""Device milliseconds a step that the expert FFNs take on chip 0: over
the whole step programs of the profile, the self times of the ops whose
own HLO line (result and operands) holds an expert activation: an array
with a dimension of ``intermediate_size`` and none of ``hidden_size``.
Every array of the three expert leaves, their gradients and their
moments holds both widths; a buffer of routed rows or of slots holds
rows by ``intermediate_size`` (``bf16[2,14336,8,512]`` where the program
dispatches through one-hot slots, ``bf16[8192,14336]`` where it sorts
the pairs: rule fixed from a kept trace of each, PR 30). So the ops are
the expert matmuls, forward, remade under remat and backward, as XLA
fusions or as grouped-matmul kernels, with the SiLU and the product
fused into or standing between them; not AdamW over the expert leaves,
not the sum of a weight gradient into its float32 accumulator where that
is an op of its own, and not the sort, the gather or the scatter of
rows, which are ``hidden_size`` wide. A ``while`` that carries such an
array is no op of the experts and is left out. It reads a cell in which
every FFN is an expert FFN and a microbatch's rows times ``top_k`` are
not ``hidden_size``. A step program the profile's edge cut holds fewer
such ops than the others and is left out, as in
``named_kernels.kernel_seconds``. None without a trace or such an op."""

import functools
import re

from benchmarks.harness import trace_reduce

CONTROL_FLOW = (" while(", " conditional(", " call(")
ARRAY = re.compile(r"\w+\[([\d,]+)\]")


def read(ctx):
    fields = ctx["fields"]
    if not ctx["trace_raw"] or "num_local_experts" not in fields:
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    if not planes:
        return None
    ffn, hidden = fields["intermediate_size"], fields["hidden_size"]

    @functools.lru_cache(maxsize=None)   # a name recurs in every step
    def is_expert(name):
        if any(word in name for word in CONTROL_FLOW):
            return False
        for dims in ARRAY.findall(name):
            dims = [int(d) for d in dims.split(",")]
            if ffn in dims and hidden not in dims:
                return True
        return False

    ops = trace_reduce.line_events(planes[0], trace_reduce.OPS_LINE)
    by_step = [[e for e in ops if s[1] <= e[1] and e[1] + e[2] <= s[1] + s[2]]
               for s in trace_reduce.step_events(planes[0],
                                                 ctx["step_module"])]
    counts = [sum(is_expert(e[0]) for e in step) for step in by_step]
    most = max(counts, default=0)
    if not most:
        return None
    whole = [step for step, n in zip(by_step, counts) if n == most]
    ns = sum(own for step in whole
             for name, own in trace_reduce.self_times(step).items()
             if is_expert(name))
    return ns / 1e6 / len(whole)
