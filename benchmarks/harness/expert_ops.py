"""Device milliseconds a step that the expert FFNs take, chip by chip.

Over the whole step programs of a chip's plane, the self times of the
ops whose own HLO line (result and operands) holds an expert activation:
an array with a dimension of the expert width a chip holds and none of
``hidden_size``. That width is ``intermediate_size`` over the product of
the mesh axes that ``DEFAULT_RULES["expert_mlp"]`` names, which
``jobs/train.py`` puts into ``job["expert_mlp_shards"]`` (PR 35): 14336
where whole experts sit on chips or there is no mesh, 3584 since PR 33
spread every expert's columns over ``ep`` 4. A ``job`` without the count
(the hand-made traces of the older layouts) reads the whole width. Every
array of the three expert leaves, their gradients
and their moments holds both widths; a buffer of routed rows or of slots
holds rows by that width (``bf16[2,14336,8,512]`` where the program
dispatches through one-hot slots, ``bf16[8192,14336]`` where it sorts the
pairs, ``bf16[8192,3584]`` where it sorts them into sliced experts: rule
fixed from a kept trace of each, PR 30, PR 35). So the ops
are the expert matmuls, forward, remade under remat and backward, as XLA
fusions or as grouped-matmul kernels, with the SiLU and the product
fused into or standing between them; not AdamW over the expert leaves,
not the sum of a weight gradient into its float32 accumulator where that
is an op of its own, and not the sort, the gather or the scatter of
rows, which are ``hidden_size`` wide. A ``while`` that carries such an
array is no op of the experts and is left out. It reads a cell in which
every FFN is an expert FFN and a microbatch's rows times ``top_k`` are
not ``hidden_size``. A step program the profile's edge cut holds fewer
such ops than the others and is left out, as in
``named_kernels.kernel_seconds``.

From PR 30 to PR 32 a chip computed the pairs routed to its own experts,
so the chips' readings differed by the routing and the step waited for
the largest (``moe.expert_ms``); the largest over their mean is
``moe.hot_chip_ratio``. Since PR 33 every chip computes every routed pair
over its columns, and the ratio guards against a layout in which a chip
waits again."""

import functools
import re
from typing import List, Optional

from benchmarks.harness import trace_reduce

CONTROL_FLOW = (" while(", " conditional(", " call(")
ARRAY = re.compile(r"\w+\[([\d,]+)\]")


def _step_ms(plane, step_module, is_expert) -> List[float]:
    """The expert ops' milliseconds in each whole step program of one
    chip, in the order the steps ran."""
    ops = trace_reduce.line_events(plane, trace_reduce.OPS_LINE)
    by_step = [[e for e in ops if s[1] <= e[1] and e[1] + e[2] <= s[1] + s[2]]
               for s in trace_reduce.step_events(plane, step_module)]
    counts = [sum(is_expert(e[0]) for e in step) for step in by_step]
    most = max(counts, default=0)
    if not most:
        return []
    return [sum(own for name, own in trace_reduce.self_times(step).items()
                if is_expert(name)) / 1e6
            for step, n in zip(by_step, counts) if n == most]


def per_chip_step_ms(ctx) -> Optional[List[List[float]]]:
    """A list a chip, in device order, of the expert milliseconds of each
    of its whole steps; None without a trace, an expert configuration or
    such an op on every chip. Kept in ``ctx``, which a run's readers
    share, beside what it was read from: the planes are cut into steps
    once."""
    fields = ctx["fields"]
    if not ctx["trace_raw"] or "num_local_experts" not in fields:
        return None
    kept = ctx.get("expert_ops")
    if not kept or kept[0] is not ctx["trace_raw"]:
        kept = ctx["expert_ops"] = (ctx["trace_raw"], _cut_into_steps(ctx))
    return kept[1]


def _cut_into_steps(ctx):
    ffn = (ctx["fields"]["intermediate_size"]
           // (ctx.get("job") or {}).get("expert_mlp_shards", 1))
    hidden = ctx["fields"]["hidden_size"]

    @functools.lru_cache(maxsize=None)   # a name recurs in every step
    def is_expert(name):
        if any(word in name for word in CONTROL_FLOW):
            return False
        for dims in ARRAY.findall(name):
            dims = [int(d) for d in dims.split(",")]
            if ffn in dims and hidden not in dims:
                return True
        return False

    chips = [_step_ms(plane, ctx["step_module"], is_expert)
             for plane in trace_reduce.device_planes(ctx["trace_raw"])]
    return chips if chips and all(chips) else None


def per_chip_ms(ctx) -> Optional[List[float]]:
    """A chip's mean over its whole steps, in device order."""
    chips = per_chip_step_ms(ctx)
    return chips and [sum(steps) / len(steps) for steps in chips]
