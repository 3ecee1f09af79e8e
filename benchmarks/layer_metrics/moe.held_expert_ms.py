"""Device milliseconds a whole step that the held experts take, on chip
0: the grouped-matmul kernels by name (``gmm`` and ``tgmm``, megablox:
the three projections forward and their two gradients each) and the ops
between them, found by a rule on their own HLO line: an array of the
expert width (``moe_intermediate_size``, 1408) and none of the hidden
size (the SwiGLU's activation and product, forward, remade and
backward). Not the shared experts (twice the width), the dense layer's
SwiGLU, the sort and gather of rows (hidden-wide), nor the optimizer
over the expert leaves (whose arrays hold both widths). Unlike
``harness/expert_ops.py`` it reads the experts' own width key, not
``intermediate_size``, which here is the dense layer's. None without a
trace, a configuration of shared and routed experts or such an op."""

from benchmarks.harness import op_rules

KERNELS = ("%gmm.", "%tgmm.")


def read(ctx):
    fields = ctx.get("fields") or {}
    if "moe_intermediate_size" not in fields:
        return None
    width, hidden = fields["moe_intermediate_size"], fields["hidden_size"]

    def picks(name):
        if name.startswith(KERNELS):
            return op_rules.PALLAS in name
        dims = op_rules.dims_in_line(name)
        return op_rules.plain_op(name) and width in dims and hidden not in dims

    return op_rules.step_ms(ctx, picks)
