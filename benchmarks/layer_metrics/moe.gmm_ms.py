"""Device milliseconds a whole step that the experts' grouped-matmul
kernels take, on the chip where they take longest: the ``gmm`` and
``tgmm`` Pallas calls (megablox: the three projections forward and
their two gradients each), found by the name that heads their op events
(``harness/named_kernels.py`` ``kernel_seconds``). A name does not
depend on the layout: this reads the layer where a chip holds whole
experts (PR 30 to PR 32) and where it holds a slice of every expert's
columns (since PR 33). ``moe.expert_ms`` and ``moe.hot_chip_ratio`` look
for an array as wide as ``intermediate_size`` and find none on a chip
that holds a slice. Not the SiLU-and-product fusions between the
kernels, which have no name of their own. None without a trace or such
a kernel on every chip.

Also prints the note ``gmm_ms_by_chip``: every chip's milliseconds a
step, the kernel calls a step, and the largest over the smallest (1.0
where the chips' work does not follow the routing)."""

from benchmarks.harness import named_kernels, program_spans, trace_reduce

KERNELS = ("gmm.", "tgmm.")


def read(ctx):
    if not ctx["trace_raw"]:
        return None
    found = [named_kernels.kernel_seconds(plane, KERNELS, ctx["step_module"])
             for plane in trace_reduce.device_planes(ctx["trace_raw"])]
    if not found or not all(steps for _, _, steps in found):
        return None
    chips = [1e3 * seconds / steps for seconds, _, steps in found]
    if len(chips) > 1:
        program_spans.note(
            "gmm_ms_by_chip", chips=chips,
            calls_a_step=[calls for _, calls, _ in found],
            largest_over_smallest=max(chips) / min(chips))
    return max(chips)
