"""How often a looped model's step runs the forward flash kernel for one
layer application: ``flash_fwd`` calls in a whole step program on chip 0
(``named_kernels.kernel_seconds``'s count) over ``grad_accum x
total_ut_steps x num_hidden_layers``, the applications a step makes.
1.0: the backward pass reuses the forward's result; 2.0: remat runs the
kernel again for it. What the remat choice costs in kernel time, as a
number. None without a trace, a call, or a configuration with passes."""

from benchmarks.harness import named_kernels, trace_reduce


def read(ctx):
    job, fields = ctx["job"], ctx["fields"]
    if not ctx["trace_raw"] or "total_ut_steps" not in fields:
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    if not planes:
        return None
    _, calls, _ = named_kernels.kernel_seconds(
        planes[0], ("flash_fwd.",), ctx["step_module"])
    if not calls:
        return None
    return calls / (job["grad_accum"] * fields["total_ut_steps"]
                    * fields["num_hidden_layers"])
