"""The forward flash-attention kernel's share of its compute roofline on
chip 0: the ``flash_fwd`` Pallas calls inside whole step programs (a
forward the step recomputes counts as time, not as work) against
``job["flash_fwd_flops"]`` a microbatch, the first of the cell's family's
``flash_attention_flops``, times the share of a microbatch's attention
that the chip's calls were given (read from their own query operand),
over the published bf16 peak. ``harness/named_kernels.py`` has the rule."""

from benchmarks.harness import named_kernels


def read(ctx):
    return named_kernels.attention_roofline(
        ctx, ("flash_fwd.",), ("flash_fwd_flops",))
