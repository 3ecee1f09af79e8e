"""The forward flash-attention kernel's share of its compute roofline on
chip 0: the ``flash_fwd`` Pallas calls inside whole step programs (a
forward the step recomputes counts as time, not as work) against
``flops.ATTN_FWD_MATMULS`` score-sized matmuls a layer and microbatch
over the published bf16 peak. ``harness/named_kernels.py`` has the
rule."""

from benchmarks.harness import flops, named_kernels


def read(ctx):
    return named_kernels.attention_roofline(
        ctx, ("flash_fwd.",), flops.ATTN_FWD_MATMULS)
