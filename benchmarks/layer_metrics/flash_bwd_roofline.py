"""The two backward flash-attention kernels' share of their compute
roofline on chip 0: the ``flash_bwd_dq`` and ``flash_bwd_dkv`` Pallas
calls inside whole step programs against ``flops.ATTN_BWD_MATMULS``
score-sized matmuls a layer and microbatch (the recomputed scores among
them, once) over the published bf16 peak. ``harness/named_kernels.py``
has the rule."""

from benchmarks.harness import flops, named_kernels


def read(ctx):
    return named_kernels.attention_roofline(
        ctx, ("flash_bwd_dq.", "flash_bwd_dkv."), flops.ATTN_BWD_MATMULS)
