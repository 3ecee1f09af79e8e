"""The two backward flash-attention kernels' share of their compute
roofline on chip 0: the ``flash_bwd_dq`` and ``flash_bwd_dkv`` Pallas
calls inside whole step programs against ``job["flash_bwd_flops"]`` a
microbatch, the second of the cell's family's ``flash_attention_flops``
(the recomputed scores among them, once), times the share of a
microbatch's attention that the chip's calls were given (read from their
own query operand), over the published bf16 peak.
``harness/named_kernels.py`` has the rule."""

from benchmarks.harness import named_kernels


def read(ctx):
    return named_kernels.attention_roofline(
        ctx, ("flash_bwd_dq.", "flash_bwd_dkv."), ("flash_bwd_flops",))
