"""The flash-attention kernels' share of their roofline on chip 0, by
every Pallas kernel of the step.

Kernel time: summed device time, inside whole step programs, of the
trace events whose HLO text says ``custom_call_target="tpu_custom_call"``
whatever their name. ``ops/flash_attention.py``'s three (forward,
backward dq, backward dkv) are the only ones a train step of the cells
on this metric's list holds; the expert cell's step holds the grouped
matmuls too and is not on it. A step program that the profile's edge cut
is left out with its calls, as for the two rooflines by name (until PR 36
this reader counted it as a whole step and read 3 % high).
Least time: the FLOPs attention's forward and backward need for the
whole steps seen, ``job["flash_fwd_flops"] + job["flash_bwd_flops"]`` a
microbatch, times the share of a microbatch's attention that the chip's
calls were given, read from the query operand of the flash kernels found
among them by name; over the published bf16 peak. The cell's family
counts the FLOPs over every call its architecture makes of the kernels
(for a plain decoder ``harness/flops.py``'s 2 + 5 causal score-sized
matmuls a layer, however the kernels split or repeat them) and
``jobs/train.py`` puts them into the job; a family that counts none has
no roofline here. ``harness/named_kernels.py`` has the rule.
"""

from benchmarks.harness import named_kernels


def read(ctx):
    return named_kernels.attention_roofline(
        ctx, named_kernels.ANY_KERNEL, ("flash_fwd_flops", "flash_bwd_flops"))
