"""The flash-attention kernels' share of their roofline on chip 0.

Kernel time: summed device time, inside whole step programs, of the
trace events whose HLO text says ``custom_call_target="tpu_custom_call"``
-- every Pallas kernel of the step. ``ops/flash_attention.py``'s three
(forward, backward dq, backward dkv) are the only ones a train step of
these models holds, and the trace gives them no name of their own
(``kernel_metadata={}``): PERF.md lists the name the program must give.
Least time: the FLOPs attention's forward and backward need for the
whole steps seen, ``job["flash_fwd_flops"] + job["flash_bwd_flops"]`` a
microbatch, over the published bf16 peak. The cell's family counts them
over every call its architecture makes of the kernels (for a plain
decoder ``harness/flops.py``'s 2 + 5 causal score-sized matmuls a layer,
however the kernels split or repeat them) and ``jobs/train.py`` puts
them into the job; a family that counts none has no roofline here. The
bound is compute: at sequence 4096 and head size 128 a call needs some
hundreds of FLOPs for each byte it must move.
"""

from benchmarks.harness import trace_reduce

PALLAS = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    job = ctx["job"]
    if not ctx["trace_raw"] or not ctx["peaks"] or not (
            job["flash_fwd_flops"] + job["flash_bwd_flops"]):
        return None
    planes = trace_reduce.device_planes(ctx["trace_raw"])
    if not planes:
        return None
    seconds, _, steps = trace_reduce.kernel_seconds(
        planes[0], PALLAS, ctx["step_module"])
    if not seconds:
        return None
    least = (steps * job["grad_accum"]
             * (job["flash_fwd_flops"] + job["flash_bwd_flops"])
             / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / seconds
