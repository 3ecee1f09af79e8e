"""Model FLOP/s utilization: tokens per second of the window (less the
seconds the profiler's own start and stop held the loop) times the
FLOPs a token's forward and backward need (the cell's family's
``train_flops_per_token``, in ``job``: matmul parameters x 6 + causal
attention, active experts only, no recompute),
over chips x the published bf16 peak (``harness/peaks.py``)."""


def read(ctx):
    if not ctx["peaks"]:
        return None  # a rehearsal: no chip, no peak, no utilization
    job = ctx["job"]
    achieved = job["tokens_per_s_untraced"] * job["train_flops_per_token"]
    return 100.0 * achieved / (job["chips"] * ctx["peaks"]["bf16_flops_per_s"])
