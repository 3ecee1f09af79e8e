"""The share of the routed (token, expert) pairs whose expert this chip
holds, at the window's last step, mean over the expert layers: the
program's gauge ``dlrover_moe_held_pair_share`` (``models/moe.py``
``publish_stats``, from the step's ``expert_load`` stats, which the
trainer publishes when the registry is read). Held over the router's
width (8 / 64 = 0.125) where the routing is even; it sets how many rows
the held experts compute a step. None without a job, a configuration
that holds a share of its experts, or the gauge (a program that does not
publish it)."""

from benchmarks.harness import op_rules


def read(ctx):
    if "n_routed_experts" not in (ctx.get("fields") or {}):
        return None
    return op_rules.registry_value(ctx, "dlrover_moe_held_pair_share")
