"""The hottest held expert's pairs over the mean expert's, at the
window's last step, mean over the expert layers: the program's gauge
``dlrover_moe_held_load_ratio`` (``models/moe.py`` ``publish_stats``).
1.0 where the held experts draw the mean share; a hot expert costs the
grouped matmuls row tiles that the others leave empty. None without a
job, a configuration that holds a share of its experts, or the gauge (a
program that does not publish it)."""

from benchmarks.harness import op_rules


def read(ctx):
    if "n_routed_experts" not in (ctx.get("fields") or {}):
        return None
    return op_rules.registry_value(ctx, "dlrover_moe_held_load_ratio")
