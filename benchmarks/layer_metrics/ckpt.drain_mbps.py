"""State bytes over the mean of the registry's
``dlrover_ckpt_drain_seconds`` for the drains that ended after the window
opened: device -> host -> the shm frame, in MB/s (1e6 bytes)."""


def read(ctx):
    h = ctx["registry"]["dlrover_ckpt_drain_seconds"]
    if not h["count"] or not h["sum"]:
        return None
    return ctx["job"]["state_bytes"] / (h["sum"] / h["count"]) / 1e6
