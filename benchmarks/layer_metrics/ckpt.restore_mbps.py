"""State bytes over the registry's
``dlrover_ckpt_restore_seconds{source="shm"}`` per restore (the mean
over the timed restores, the warm-up left out), in MB/s (1e6 bytes)."""


def read(ctx):
    h = ctx["registry"]["dlrover_ckpt_restore_seconds{source=shm}"]
    if not h["count"] or not h["sum"]:
        return None
    return ctx["job"]["state_bytes"] / (h["sum"] / h["count"]) / 1e6
