"""Mean of the registry's ``dlrover_ckpt_save_block_seconds`` over the
window's saves: plan + on-device copy + D2H dispatch inside
``ckpt/engine.py``."""


def read(ctx):
    h = ctx["registry"]["dlrover_ckpt_save_block_seconds"]
    return 1e3 * h["sum"] / h["count"] if h["count"] else None
