"""Summed device time of the collective operations (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute, with their
-start and -done halves) on chip 0, per step. Whether it is hidden behind
compute the sum does not say."""


def read(ctx):
    chips = ctx["trace"]
    if not chips or not chips[0]["steps"]:
        return None
    return 1e3 * chips[0]["collective_s"] / chips[0]["steps"]
