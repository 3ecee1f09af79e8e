"""Share of the traced span in which no operation ran on the chip: 1 -
(union of the device-op intervals) / (first op's start to last op's
end), averaged over the chips used. Source: profiler trace."""


def read(ctx):
    chips = ctx["trace"]
    if not chips:
        return None
    return 100.0 * sum(1.0 - c["busy_s"] / c["window_s"]
                       for c in chips) / len(chips)
