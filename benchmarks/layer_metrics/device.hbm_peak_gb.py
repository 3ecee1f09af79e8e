"""``device.memory_stats()["peak_bytes_in_use"]`` of the fullest chip,
read when the window ends (before the restore that follows it), in GB
(1e9 bytes)."""


def read(ctx):
    peak = max(ctx["memory"]["window_peak_bytes"], default=0)
    return peak / 1e9 if peak else None
