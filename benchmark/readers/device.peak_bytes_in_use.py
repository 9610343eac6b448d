"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device, read
when the window closes (before the reference runs)."""


def read(ctx):
    return ctx["memory_peak_bytes"] or None
