"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read when
the window closes and before the reference runs."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
