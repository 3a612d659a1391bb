"""Peak device memory after the window: ``memory_stats()["peak_bytes_in_use"]`` of the
fullest chip, read before the reference runs."""


def read(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 2**30
