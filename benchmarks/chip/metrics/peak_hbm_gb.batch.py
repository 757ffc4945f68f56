"""Device: peak device memory in use by the end of the window on the
fullest of the cell's chips (``memory_stats()["peak_bytes_in_use"]``),
in GB."""


def read(ctx):
    b = ctx.device.get("memory_peak_bytes")
    return b / 1e9 if b else None
