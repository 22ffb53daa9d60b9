"""Device layer: the chip's peak bytes in use over its byte limit, read
from ``memory_stats`` after the window and before the program's state
is freed."""


def read(ctx):
    m = ctx.memory
    if not m.get("peak_bytes_in_use") or not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
