"""Front end: real (unpadded) rows per ServeLoop flush over the window,
from the loop's own counters (cache misses over flushes)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("flushes"):
        return None
    return c["cache_misses"] / c["flushes"]
