"""Device: percent of the traced window in which no operation ran on
the chip (``trace.idle_share``)."""

from benchlib import layers


def read(ctx):
    return layers.idle_share(ctx)
