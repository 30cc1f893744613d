"""Scorer kernel: percent of its roofline in the traced server calls
(``layers.maxsim_roofline``)."""

from benchlib import layers


def read(ctx):
    return layers.maxsim_roofline(ctx)
