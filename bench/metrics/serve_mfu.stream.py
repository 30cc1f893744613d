"""Whole serving step: percent of the bf16 peak over the traced window
(``layers.serve_mfu``)."""

from benchlib import layers


def read(ctx):
    return layers.serve_mfu(ctx)
