"""Whole build step: percent of the bf16 peak, the encoder FLOPs plus one
sample x token similarity pass per document (``work.encoder_flops``,
``work.voronoi_least_flops``) of the traced slabs over the traced window."""


def read(ctx):
    t = ctx["traced"]
    if not t or t["seconds"] <= 0:
        return None
    return 100.0 * t["flops"] / t["seconds"] / ctx["peaks"].flops
