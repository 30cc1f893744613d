"""Encoder: percent of the bf16 peak, the encoder FLOPs of the real tokens
(``work.encoder_flops``) over the time of the benchmark's encode spans."""


def read(ctx):
    s = [(t1 - t0, a["flops"]) for n, t0, t1, a in ctx["spans"]
         if n == "encode"]
    secs = sum(d for d, _ in s)
    if not secs:
        return None
    return 100.0 * sum(f for _, f in s) / secs / ctx["peaks"].flops
