"""Encoder: percent of the token slots the encoder computed in the window
that were padding, from the program's counter of encoded slots
(``launch.serve.EncodeStats``, returned as ``encode_real_tokens`` and
``encode_slots``); nothing to read where the program has no counter."""


def read(ctx):
    c = ctx["counters"]
    slots = c.get("encode_slots")
    if not slots:
        return None
    return 100.0 * (slots - c["encode_real_tokens"]) / slots
