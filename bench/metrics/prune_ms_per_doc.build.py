"""Pruning: milliseconds of ``prune_corpus`` per document, from the
benchmark's prune spans over the window."""


def read(ctx):
    s = [(t1 - t0, a["docs"]) for n, t0, t1, a in ctx["spans"]
         if n == "prune"]
    docs = sum(d for _, d in s)
    return 1e3 * sum(d for d, _ in s) / docs if docs else None
