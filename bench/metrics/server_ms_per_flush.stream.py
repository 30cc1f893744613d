"""Server: mean milliseconds of a ``RetrievalServer.query_batch`` call
made by ServeLoop, from the benchmark's span around each call."""


def read(ctx):
    d = [t1 - t0 for name, t0, t1, _ in ctx["spans"] if name == "server_call"]
    return 1e3 * sum(d) / len(d) if d else None
