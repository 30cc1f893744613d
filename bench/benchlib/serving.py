"""What the serving drivers share: the server over the configuration's
index, a query pool encoded in set-up, the proxy that times each server
call, and the check of served answers against the exhaustive reference.
"""

from __future__ import annotations

import gc

import numpy as np

from benchlib import common, index, inputs, reference, work

K = 10
ENCODE_BATCH = 256


def open_server(run):
    """(server, packed index, work facts) for the run's configuration."""
    from repro.serve.retrieval import RetrievalServer
    packed = index.load_or_build(run.config, run.params, run.cfg)
    server = RetrievalServer(packed, k=K, n_first=packed.n_docs)
    codec = run.config["codec"]
    facts = {"buckets": [(b.n_docs, int(np.asarray(b.masks).sum()))
                         for b in packed.buckets],
             "codec": codec, "n_centroids": packed.n_centroids,
             "kept_tokens": packed.tokens_kept,
             "l": run.cfg.query_len, "dim": packed.dim,
             "n_docs": packed.n_docs, "backend": server.backend}
    common.info(f"server: backend {server.backend}, {packed.n_docs} docs, "
                f"{packed.tokens_kept} tokens kept, {len(packed.buckets)} "
                f"buckets, {packed.storage()['bytes_stored']} bytes stored")
    return server, packed, facts


def encode_pool(run, n: int, rng) -> np.ndarray:
    """``n`` distinct queries drawn from ``rng``, encoded in fixed-size
    jitted batches: (n, query_len, out_dim) f32 on the host.  Short
    Zipf-drawn queries repeat, so repeats are drawn again: no two queries
    of a pool are alike, and the serving loop's result cache never hits."""
    import jax
    import jax.numpy as jnp
    from repro.models import colbert
    cfg = run.cfg
    ids = np.zeros((0, cfg.query_len), np.int32)
    while len(ids) < n:
        more = inputs.query_ids(rng, n, run.traffic["query_lengths"],
                                run.config["model"])
        both = np.concatenate([ids, more])
        _, first = np.unique(both, axis=0, return_index=True)
        ids = both[np.sort(first)]
    ids = ids[:n]
    enc = jax.jit(lambda p, t: colbert.encode_queries(p, cfg, t)[0]
                  .astype(jnp.float32))
    out = np.empty((n, cfg.query_len, cfg.out_dim), np.float32)
    for lo in range(0, n, ENCODE_BATCH):
        chunk = ids[lo:lo + ENCODE_BATCH]
        pad = ENCODE_BATCH - len(chunk)
        if pad:
            chunk = np.pad(chunk, ((0, pad), (0, 0)))
        out[lo:lo + ENCODE_BATCH] = np.asarray(
            enc(run.params, jnp.asarray(chunk)))[:ENCODE_BATCH - pad]
    return out


def real_rows(q) -> int:
    """Rows of a batch that ``ServeLoop`` did not add as padding.  The
    loop pads a flush to a power of two by repeating its first row, and
    no two queries of a pool are alike, so every row past the first that
    equals it is padding."""
    q = np.asarray(q).reshape(len(q), -1)
    return 1 + int((q[1:] != q[:1]).any(1).sum())


class TimedServer:
    """The server as ``ServeLoop`` sees it, with each ``query_batch``
    timed in a span of the benchmark's own that records the batch's rows
    and its real rows."""

    def __init__(self, server, spans):
        self._server = server
        self._spans = spans

    def query_batch(self, q):
        if not self._spans.on:
            return self._server.query_batch(q)
        with self._spans.span("server_call", n=int(q.shape[0]),
                              n_real=real_rows(q)):
            return self._server.query_batch(q)

    def __getattr__(self, name):
        return getattr(self._server, name)


def call_work(facts: dict, n_q: int) -> tuple[int, int]:
    return work.serve_call_work(n_q, facts["l"], facts["dim"],
                                facts["buckets"], facts["codec"],
                                facts["n_centroids"])


def check_answers(run, packed, queries, ids, scores) -> list:
    """Compare served answers of the sampled queries with the exhaustive
    reference over the stored tokens, at the configuration's precision.
    Call it after the window, with the server gone: it frees the packed
    index before computing.  In a control run (``run.control``) the
    reference one precision lower answers in the program's place.
    Returns the checks [(name, value, limit)]."""
    buckets = reference.stored_arrays(packed)
    n_docs, dim, bits = packed.n_docs, packed.dim, packed.residual_bits
    del packed
    gc.collect()
    docs, masks = reference.stored_tokens(buckets, n_docs, dim, bits)
    precision = run.config["precision"]["serve"]
    ref = reference.maxsim_scores(queries, docs, masks, precision=precision)
    if run.control:
        ids, scores = reference.topk(reference.maxsim_scores(
            queries, docs, masks, precision=reference.lower(precision)), K)
    got = reference.answer_gap(ids, scores, ref)
    return [("bad_ids", got["bad_ids"], 0),
            ("gap", got["gap"], run.config["limits"]["serve_gap"])]
