"""Operations and bytes each measured layer needs, computed from shapes.

Every function counts the least work of the algorithm, not what an
implementation happens to do: padding, recomputation and decode
arithmetic are left out, so a share computed from these counts cannot
pass 100% unless the time leaves out part of the work.
"""

from __future__ import annotations

FP32 = 4


def maxsim_flops(n_q: int, l: int, dim: int, tokens: int) -> int:
    """MaxSim scoring of ``n_q`` queries of ``l`` tokens against ``tokens``
    kept doc tokens: one ``dim``-long dot product (a multiply and an add
    per element) for every (query token, doc token) pair.  The max and
    the sum over query tokens are not counted."""
    return 2 * n_q * l * dim * tokens


def token_bytes(dim: int, codec: dict) -> int:
    """Bytes one kept doc token needs in HBM: the embedding (fp32), or
    its one-byte centroid id, packed residual and fp32 scale (residual
    codec).  Padding slots and keep masks are not counted."""
    if codec["compression"] == "none":
        return dim * FP32
    if codec["compression"] == "residual":
        return 1 + dim * codec["residual_bits"] // 8 + FP32
    raise ValueError(f"no byte count for codec {codec!r}")


def maxsim_bytes(n_q: int, l: int, dim: int, n_docs: int, tokens: int,
                 codec: dict, n_centroids: int = 0) -> int:
    """Least HBM traffic of scoring one bucket of ``n_docs`` documents
    that keep ``tokens`` tokens: every kept token and the bucket's
    codebook read once, the query block read once, the (n_q, n_docs)
    scores written once."""
    docs = tokens * token_bytes(dim, codec)
    book = n_centroids * dim * FP32
    return docs + book + n_q * l * dim * FP32 + n_q * n_docs * FP32


def serve_call_work(n_q: int, l: int, dim: int, buckets, codec: dict,
                    n_centroids: int = 0) -> tuple[int, int]:
    """(flops, bytes) of scoring ``n_q`` real queries (padding rows left
    out) against every bucket of a packed index; ``buckets`` is a list of
    (n_docs, kept tokens)."""
    flops = sum(maxsim_flops(n_q, l, dim, t) for _, t in buckets)
    nbytes = sum(maxsim_bytes(n_q, l, dim, n, t, codec, n_centroids)
                 for n, t in buckets)
    return flops, nbytes


def serve_query_flops(l: int, dim: int, kept_tokens: int) -> int:
    """Least FLOPs of answering one query exhaustively: every query token
    dotted with every kept doc token of the index."""
    return 2 * l * dim * kept_tokens


def encoder_flops(n_real: int, model: dict) -> int:
    """Forward FLOPs of encoding one document of ``n_real`` real tokens
    (attention among real tokens only): per layer the Q, K, V and output
    projections (8 n d^2), the score and value products (4 n^2 d) and the
    three SwiGLU matrices (6 n d d_ff); then the projection to
    ``out_dim``.  Norms, softmax and the embedding lookup are left out."""
    n, d, f = n_real, model["d_model"], model["d_ff"]
    per_layer = 8 * n * d * d + 4 * n * n * d + 6 * n * d * f
    return model["n_layers"] * per_layer + 2 * n * d * model["out_dim"]


def voronoi_least_flops(n_real: int, n_samples: int, dim: int) -> int:
    """The least work of the Monte-Carlo Voronoi estimate of one document:
    one pass of sample x token similarities (Eq. 8 needs each sample's
    best and second-best token at least once)."""
    return 2 * n_samples * dim * n_real


def least_time(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The least time the chip could take for the work, and which peak
    bounds it ("compute" or "memory")."""
    tc, tm = flops / peaks.flops, nbytes / peaks.hbm_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
