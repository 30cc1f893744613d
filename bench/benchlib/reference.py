"""Plain references the benchmark holds the program's answers to.

Nothing here imports the program.  The serving reference is an
exhaustive MaxSim over the tokens a packed index stores, decoded here
from the stored arrays; the build reference is the Voronoi greedy of
Alg. 1 with its corpus-wide merge (Sec. 4.2), written out step by step.
Each computes at a precision the configuration states; a control
computes one step lower, and has to come out as not correct.  The
precisions are spelled out on rounded operands, so that they read the
same on any platform: ``highest`` is fp32; ``high`` is the MXU's three
bf16 passes (hi*hi + hi*lo + lo*hi); ``bf16`` and ``fp8`` round every
operand to the 7 or 3 mantissa bits of bfloat16 or float8_e4m3.  Products
of rounded operands are exact in fp32, and every sum accumulates in fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# From the highest precision down: a control runs one step below the
# precision the configuration states.
PRECISIONS = ("highest", "high", "bf16", "fp8")
_HIGHEST = jax.lax.Precision.HIGHEST
# Mantissa bits an operand keeps: bfloat16's 7, float8_e4m3's 3.
_MANTISSA = {"bf16": 7, "fp8": 3}


def round_mantissa(x, bits: int):
    """fp32 ``x`` rounded to ``bits`` mantissa bits, to nearest, ties to
    even, keeping fp32's exponent range (no subnormal flush or
    saturation).  Done on the bits, so no platform's type conversions
    can skip it."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    odd = (u >> drop) & 1
    u = (u + (1 << (drop - 1)) - 1 + odd) & ~jnp.uint32((1 << drop) - 1)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def lower(precision: str) -> str:
    """The next precision below ``precision``: the control's."""
    return PRECISIONS[PRECISIONS.index(precision) + 1]


def _parts(x, precision: str) -> list:
    """``x`` as the matmul operand(s) that ``precision`` multiplies."""
    if precision == "highest":
        return [x]
    if precision == "high":
        hi = round_mantissa(x, _MANTISSA["bf16"])
        return [hi, round_mantissa(x - hi, _MANTISSA["bf16"])]
    return [round_mantissa(x, _MANTISSA[precision])]


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum`` of two fp32 operands at one of PRECISIONS."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    pa, pb = _parts(a, precision), _parts(b, precision)
    terms = [(x, y) for i, x in enumerate(pa) for j, y in enumerate(pb)
             if i + j < 2]                   # "high" drops lo*lo
    out = jnp.einsum(spec, *terms[0], precision=_HIGHEST)
    for x, y in terms[1:]:
        out = out + jnp.einsum(spec, x, y, precision=_HIGHEST)
    return out


def round_weights(x, precision: str):
    """``x`` as a matmul at ``precision`` weighs it against exact 0/1
    operands (a one-hot segment sum): the sum of its rounded parts."""
    return sum(_parts(x, precision))


# -- serving -------------------------------------------------------------

def decode_residual(codes, resq, scale, codebook, bits: int) -> np.ndarray:
    """codebook[codes] + (u - 2**(bits-1)) * scale, where value ``d`` of a
    token's packed residual sits in byte ``d // (8 // bits)`` at shift
    ``(d % (8 // bits)) * bits``."""
    vpb = 8 // bits
    shifts = np.arange(vpb, dtype=np.int32) * bits
    u = (np.asarray(resq, np.int32)[..., None] >> shifts) & ((1 << bits) - 1)
    u = u.reshape(*u.shape[:-2], -1)
    q = (u - 2 ** (bits - 1)).astype(np.float32)
    cent = np.asarray(codebook, np.float32)[np.asarray(codes, np.int64)]
    return cent + q * np.asarray(scale, np.float32)


def stored_arrays(packed) -> list[dict]:
    """The stored arrays of every bucket of a packed index, copied to the
    host: what the reference decodes once the program's state is gone."""
    keys = ("doc_ids", "masks", "embs", "codes", "resq", "rscale",
            "codebook")
    return [{k: (None if getattr(b, k) is None
                 else np.asarray(jax.device_get(getattr(b, k))))
             for k in keys} for b in packed.buckets]


def stored_tokens(buckets: list[dict], n_docs: int, dim: int,
                  bits: int = 0):
    """Decode stored buckets and lay them back out per document:
    (n_docs, cap_max, dim) f32 and (n_docs, cap_max) bool masks."""
    cap = max(b["masks"].shape[1] for b in buckets)
    docs = np.zeros((n_docs, cap, dim), np.float32)
    masks = np.zeros((n_docs, cap), bool)
    for b in buckets:
        c = b["masks"].shape[1]
        if b["embs"] is not None:
            e = b["embs"].astype(np.float32)
        else:
            e = decode_residual(b["codes"], b["resq"], b["rscale"],
                                b["codebook"], bits)
        docs[b["doc_ids"], :c] = e
        masks[b["doc_ids"], :c] = b["masks"]
    return docs, masks


@functools.partial(jax.jit, static_argnames=("precision",))
def _maxsim_block(q, d, mk, *, precision):
    s = einsum("qld,nmd->qnlm", q, d, precision)
    return jnp.where(mk[None, :, None, :], s, -jnp.inf).max(-1).sum(-1)


def maxsim_scores(q, docs, masks, *, precision: str = "highest",
                  q_chunk: int = 32, d_chunk: int = 256) -> np.ndarray:
    """Exhaustive MaxSim of every query against every document,
    (n_q, n_docs) f32 on the host, in blocks that fit the device."""
    q = np.asarray(q, np.float32)
    out = np.empty((q.shape[0], docs.shape[0]), np.float32)
    for a in range(0, q.shape[0], q_chunk):
        qb = jnp.asarray(q[a:a + q_chunk])
        for b in range(0, docs.shape[0], d_chunk):
            out[a:a + q_chunk, b:b + d_chunk] = np.asarray(_maxsim_block(
                qb, jnp.asarray(docs[b:b + d_chunk]),
                jnp.asarray(masks[b:b + d_chunk]), precision=precision))
    return out


def topk(scores: np.ndarray, k: int):
    """Top-k of each row, descending, ties to the lowest doc id."""
    order = np.lexsort((np.broadcast_to(np.arange(scores.shape[1]),
                                        scores.shape), -scores), axis=1)
    ids = order[:, :k]
    return ids, np.take_along_axis(scores, ids, 1)


def answer_gap(ids, scores, ref_scores: np.ndarray) -> dict:
    """How far served top-k answers depart from the reference.

    ``gap`` is the widest of two distances, over every rank of every
    answer: how far a served score lies from the reference score of the
    document it names, and how far that document's reference score lies
    below the reference's own score at that rank.  A near-tie swap of two
    documents costs a rounding error; a wrong or altered document costs
    the score distance to the right one.  ``bad_ids`` counts answers
    naming a document twice or one that does not exist."""
    ids = np.asarray(ids, np.int64)
    scores = np.asarray(scores, np.float32)
    n_docs = ref_scores.shape[1]
    k = ids.shape[1]
    valid = (ids >= 0) & (ids < n_docs)
    dup = np.array([len(set(r.tolist())) != k for r in ids])
    bad = int(((~valid).any(1) | dup).sum())
    safe = np.where(valid, ids, 0)
    ref_of = np.take_along_axis(ref_scores, safe, 1)
    _, ref_best = topk(ref_scores, k)
    gap = np.maximum(np.abs(scores - ref_of), ref_best - ref_of)
    gap = np.where(valid & np.isfinite(scores), gap, np.inf)
    return {"gap": float(gap.max()) if gap.size else 0.0, "bad_ids": bad}


# -- build: the Voronoi greedy (Alg. 1) and the global merge ---------------

@functools.partial(jax.jit, static_argnames=("precision",))
def _greedy_batch(embs, masks, samples, *, precision):
    """Ranks and errors-at-removal of Alg. 1 for a batch of documents of
    one width: each step recomputes every sample's best and second-best
    alive token, sums the gaps of each token's cell (Eq. 8), and removes
    the alive token of least error (lowest index on ties), never the last
    one.  Tokens never removed keep rank ``w`` and error +inf.  At a
    precision below fp32 both products round their operands: the sample
    x token similarities, and the gaps as weights of the cell sums."""
    n = samples.shape[0]
    w = embs.shape[1]
    tok = jnp.arange(w)

    def one(emb, mask):
        s = einsum("nd,md->nm", samples, emb, precision)
        s = jnp.where(mask[None], s, -jnp.inf)

        def step(carry, pos):
            alive, rank, err_at = carry
            sa = jnp.where(alive[None], s, -jnp.inf)
            bi = jnp.argmax(sa, 1)
            best = jnp.max(sa, 1)
            second = jnp.max(jnp.where(tok[None] == bi[:, None], -jnp.inf,
                                       sa), 1)
            gap = jnp.where(jnp.isfinite(second), best - second, 0.0)
            gap = round_weights(gap, precision)
            err = jnp.zeros((w,), jnp.float32).at[bi].add(gap) / n
            err = jnp.where(alive, err, jnp.inf)
            j = jnp.argmin(err)
            kill = (jnp.sum(alive) > 1) & (tok == j)
            return (alive & ~kill, jnp.where(kill, pos, rank),
                    jnp.where(kill, err[j], err_at)), None

        init = (mask, jnp.full((w,), w, jnp.int32),
                jnp.full((w,), jnp.inf, jnp.float32))
        (_, rank, err_at), _ = jax.lax.scan(step, init,
                                            jnp.arange(w - 1, dtype=jnp.int32))
        return rank, err_at

    return jax.vmap(one)(embs, masks)


def _width(n_real: int, m: int, min_width: int = 8) -> int:
    w = 1
    while w < max(n_real, 1):
        w *= 2
    return min(m, max(min_width, w))


def voronoi_orders(embs, masks, samples, *, precision: str = "highest"):
    """(rank, err) of every document, (n, m) each: documents run in
    groups of equal power-of-two width (a document's order depends only
    on its own alive tokens, so the width changes nothing)."""
    embs = np.asarray(embs, np.float32)
    masks = np.asarray(masks, bool)
    n, m = masks.shape
    last = np.where(masks.any(1), m - np.argmax(masks[:, ::-1], 1), 0)
    widths = np.array([_width(int(x), m) for x in last])
    rank = np.full((n, m), m, np.int64)
    err = np.full((n, m), np.inf, np.float32)
    samples = jnp.asarray(samples, jnp.float32)
    for w in np.unique(widths):
        ix = np.flatnonzero(widths == w)
        r, e = _greedy_batch(jnp.asarray(embs[ix, :w]),
                             jnp.asarray(masks[ix, :w]), samples,
                             precision=precision)
        r, e = np.asarray(r), np.asarray(e)
        rank[ix, :w] = np.where(r >= w, m, r)
        err[ix, :w] = e
    return rank, err


def global_keep(rank, err, masks, keep_fraction: float) -> np.ndarray:
    """Sec. 4.2 merge: each document's errors are made monotone along its
    own removal order (running max), and the ``n_total - ceil(keep *
    n_total)`` smallest keys of the whole batch are pruned, ties in flat
    order.  Survivors (error +inf) are never pruned."""
    masks = np.asarray(masks, bool)
    keys = np.full(masks.shape, np.inf, np.float32)
    for d in range(masks.shape[0]):
        gone = np.flatnonzero(masks[d] & np.isfinite(err[d]))
        order = gone[np.argsort(rank[d, gone], kind="stable")]
        keys[d, order] = np.maximum.accumulate(err[d, order])
    n_total = int(masks.sum())
    n_prune = max(n_total - int(np.ceil(keep_fraction * n_total)), 0)
    pruned = np.zeros(keys.size, bool)
    pruned[np.argsort(keys.reshape(-1), kind="stable")[:n_prune]] = True
    return masks & ~pruned.reshape(masks.shape)


def keep_reference(embs, masks, samples, keep_fraction: float, *,
                   precision: str = "highest") -> np.ndarray:
    rank, err = voronoi_orders(embs, masks, samples, precision=precision)
    return global_keep(rank, err, masks, keep_fraction)


def keep_mismatch(keep, keep_ref, masks) -> float:
    """Share of real tokens whose keep decision differs."""
    masks = np.asarray(masks, bool)
    diff = (np.asarray(keep, bool) != np.asarray(keep_ref, bool)) & masks
    return float(diff.sum() / max(int(masks.sum()), 1))


def pack_mismatch(buckets: list[dict], embs, keep) -> int:
    """Documents whose stored fp32 tokens are not exactly their kept
    embeddings, in order, or that are stored other than once."""
    embs = np.asarray(embs, np.float32)
    keep = np.asarray(keep, bool)
    seen = np.zeros(keep.shape[0], np.int64)
    bad = 0
    for b in buckets:
        for row, d in enumerate(b["doc_ids"].tolist()):
            seen[d] += 1
            got = b["embs"][row][b["masks"][row]]
            if not np.array_equal(got, embs[d][keep[d]]):
                bad += 1
    return bad + int((seen != 1).sum())
