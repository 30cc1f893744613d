"""Late-interaction (ColBERT) scoring ops — Eq. 1 of the paper.

Conventions used across the framework:
  * a *document* is a padded matrix ``d_emb`` of shape (m_max, dim) with a
    boolean ``d_mask`` of shape (m_max,) marking real tokens;
  * batches stack on the leading axis: (n_docs, m_max, dim);
  * queries are (l, dim) (+ optional mask) — ColBERT queries are
    fixed-length (query augmentation with [MASK]) so masks default to all
    true.

``NEG_INF`` is a large-but-finite sentinel so masked maxes never produce
NaNs via (-inf) - (-inf).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def maxsim(q_emb: jax.Array, d_emb: jax.Array, d_mask: jax.Array | None = None,
           q_mask: jax.Array | None = None) -> jax.Array:
    """ColBERT(Q, D) = sum_q max_d q.d  for one (query, doc) pair."""
    scores = q_emb @ d_emb.T                       # (l, m)
    if d_mask is not None:
        scores = jnp.where(d_mask[None, :], scores, NEG_INF)
    best = scores.max(axis=-1)                     # (l,)
    if q_mask is not None:
        best = jnp.where(q_mask, best, 0.0)
    return best.sum()


def maxsim_batch_docs(q_emb: jax.Array, d_embs: jax.Array,
                      d_masks: jax.Array | None = None,
                      q_mask: jax.Array | None = None) -> jax.Array:
    """Score one query against a batch of docs: (n_docs,)."""
    fn = lambda d, m: maxsim(q_emb, d, m, q_mask)
    if d_masks is None:
        d_masks = jnp.ones(d_embs.shape[:2], bool)
    return jax.vmap(fn)(d_embs, d_masks)


def maxsim_pairs(q_embs: jax.Array, d_embs: jax.Array,
                 d_masks: jax.Array | None = None,
                 q_masks: jax.Array | None = None) -> jax.Array:
    """Paired scoring: query i vs doc i -> (batch,)."""
    if d_masks is None:
        d_masks = jnp.ones(d_embs.shape[:2], bool)
    if q_masks is None:
        q_masks = jnp.ones(q_embs.shape[:2], bool)
    return jax.vmap(maxsim)(q_embs, d_embs, d_masks, q_masks)


def maxsim_matrix(q_embs: jax.Array, d_embs: jax.Array,
                  d_masks: jax.Array | None = None,
                  q_masks: jax.Array | None = None) -> jax.Array:
    """All-pairs scoring: (n_q, n_d) score matrix (in-batch negatives /
    reranking).  Memory O(n_q * n_d * l * m) is avoided by contracting the
    token axes per (q, d) pair via einsum + masked max.
    """
    # scores[a, b, i, j] = q_embs[a, i] . d_embs[b, j]
    s = jnp.einsum("aid,bjd->abij", q_embs, d_embs)
    if d_masks is not None:
        s = jnp.where(d_masks[None, :, None, :], s, NEG_INF)
    best = s.max(axis=-1)                          # (n_q, n_d, l)
    if q_masks is not None:
        best = jnp.where(q_masks[:, None, :], best, 0.0)
    return best.sum(axis=-1)


def top2_scores(samples: jax.Array, d_emb: jax.Array,
                d_mask: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-sample (best, second, argbest, argsecond) of samples @ d_emb.T.

    This is the reference path of the Voronoi estimator, and at k=2 the
    oracle of the Pallas ``maxsim_topk`` kernel.
    Shapes: samples (N, dim), d_emb (m, dim), d_mask (m,) ->
    ((N,), (N,), (N,), (N,)).
    """
    scores = samples @ d_emb.T                     # (N, m)
    scores = jnp.where(d_mask[None, :], scores, NEG_INF)
    best_idx = jnp.argmax(scores, axis=-1)
    best = jnp.take_along_axis(scores, best_idx[:, None], axis=-1)[:, 0]
    masked = scores.at[jnp.arange(scores.shape[0]), best_idx].set(NEG_INF)
    second_idx = jnp.argmax(masked, axis=-1)
    second = jnp.take_along_axis(masked, second_idx[:, None], axis=-1)[:, 0]
    return best, second, best_idx, second_idx
