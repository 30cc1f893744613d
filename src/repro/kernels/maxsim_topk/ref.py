"""Pure-jnp oracle for the fused MaxSim top-K kernel.

Given samples S (N, dim), tokens D (m, dim) and an alive mask (m,),
return each sample's top-k scores and token indices of S @ D.T over
alive tokens via ``jax.lax.top_k`` on the materialized masked score
matrix — sorted descending, ties to the lowest index.  The kernel, which
is the shortlist pruning path's rescan, must match it bit-for-bit, ties
included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


def maxsim_topk_ref(samples, tokens, alive, k):
    scores = samples.astype(jnp.float32) @ tokens.astype(jnp.float32).T
    scores = jnp.where(alive[None, :], scores, NEG)
    vals, idxs = jax.lax.top_k(scores, k)
    return vals, idxs.astype(jnp.int32)
