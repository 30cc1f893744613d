"""jit'd public wrappers for the fused MaxSim top-K kernel.

``maxsim_topk_op`` selects the compiled Pallas TPU kernel on TPU
backends and the interpret-mode kernel elsewhere (bit-identical
semantics).  It is the rescan primitive of the ``shortlist_topk``
pruning path (`repro.core.voronoi.pruning_order_shortlist`): unlike ``jax.lax.top_k`` — whose TopK custom-call
de-partitions the batch axis under GSPMD — the kernel's grid is plain
data parallelism over sample blocks, so the shortlist algorithm stays
shardable over samples/docs on a multi-host mesh.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.maxsim_topk.maxsim_topk import maxsim_topk


@functools.partial(jax.jit, static_argnames=("k", "block_s", "block_t"))
def maxsim_topk_op(samples, tokens, alive, *, k: int, block_s: int = 256,
                   block_t: int = 128):
    """(values (N, k), indices (N, k)) over alive tokens, fused; output
    bit-identical to ``lax.top_k`` of the masked score matrix."""
    return maxsim_topk(samples, tokens, alive, k=k, block_s=block_s,
                       block_t=block_t)
