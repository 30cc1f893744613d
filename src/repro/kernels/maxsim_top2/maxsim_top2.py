"""Fused MaxSim top-2 Pallas TPU kernel — the Voronoi-pruning hot loop.

Computes, for N sample queries against m document tokens, the per-sample
(best, second-best, argbest, argsecond) of the dot-product scores
**without ever materializing the (N, m) score matrix in HBM**
(DESIGN.md §3).

Tiling:
  grid = (N / BS, m / BT); the token axis is the minor (sequential) grid
  dimension, so each sample block's running (best, second, argbest,
  argsecond) tuple lives in its output VMEM blocks across the token-tile
  sweep — the classic flash-attention accumulator pattern, applied to a
  top-2 reduction instead of a softmax.

  * samples tile  (BS, dim)  — rows, MXU-aligned (BS multiple of 8,
    dim padded to 128 lanes by the wrapper);
  * tokens tile   (BT, dim)  — BT multiple of 128 for the transposed
    MXU matmul;
  * scores tile   (BS, BT)   — VREG-resident f32 accumulator;
  * alive mask    (1, BT)    int32 — dead/padded tokens forced to -1e30.

The top-2 merge across tiles is associative: for disjoint tile results
the merged best is the larger of the two bests, and the merged second is
the larger of {loser of the bests, winner's own second}.  Ties resolve
to the earlier tile / lower index for both best AND second, matching the
jnp.argmax tie-breaking of ref.py exactly.

Iterative Voronoi pruning re-invokes the kernel with an updated alive
mask (`maxsim_top2_update_op` in ops.py); only samples whose best or
second token died change state, and the mask-forced -inf keeps dead
tokens out of both maxima.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import default_interpret

NEG = -1e30


def _kernel(s_ref, t_ref, alive_ref, best_ref, second_ref, bi_ref, si_ref):
    j = pl.program_id(1)
    bt = t_ref.shape[0]

    s = s_ref[...].astype(jnp.float32)            # (BS, dim)
    t = t_ref[...].astype(jnp.float32)            # (BT, dim)
    alive = alive_ref[...]                        # (1, BT) int32
    scores = jax.lax.dot_general(
        s, t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (BS, BT) on the MXU
    scores = jnp.where(alive > 0, scores, NEG)

    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    loc_best = jnp.max(scores, axis=1, keepdims=True)            # (BS,1)
    is_best = scores == loc_best
    # first column attaining the max (matches jnp.argmax)
    loc_bi = jnp.min(jnp.where(is_best, col, bt), axis=1,
                     keepdims=True)                               # (BS,1)
    masked = jnp.where(col == loc_bi, NEG, scores)
    loc_second = jnp.max(masked, axis=1, keepdims=True)           # (BS,1)
    is_second = masked == loc_second
    loc_si = jnp.min(jnp.where(is_second, col, bt), axis=1,
                     keepdims=True)                               # (BS,1)
    loc_bi_glob = loc_bi + j * bt
    loc_si_glob = loc_si + j * bt

    @pl.when(j == 0)
    def _init():
        best_ref[...] = loc_best
        second_ref[...] = loc_second
        bi_ref[...] = loc_bi_glob
        si_ref[...] = loc_si_glob

    @pl.when(j > 0)
    def _merge():
        b_old = best_ref[...]
        s_old = second_ref[...]
        i_old = bi_ref[...]
        si_old = si_ref[...]
        new_wins = loc_best > b_old                               # strict >
        b_new = jnp.where(new_wins, loc_best, b_old)
        i_new = jnp.where(new_wins, loc_bi_glob, i_old)
        # runner-up among {loser of the bests, winner's own second}.
        lose1 = jnp.where(new_wins, b_old, loc_best)
        lose1_i = jnp.where(new_wins, i_old, loc_bi_glob)
        own2 = jnp.where(new_wins, loc_second, s_old)
        own2_i = jnp.where(new_wins, loc_si_glob, si_old)
        # Tie-break to the LOWER global index: when the current tile won,
        # the loser-of-bests index i_old comes from an earlier tile (<=
        # own2's current-tile index) so ties take it; when the old state
        # won, own2_i = si_old is the earlier one so ties keep it.
        # (boolean algebra, not a select of two i1 vectors: Mosaic cannot
        # truncate the select's i8 result back to i1)
        take_lose = ((new_wins & (lose1 >= own2))
                     | (~new_wins & (lose1 > own2)))
        s_new = jnp.where(take_lose, lose1, own2)
        si_new = jnp.where(take_lose, lose1_i, own2_i)
        best_ref[...] = b_new
        second_ref[...] = s_new
        bi_ref[...] = i_new
        si_ref[...] = si_new


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_t", "interpret"))
def maxsim_top2(samples: jax.Array, tokens: jax.Array, alive: jax.Array,
                *, block_s: int = 256, block_t: int = 128,
                interpret: bool | None = None):
    """Fused top-2 of samples @ tokens.T over alive tokens.

    samples: (N, dim); tokens: (m, dim); alive: (m,) bool.
    Returns (best (N,), second (N,), argbest (N,), argsecond (N,)) —
    f32, f32, int32, int32.  ``interpret=None`` resolves to the compiled
    Mosaic kernel on TPU and the Pallas interpreter elsewhere
    (`repro.core.backend.default_interpret`).
    """
    interpret = default_interpret(interpret)
    N, dim = samples.shape
    m = tokens.shape[0]
    bs = min(block_s, max(8, N))
    bt = min(block_t, max(8, m))
    pad_n = (-N) % bs
    pad_m = (-m) % bt
    if pad_n:
        samples = jnp.pad(samples, ((0, pad_n), (0, 0)))
    if pad_m:
        tokens = jnp.pad(tokens, ((0, pad_m), (0, 0)))
        alive = jnp.pad(alive, (0, pad_m))
    Np, mp = samples.shape[0], tokens.shape[0]
    alive_i = alive.astype(jnp.int32)[None, :]     # (1, mp)

    grid = (Np // bs, mp // bt)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, dim), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bt), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, 1), jnp.int32),
            jax.ShapeDtypeStruct((Np, 1), jnp.int32),
        ],
        interpret=interpret,
    )(samples, tokens, alive_i)
    best, second, bi, si = (o[:N, 0] for o in out)
    return best, second, bi, si
