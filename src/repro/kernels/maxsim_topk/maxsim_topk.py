"""Fused MaxSim top-K Pallas TPU kernel — the shortlist-rescan hot path.

A tile-resident top-K reduction of a matmul: for N sample queries
against m document tokens it returns each sample's K best dot-product scores and their token indices
**without ever materializing the (N, m) score matrix in HBM**, and —
critically — without ``jax.lax.top_k``, whose TopK custom-call makes
GSPMD all-gather the batch axis.  This is what lets the shortlist
pruning algorithm's periodic full rescan stay partitionable over the
sample/doc axes on a multi-host mesh (DESIGN_BACKENDS.md path matrix,
``shortlist_topk`` row).

Tiling:
  grid = (ceil(N / BS), m / BT); the token axis is the minor (sequential) grid
  dimension, so each sample block's running (K values, K indices) pair
  lives in its output VMEM blocks across the token-tile sweep — the
  flash-attention accumulator pattern applied to a top-K reduction.

  * samples tile  (BS, dim)  — BS a multiple of 128: ``block_s``
    rounded up to one, at most N rounded up to one.  A last block that
    overhangs N computes its extra lanes on unspecified rows, and they
    are dropped on store: nothing pads or slices the sample axis;
  * tokens tile   (BT, dim)  — BT = min(block_t, max(8, m)): one tile
    whenever m <= block_t, as in every width the tuner picks for;
  * scores tile   computed (BS, BT) on the MXU, then transposed once to
    (BT, BS) — tokens on sublanes, samples on lanes — and never written
    out;
  * running state (K, BS) f32 values + (K, BS) int32 global indices:
    lane-dense blocks of (K, N) outputs.  ``maxsim_topk`` returns their
    transpose, (N, K), which a jitted caller may read in place: compiled
    for a v5e, the pruning scan does so at K <= 16, and at K = 32 it
    lays the outputs K-minor with one copy.

  With samples on lanes each selection pass is elementwise across the
  sample block: the max over the pool is a VPU max over its sublane
  groups plus one 8-sublane reduce, the lowest-index pick the same over
  the masked indices, the retire a compare and select with the (1, BS)
  pick broadcast down the sublanes, and the K picks fill K rows of the
  (K, BS) block.  No pass reduces across lanes.  The matmul is the same
  (BS, dim) x (dim, BT) product for every layout, so the scores, and the
  output, are bit for bit those of ``s @ t.T``.

Merge across tiles: the candidate pool is the fresh (BT, BS) tile, and
on every tile after the first the running K-list stacked above it
(K + BT rows); K selection passes extract the maximum (ties to the
LOWEST global token index) and retire the picked entry.  On the first
tile the pool is the tile alone when K <= BT: its BT entries, each
>= NEG, fill the K-list, and a real entry wins every tie with a higher
index.  When K > BT the first tile's pool also holds K out-of-range
sentinels (value NEG, unique indices above any real token) so that K
entries exist.  Global token indices are unique across the pool — the
running list holds indices from *earlier* tiles only, plus sentinels —
so retiring by index kills exactly one entry per pass and the output
K-list is duplicate-free.  The result is bit-identical to ``lax.top_k``
over the masked (N, m) score matrix, including its sorted-descending
order and lowest-index tie-breaking (tested against the oracle in
ref.py, ties included).

K is a static kernel parameter; the selection loop unrolls K passes
over the pool per tile.  At the K <= 32 that the shortlist algorithm
uses, these passes take most of the kernel's time, not the
(BS, dim) x (dim, BT) MXU matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import default_interpret

NEG = -1e30          # masked-score sentinel (matches core.scoring)
RETIRED = -2e30      # strictly below NEG: a retired entry never re-picked
IDX_SENTINEL_PAD = 0x7FFFFFFF
LANES = 128


def _kernel(s_ref, t_ref, alive_ref, vals_ref, idxs_ref, *, k):
    j = pl.program_id(1)
    bt = t_ref.shape[0]

    s = s_ref[...].astype(jnp.float32)            # (BS, dim)
    t = t_ref[...].astype(jnp.float32)            # (BT, dim)
    alive = alive_ref[...]                        # (1, BT) int32
    scores = jax.lax.dot_general(
        s, t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (BS, BT) on the MXU
    scores = jnp.where(alive > 0, scores, NEG).T  # (BT, BS): tokens on sublanes
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) + j * bt

    bs = scores.shape[1]
    krow = jax.lax.broadcasted_iota(jnp.int32, (k, bs), 0)

    def select(v, g):
        """K selection passes down the sublanes of a (pool, BS) block."""
        out_v = jnp.zeros((k, bs), jnp.float32)
        out_i = jnp.zeros((k, bs), jnp.int32)
        for p in range(k):
            top = jnp.max(v, axis=0, keepdims=True)
            pick = jnp.min(jnp.where(v == top, g, IDX_SENTINEL_PAD),
                           axis=0, keepdims=True)  # lowest index on ties
            out_v = jnp.where(krow == p, top, out_v)
            out_i = jnp.where(krow == p, pick, out_i)
            if p + 1 < k:
                v = jnp.where(g == pick, RETIRED, v)
        vals_ref[...] = out_v
        idxs_ref[...] = out_i

    def merge(run_v, run_i):
        select(jnp.concatenate([run_v, scores], axis=0),
               jnp.concatenate([run_i, row], axis=0))

    @pl.when(j == 0)
    def _first():
        if k <= bt:
            # The tile's own bt >= k entries, each >= NEG, fill the
            # K-list: a sentinel could never be picked, so none is pooled.
            select(scores, row)
        else:
            # Seed the K-list with NEG values and unique out-of-range
            # index sentinels: they lose every value tie to a real token
            # (dead or alive, ties break to the lower index) and their
            # uniqueness keeps retire-by-index exact.  num_programs(1) *
            # bt == padded m, so sentinels are provably > any real index.
            merge(jnp.full((k, bs), NEG, jnp.float32),
                  pl.num_programs(1) * bt + krow)

    @pl.when(j > 0)
    def _merge():
        merge(vals_ref[...], idxs_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("k", "block_s", "block_t", "interpret"))
def maxsim_topk(samples: jax.Array, tokens: jax.Array, alive: jax.Array,
                *, k: int, block_s: int = 256, block_t: int = 128,
                interpret: bool | None = None):
    """Fused top-k of samples @ tokens.T over alive tokens.

    samples: (N, dim); tokens: (m, dim); alive: (m,) bool; k <= m.
    Returns (values (N, k) f32 sorted descending, indices (N, k) int32)
    bit-identical to ``jax.lax.top_k(where(alive, S @ D.T, -1e30), k)``.
    ``interpret=None`` resolves to the compiled Mosaic kernel on TPU and
    the Pallas interpreter elsewhere (`repro.core.backend`).
    """
    interpret = default_interpret(interpret)
    N, dim = samples.shape
    m = tokens.shape[0]
    if k > m:
        raise ValueError(f"k={k} exceeds token count m={m}")
    # Samples lie on lanes: a sample block is a whole number of 128-lane
    # columns, and a last block that overhangs N is clipped on store.
    bs = min(pl.cdiv(block_s, LANES), pl.cdiv(N, LANES)) * LANES
    bt = min(block_t, max(8, m))
    pad_m = (-m) % bt
    if pad_m:
        tokens = jnp.pad(tokens, ((0, pad_m), (0, 0)))
        alive = jnp.pad(alive, (0, pad_m))
    mp = tokens.shape[0]
    alive_i = alive.astype(jnp.int32)[None, :]     # (1, mp)

    grid = (pl.cdiv(N, bs), mp // bt)
    vals, idxs = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, dim), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bt), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((k, bs), lambda i, j: (0, i)),
            pl.BlockSpec((k, bs), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, N), jnp.float32),
            jax.ShapeDtypeStruct((k, N), jnp.int32),
        ],
        interpret=interpret,
    )(samples, tokens, alive_i)
    return vals.T, idxs.T
