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
  grid = (N / BS, m / BT); the token axis is the minor (sequential) grid
  dimension, so each sample block's running (K values, K indices) pair
  lives in its output VMEM blocks across the token-tile sweep — the
  flash-attention accumulator pattern applied to a top-K reduction.

  * samples tile  (BS, dim)  — rows, MXU-aligned;
  * tokens tile   (BT, dim)  — BT multiple of 128 for the MXU matmul;
  * scores tile   (BS, BT)   — VREG-resident f32, never written out;
  * running state (BS, K) f32 values + (BS, K) int32 global indices.

Merge across tiles: the running K-list and the fresh (BS, BT) tile are
treated as one candidate pool of K + BT entries; K selection passes
extract the maximum (ties to the LOWEST global token index) and retire
the picked entry.  Global token indices are unique across the pool —
the running list holds indices from *earlier* tiles only, plus unique
out-of-range sentinels from initialization — so retiring by index kills
exactly one entry per pass and the output K-list is duplicate-free.
The result is bit-identical to ``lax.top_k`` over the masked (N, m)
score matrix, including its sorted-descending order and lowest-index
tie-breaking (tested against the oracle in ref.py, ties included).

K is a static kernel parameter; the selection loop unrolls K passes
over a (BS, K + BT) candidate pool per tile — cheap next to the
(BS, dim) x (dim, BT) MXU matmul for the K <= 32 regime the shortlist
algorithm uses.
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


def _kernel(s_ref, t_ref, alive_ref, vals_ref, idxs_ref, *, k):
    j = pl.program_id(1)
    bt = t_ref.shape[0]

    s = s_ref[...].astype(jnp.float32)            # (BS, dim)
    t = t_ref[...].astype(jnp.float32)            # (BT, dim)
    alive = alive_ref[...]                        # (1, BT) int32
    scores = jax.lax.dot_general(
        s, t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (BS, BT) on the MXU
    scores = jnp.where(alive > 0, scores, NEG)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + j * bt

    bs = scores.shape[0]
    krow = jax.lax.broadcasted_iota(jnp.int32, (bs, k), 1)

    def merge(run_v, run_i):
        """K selection passes over the (BS, K + BT) candidate pool."""
        v = jnp.concatenate([run_v, scores], axis=1)
        g = jnp.concatenate([run_i, col], axis=1)
        out_v, out_i = [], []
        for _ in range(k):
            top = jnp.max(v, axis=1, keepdims=True)
            pick = jnp.min(jnp.where(v == top, g, IDX_SENTINEL_PAD),
                           axis=1, keepdims=True)  # lowest index on ties
            out_v.append(top)
            out_i.append(pick)
            v = jnp.where(g == pick, RETIRED, v)
        vals_ref[...] = jnp.concatenate(out_v, axis=1)
        idxs_ref[...] = jnp.concatenate(out_i, axis=1)

    @pl.when(j == 0)
    def _init():
        # Seed the K-list with NEG values and unique out-of-range index
        # sentinels: they lose every value tie to a real token (dead or
        # alive, ties break to the lower index) and their uniqueness
        # keeps retire-by-index exact.  num_programs(1) * bt == padded
        # m, so sentinels are provably > any real index.
        merge(jnp.full((bs, k), NEG, jnp.float32),
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
    vals, idxs = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, dim), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bt), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bs, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, k), jnp.float32),
            jax.ShapeDtypeStruct((Np, k), jnp.int32),
        ],
        interpret=interpret,
    )(samples, tokens, alive_i)
    return vals[:N], idxs[:N]
