"""Batched ColBERT MaxSim scoring Pallas kernels (serving/rerank hot spot).

Two entry points share the same tiling idea — documents are short
(m <= ~256) so a whole (DB, m, dim) doc tile fits VMEM, the block score
tensor stays in VMEM, is masked, max-reduced over document tokens and
sum-reduced over query tokens on-chip, and only per-doc scalars reach
HBM.  This is the padded block-diagonal batching described in
DESIGN.md §3.

* ``colbert_maxsim``       — one query (l, dim) against all docs; the MXU
  sees one dense (DB*m, dim) x (dim, l) matmul per tile.
* ``colbert_maxsim_multi`` — a query BATCH (n_q, l, dim) against all
  docs; the MXU sees (DB*m, dim) x (dim, ~256) matmuls per tile (queries
  in chunks of ``SCORE_LANES`` (query, token) columns) and the output
  block is (DB, n_q).  This is the serving path: the full corpus is
  swept in doc blocks and the 4-D (n_q, n_docs, l, m) einsum tensor of
  the reference path is never materialized — the biggest intermediate
  is one (DB*m, ~256) VMEM score chunk.

Layout rules the compiled (Mosaic) kernels obey, and the interpreter
runs unchanged:

* doc tokens sit on sublanes: the wrappers pad m to a multiple of 8 with
  masked tokens (a no-op on the max), so every (DB, m, x) <-> (DB*m, x)
  reshape is tile-aligned;
* lanes are never split: the per-query sum over l reads static lane
  slices of the (DB, n_q*l) best-match tile, never a (n_q, l) reshape;
* masks are int32 and broadcast *before* the compare — Mosaic cannot
  relayout i1 vectors;
* the multi outputs are (n_docs, n_q) with a (DB, n_q) block (DB a
  multiple of 8 or the whole doc axis); the wrapper transposes;
* every matmul runs at ``Precision.HIGHEST``: the MXU's default f32 path
  rounds operands to bf16, and an fp32 index is scored in fp32.

The ``*_residual`` variants take the compressed form of a bucket
(centroid codes + bit-packed b-bit residuals + per-token scale +
codebook; serve.index "residual" compression) and fuse the decode as a
tile **prologue**: the packed bytes are fanned out to their lanes by a
0/1 matmul and unpacked with per-lane shifts/masks on the VPU, the
centroid rows are gathered with a one-hot (DB*m, C) x (C, dim) MXU
matmul (exact 0/1 weights — bitwise-identical to an eager
``codebook[codes]`` gather, pinned by the interpret parity tests), and
the reconstructed (DB*m, dim) tile feeds the SAME scoring epilogue.
Only the compressed bytes cross HBM; the fp32 bucket exists one VMEM
tile at a time.  ``colbert_maxsim_residual_rerank`` is the per-query
candidate-set variant where each gathered doc row carries its OWN
bucket's codebook and scale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import default_interpret

NEG = -1e30
# f32 vectors tile as (8 sublanes, 128 lanes); doc tokens ride sublanes.
SUBLANES = 8
# (query, token) columns scored per MXU pass: bounds the VMEM score chunk
# at (DB*m, 256) f32 whatever the query batch.
SCORE_LANES = 256
_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def doc_block(block_d: int, n_docs: int) -> int:
    """The doc block a kernel launches with: ``block_d`` rounded up to
    whole sublane tiles, or the whole (short) doc axis."""
    return min(-(-block_d // SUBLANES) * SUBLANES, n_docs)


def _pad_axis(x, axis: int, n: int):
    if not n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n)
    return jnp.pad(x, widths)


def _maxsim_columns(d2, q2, msk, qmsk, l: int) -> list:
    """Per-query MaxSim of one doc tile: d2 (DB*m, dim) f32 doc tokens,
    q2 (n_q*l, dim) f32 query tokens, msk (DB, m) int32, qmsk (n_q, l)
    int32 -> n_q columns of shape (DB, 1)."""
    db, m = msk.shape
    n_q = q2.shape[0] // l
    alive = msk[:, :, None] > 0                   # (DB, m, 1)
    qc = max(1, SCORE_LANES // l)
    cols = []
    for q0 in range(0, n_q, qc):
        q1 = min(q0 + qc, n_q)
        s = _dot(d2, q2[q0 * l:q1 * l], ((1,), (1,)))
        s = s.reshape(db, m, (q1 - q0) * l)
        best = jnp.max(jnp.where(alive, s, NEG), axis=1)   # (DB, qc*l)
        for i in range(q1 - q0):
            b = best[:, i * l:(i + 1) * l]
            qm = qmsk[q0 + i:q0 + i + 1, :]
            cols.append(jnp.sum(jnp.where(qm > 0, b, 0.0), axis=1,
                                keepdims=True))
    return cols


def _kernel(q_ref, d_ref, mask_ref, qmask_ref, out_ref):
    db, m, dim = d_ref.shape
    d2 = d_ref[...].astype(jnp.float32).reshape(db * m, dim)
    q = q_ref[...].astype(jnp.float32)            # (l, dim)
    out_ref[...] = _maxsim_columns(d2, q, mask_ref[...], qmask_ref[...],
                                   q.shape[0])[0]  # (DB, 1)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def colbert_maxsim(q_emb: jax.Array, d_embs: jax.Array, d_masks: jax.Array,
                   q_mask: jax.Array | None = None, *, block_d: int = 8,
                   interpret: bool | None = None) -> jax.Array:
    """q_emb (l, dim) x d_embs (n_docs, m, dim) -> (n_docs,) scores.

    ``interpret=None`` resolves to the compiled Mosaic kernel on TPU and
    the Pallas interpreter elsewhere (`backend.default_interpret`).
    """
    interpret = default_interpret(interpret)
    n_docs, m, dim = d_embs.shape
    l = q_emb.shape[0]
    db = doc_block(block_d, n_docs)
    pad_m = (-m) % SUBLANES
    d_embs = _pad_axis(_pad_axis(d_embs, 0, (-n_docs) % db), 1, pad_m)
    d_masks = _pad_axis(_pad_axis(d_masks, 0, (-n_docs) % db), 1, pad_m)
    np_, mp = d_masks.shape
    mask_i = d_masks.astype(jnp.int32)
    if q_mask is None:
        q_mask = jnp.ones((l,), bool)
    qmask_i = q_mask.astype(jnp.int32)[None, :]   # (1, l)
    out = pl.pallas_call(
        _kernel,
        grid=(np_ // db,),
        in_specs=[
            pl.BlockSpec((l, dim), lambda i: (0, 0)),
            pl.BlockSpec((db, mp, dim), lambda i: (i, 0, 0)),
            pl.BlockSpec((db, mp), lambda i: (i, 0)),
            pl.BlockSpec((1, l), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((db, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(q_emb, d_embs, mask_i, qmask_i)
    return out[:n_docs, 0]


def _kernel_multi(q_ref, d_ref, mask_ref, qmask_ref, out_ref, *, l):
    db, m, dim = d_ref.shape
    d2 = d_ref[...].astype(jnp.float32).reshape(db * m, dim)
    cols = _maxsim_columns(d2, q_ref[...].astype(jnp.float32),
                           mask_ref[...], qmask_ref[...], l)
    out_ref[...] = jnp.concatenate(cols, axis=1)  # (DB, n_q)


def _multi_call(kernel, q_embs, q_masks, doc_args, doc_specs, shared_args,
                shared_specs, np_, db, interpret):
    """Launch a multi-query kernel over ``np_ // db`` doc blocks: queries
    flattened to (n_q*l, dim) (no in-kernel lane split), output
    (np_, n_q) in (DB, n_q) blocks, transposed back to (n_q, np_)."""
    n_q, l, dim = q_embs.shape
    if q_masks is None:
        q_masks = jnp.ones((n_q, l), bool)
    out = pl.pallas_call(
        functools.partial(kernel, l=l),
        grid=(np_ // db,),
        in_specs=[pl.BlockSpec((n_q * l, dim), lambda i: (0, 0)),
                  *doc_specs, *shared_specs,
                  pl.BlockSpec((n_q, l), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((db, n_q), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, n_q), jnp.float32),
        interpret=interpret,
    )(q_embs.reshape(n_q * l, dim), *doc_args, *shared_args,
      q_masks.astype(jnp.int32))
    return out.T


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def colbert_maxsim_multi(q_embs: jax.Array, d_embs: jax.Array,
                         d_masks: jax.Array,
                         q_masks: jax.Array | None = None, *,
                         block_d: int = 8,
                         interpret: bool | None = None) -> jax.Array:
    """q_embs (n_q, l, dim) x d_embs (n_docs, m, dim) -> (n_q, n_docs).

    The multi-query serving kernel: corpus swept in ``block_d`` doc
    blocks, all queries scored per block on the MXU.  No
    (n_q, n_docs, l, m) tensor exists at any point.
    """
    interpret = default_interpret(interpret)
    n_docs, m, dim = d_embs.shape
    db = doc_block(block_d, n_docs)
    pad_n, pad_m = (-n_docs) % db, (-m) % SUBLANES
    d_embs = _pad_axis(_pad_axis(d_embs, 0, pad_n), 1, pad_m)
    mask_i = _pad_axis(_pad_axis(d_masks, 0, pad_n), 1,
                       pad_m).astype(jnp.int32)
    np_, mp = mask_i.shape
    out = _multi_call(
        _kernel_multi, q_embs, q_masks, (d_embs, mask_i),
        (pl.BlockSpec((db, mp, dim), lambda i: (i, 0, 0)),
         pl.BlockSpec((db, mp), lambda i: (i, 0))),
        (), (), np_, db, interpret)
    return out[:, :n_docs]


# -- residual-codec variants: decode fused as a tile prologue ------------

def _unpack_residual(resq, scale, bits):
    """(N, pb) packed bytes (int32) x (N, 1) scales -> (N, pb * 8 //
    bits) f32 residuals.  Byte layout per train/compress.pack_bits:
    value d of a row lives in byte d // vpb at shift (d % vpb) * bits.
    A 0/1 (pb, width) matmul fans each byte out to its vpb lanes (exact:
    byte values are integers < 256), then per-lane shifts select the
    field — no lane interleave, which Mosaic cannot lower."""
    vpb = 8 // bits
    log_vpb = vpb.bit_length() - 1
    pb = resq.shape[-1]
    width = pb * vpb
    src = jax.lax.broadcasted_iota(jnp.int32, (pb, width), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (pb, width), 1)
    fan = ((dst >> log_vpb) == src).astype(jnp.float32)
    rep = _dot(resq.astype(jnp.float32), fan, ((1,), (0,)))
    shift = (jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
             & (vpb - 1)) * bits
    u = (rep.astype(jnp.int32) >> shift) & ((1 << bits) - 1)
    return (u - 2 ** (bits - 1)).astype(jnp.float32) * scale


def _gather_rows(codes, codebook):
    """codes (DB, m) int32 x codebook (C, dim) -> (DB*m, dim) codebook
    rows by one-hot MXU matmul — the Pallas-friendly gather.  0/1
    weights at full precision make it bitwise ``codebook[codes]``."""
    db, m = codes.shape
    c = codebook.shape[0]
    cid = jax.lax.broadcasted_iota(jnp.int32, (1, 1, c), 2)
    onehot = (codes[:, :, None] == cid).astype(jnp.float32)
    return _dot(onehot.reshape(db * m, c), codebook, ((1,), (0,)))


def _gather_codebook(codes, codebook):
    """codes (DB, m) x codebook (C, dim) -> (DB, m, dim); see
    :func:`_gather_rows`."""
    db, m = codes.shape
    return _gather_rows(codes.astype(jnp.int32), codebook).reshape(db, m, -1)


def _decode_rows(codes_ref, resq_ref, scale_ref, cent_fn, bits):
    """Reconstruct a compressed doc tile as (DB*m, dim) f32 in VMEM."""
    db, m, pb = resq_ref.shape
    codes = codes_ref[...].astype(jnp.int32)      # (DB, m)
    resq = resq_ref[...].astype(jnp.int32).reshape(db * m, pb)
    scale = scale_ref[...].reshape(db * m, 1)     # per-token
    return cent_fn(codes) + _unpack_residual(resq, scale, bits)


def _kernel_residual_multi(q_ref, codes_ref, resq_ref, scale_ref, mask_ref,
                           cb_ref, qmask_ref, out_ref, *, bits, l):
    cb = cb_ref[...].astype(jnp.float32)          # (C, dim)
    d2 = _decode_rows(codes_ref, resq_ref, scale_ref,
                      lambda c: _gather_rows(c, cb), bits)
    cols = _maxsim_columns(d2, q_ref[...].astype(jnp.float32),
                           mask_ref[...], qmask_ref[...], l)
    out_ref[...] = jnp.concatenate(cols, axis=1)


def _pad_residual(codes, resq, rscale, d_masks, pad_n, pad_m):
    """Pad a compressed bucket's doc and token axes; pad rows/tokens
    (zero codes/bytes/scales) decode to garbage but arrive masked."""
    return (_pad_axis(_pad_axis(codes, 0, pad_n), 1, pad_m),
            _pad_axis(_pad_axis(resq, 0, pad_n), 1, pad_m),
            _pad_axis(_pad_axis(rscale, 0, pad_n), 1, pad_m),
            _pad_axis(_pad_axis(d_masks, 0, pad_n), 1,
                      pad_m).astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("bits", "block_d", "interpret"))
def colbert_maxsim_residual_multi(q_embs: jax.Array, codes: jax.Array,
                                  resq: jax.Array, rscale: jax.Array,
                                  codebook: jax.Array, d_masks: jax.Array,
                                  q_masks: jax.Array | None = None, *,
                                  bits: int, block_d: int = 8,
                                  interpret: bool | None = None
                                  ) -> jax.Array:
    """Multi-query sweep over ONE compressed bucket: q_embs (n_q, l,
    dim) x [codes (n_docs, m) int8, resq (n_docs, m, dim*bits//8)
    uint8, rscale (n_docs, m, 1) f32 per-token, codebook (C, dim) f32]
    -> (n_q, n_docs).  Doc-axis pad rows (zero codes/resq) decode to
    garbage but arrive all-masked, exactly like fp pad rows."""
    interpret = default_interpret(interpret)
    n_docs, m = codes.shape
    dim = q_embs.shape[-1]
    c = codebook.shape[0]
    pb = resq.shape[-1]
    db = doc_block(block_d, n_docs)
    codes, resq, rscale, mask_i = _pad_residual(
        codes, resq, rscale, d_masks, (-n_docs) % db, (-m) % SUBLANES)
    np_, mp = mask_i.shape
    out = _multi_call(
        functools.partial(_kernel_residual_multi, bits=bits),
        q_embs, q_masks, (codes, resq, rscale, mask_i),
        (pl.BlockSpec((db, mp), lambda i: (i, 0)),
         pl.BlockSpec((db, mp, pb), lambda i: (i, 0, 0)),
         pl.BlockSpec((db, mp, 1), lambda i: (i, 0, 0)),
         pl.BlockSpec((db, mp), lambda i: (i, 0))),
        (codebook,), (pl.BlockSpec((c, dim), lambda i: (0, 0)),),
        np_, db, interpret)
    return out[:, :n_docs]


def _kernel_residual_rerank(q_ref, codes_ref, resq_ref, scale_ref, cb_ref,
                            mask_ref, qmask_ref, out_ref, *, bits):
    cb = cb_ref[...].astype(jnp.float32)          # (DB, C, dim)
    db, c, dim = cb.shape

    def cent(codes):                              # each row: its own table
        cid = jax.lax.broadcasted_iota(jnp.int32, (1, 1, c), 2)
        onehot = (codes[:, :, None] == cid).astype(jnp.float32)
        rows = jax.lax.dot_general(onehot, cb, (((2,), (1,)), ((0,), (0,))),
                                   precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
        return rows.reshape(-1, dim)

    d2 = _decode_rows(codes_ref, resq_ref, scale_ref, cent, bits)
    q = q_ref[...].astype(jnp.float32)            # (l, dim)
    out_ref[...] = _maxsim_columns(d2, q, mask_ref[...], qmask_ref[...],
                                   q.shape[0])[0]


@functools.partial(jax.jit,
                   static_argnames=("bits", "block_d", "interpret"))
def colbert_maxsim_residual_rerank(q_emb: jax.Array, codes: jax.Array,
                                   resq: jax.Array, rscales: jax.Array,
                                   codebooks: jax.Array,
                                   d_masks: jax.Array,
                                   q_mask: jax.Array | None = None, *,
                                   bits: int, block_d: int = 8,
                                   interpret: bool | None = None
                                   ) -> jax.Array:
    """One query vs a gathered candidate block whose rows span buckets:
    q_emb (l, dim) x [codes (n_docs, m) int8, resq (n_docs, m,
    dim*bits//8) uint8, rscales (n_docs, m, 1) f32 per-token, codebooks
    (n_docs, C, dim) f32] -> (n_docs,).  Each doc row decodes against
    its OWN codebook (gathered per candidate by the rerank caller) and
    its own per-token scales."""
    interpret = default_interpret(interpret)
    n_docs, m = codes.shape
    l, dim = q_emb.shape
    c = codebooks.shape[1]
    pb = resq.shape[-1]
    db = doc_block(block_d, n_docs)
    pad_n = (-n_docs) % db
    codes, resq, rscales, mask_i = _pad_residual(
        codes, resq, rscales, d_masks, pad_n, (-m) % SUBLANES)
    codebooks = _pad_axis(codebooks, 0, pad_n)
    np_, mp = mask_i.shape
    if q_mask is None:
        q_mask = jnp.ones((l,), bool)
    qmask_i = q_mask.astype(jnp.int32)[None, :]
    out = pl.pallas_call(
        functools.partial(_kernel_residual_rerank, bits=bits),
        grid=(np_ // db,),
        in_specs=[
            pl.BlockSpec((l, dim), lambda i: (0, 0)),
            pl.BlockSpec((db, mp), lambda i: (i, 0)),
            pl.BlockSpec((db, mp, pb), lambda i: (i, 0, 0)),
            pl.BlockSpec((db, mp, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((db, c, dim), lambda i: (i, 0, 0)),
            pl.BlockSpec((db, mp), lambda i: (i, 0)),
            pl.BlockSpec((1, l), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((db, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(q_emb, codes, resq, rscales, codebooks, mask_i, qmask_i)
    return out[:n_docs, 0]
