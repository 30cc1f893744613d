"""Late-interaction retrieval serving: index -> prune -> (two-stage) search.

The serving pipeline mirrors the paper's experimental setup:
  * first stage: cheap single-vector scoring (mean-pooled doc embedding,
    standing in for SPLADEv2) retrieves `n_first` candidates;
  * second stage: exact MaxSim rerank over the (possibly pruned)
    token-level index — the paper's ColBERTv2-rerank configuration.
    `end_to_end=True` skips stage 1 (ColBERTv2-e2e analogue).

Two index layouts feed this module (DESIGN_BACKENDS.md §Index layouts):

* ``TokenIndex`` — the dense **masked** view: full (n_docs, m, dim)
  tensor + keep-mask.  Pruning ratios sweep cheaply (flip the mask), and
  ``storage()`` *reports* what compaction would save, but the process
  keeps paying for every pruned token.  The experimentation view.
* ``repro.serve.index.PackedIndex`` — the **packed** serving artifact:
  kept tokens compacted into capacity-bucketed dense arrays the kernels
  score directly, with a doc-id remap back to corpus-global positions.
  ``storage()`` there measures bytes actually held.  Build one with
  ``TokenIndex.pack()``; persist via ``repro.serve.index_io``.

``maxsim_scores``/``search``/``RetrievalServer`` accept either layout on
both backends, with identical top-k results (asserted in
tests/test_packed_index.py).  Candidate scoring shards over the `model`
axis ("candidates" logical axis) in the production mesh — packed buckets
carry the same logical axes (``PackedIndex.shard_axes``).

Backend dispatch (``repro.core.backend``): the ``reference`` path scores
via a single einsum that materializes the 4-D (n_q, n_docs, l, m) score
tensor — O(n_q * n_docs * l * m) HBM at query time, the very footprint
token pruning exists to kill.  The ``fused`` path sweeps the corpus in
static ``block_docs``-sized blocks through the ``colbert_maxsim`` Pallas
kernels: the biggest live intermediate is one (block_docs, m, n_q, l)
VMEM tile, multi-query rerank is batched through one kernel launch, and
the compiled HLO contains no 4-D score tensor (asserted in
tests/test_backend_dispatch.py).  On the packed layout both backends
score per bucket — the packed reference path's biggest tensor is
(n_q, n_docs_b, l, cap_b), already keep_fraction-smaller than the dense
one, and the fused path's tiles shrink the same way (the autotuner keys
on each bucket's shape).

Above both backends sits the **streaming top-k** dataflow
(:func:`topk_search`; DESIGN_BACKENDS.md §Sharded serving): instead of
scattering bucket scores into an (n_q, n_docs) matrix and running one
global ``lax.top_k``, every bucket/chunk/shard reduces its scores to
(n_q, k) (score, doc-id) candidates immediately and sort-merges flow up
a tournament tree — identical results, no corpus-sized tensor in the
compiled HLO, and under ``sharding.serve_rules(mesh)`` the doc axis of
every bucket places over the candidates mesh axis with one k-wide
all-gather per shard.  ``search(..., return_full=False)`` — the
``RetrievalServer`` default — serves through it; ``return_full=True``
keeps the materializing path for metrics code that needs the densified
matrix.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import functools
import threading
import time

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import backend as backend_lib
from repro.core.scoring import NEG_INF
from repro.core.tuning import _pow2_at_least
from repro.kernels.colbert_maxsim.ops import (
    colbert_maxsim_multi_op, colbert_maxsim_rerank_op,
    colbert_maxsim_residual_multi_op, colbert_maxsim_residual_rerank_op)
from repro.serve import health as health_lib
from repro.serve.index import PackedIndex, ResidualView
from repro.sharding import (PlacementPlan, axis_rules, constrain,
                            current_rules, grid_axes_for, mesh_axes_for)
from repro.sharding.placement import bucket_weights


class TopKResult(tuple):
    """``(top_idx, top_scores)`` that also reports result ``coverage``.

    Unpacks exactly like the 2-tuple every pre-fault-tolerance caller
    expects (``ids, scores = topk_search(...)`` keeps working);
    ``coverage`` is the fraction of stored bucket bytes the answer
    consulted — ``1.0`` on every fully-healthy path, ``< 1.0`` when
    grid serving answered from surviving replicas only (every replica
    of some bucket set unreachable).  Degraded results are still exact
    over what they cover: bit-identical to the single-host oracle
    restricted to the surviving buckets (DESIGN_BACKENDS.md §Failure
    semantics).

    Only eager paths return this type (tuple subclasses are not jax
    pytrees); jitted closures return plain tuples and
    ``RetrievalServer.query_batch`` re-wraps uniformly.

    ``epoch_key`` (set by ``RetrievalServer.query_batch``, ``None``
    elsewhere) records the ``(generation, mutation_gen, index.epoch)``
    snapshot the answer was computed under — the same triple the
    closure LRU keys on — so concurrent callers (``serve.loop``) can
    attribute each answer to its epoch and key result caches on it.
    """

    coverage: float
    epoch_key: tuple | None = None

    def __new__(cls, top_idx, top_scores, coverage: float = 1.0):
        self = tuple.__new__(cls, (top_idx, top_scores))
        self.coverage = float(coverage)
        return self

    @property
    def top_idx(self):
        return self[0]

    @property
    def top_scores(self):
        return self[1]


@dataclasses.dataclass
class TokenIndex:
    d_embs: jnp.ndarray       # (n_docs, m, dim)
    d_masks: jnp.ndarray      # (n_docs, m)  original token validity
    keep: jnp.ndarray         # (n_docs, m)  pruning decision

    @classmethod
    def build(cls, d_embs, d_masks):
        return cls(d_embs=d_embs, d_masks=d_masks, keep=d_masks)

    def with_keep(self, keep):
        return TokenIndex(self.d_embs, self.d_masks, keep & self.d_masks)

    def pack(self, **kw) -> PackedIndex:
        """Compact the kept tokens into the packed serving artifact
        (``repro.serve.index.PackedIndex``) — the step that turns the
        reported savings below into actually-freed bytes.  Keyword args
        are ``PackedIndex.pack``'s (compression, granularity, ...)."""
        return PackedIndex.pack(self.d_embs, self.d_masks, self.keep, **kw)

    def storage(self) -> dict:
        """*Reported* (logical) sizes — this dense view keeps holding
        every pruned token; ``pack().storage()`` measures real bytes."""
        total = int(self.d_masks.sum())
        kept = int((self.keep & self.d_masks).sum())
        dim = self.d_embs.shape[-1]
        return {
            "tokens_total": total,
            "tokens_kept": kept,
            "remain_pct": 100.0 * kept / max(total, 1),
            "bytes_fp32": kept * dim * 4,
            "bytes_fp32_unpruned": total * dim * 4,
        }

    @property
    def active_mask(self):
        return self.keep & self.d_masks

    def pooled(self) -> jnp.ndarray:
        """Mean-pooled doc vectors for the cheap first stage."""
        w = self.active_mask[..., None].astype(self.d_embs.dtype)
        return (self.d_embs * w).sum(1) / jnp.maximum(w.sum(1), 1.0)


def _maxsim_scores_reference(d_embs, active_mask, q_embs, q_masks):
    """Materializing einsum path — the parity oracle.  A compressed
    :class:`ResidualView` decodes eagerly here (that fp32
    materialization is exactly what the fused path's HLO gate proves it
    avoids)."""
    if isinstance(d_embs, ResidualView):
        d_embs = d_embs.dense()
    s = jnp.einsum("qld,nmd->qnlm", q_embs, d_embs)
    s = jnp.where(active_mask[None, :, None, :], s, NEG_INF)
    best = s.max(-1)
    if q_masks is not None:
        best = jnp.where(q_masks[:, None, :], best, 0.0)
    return best.sum(-1)


def _maxsim_scores_fused(d_embs, active_mask, q_embs, q_masks, *,
                         block_docs, block_q):
    """Chunked kernel path: corpus swept in ``block_docs`` blocks, query
    batch in ``block_q`` chunks (a static unrolled loop under jit) to
    bound the per-launch VMEM tile.  A :class:`ResidualView` doc array
    dispatches to the decode-fused kernel — the compressed bytes go
    straight to VMEM and the fp32 bucket never exists outside a tile."""
    n_q = q_embs.shape[0]
    bq = min(block_q, n_q)
    outs = []
    for start in range(0, n_q, bq):
        q_chunk = q_embs[start:start + bq]
        qm_chunk = None if q_masks is None else q_masks[start:start + bq]
        if isinstance(d_embs, ResidualView):
            outs.append(colbert_maxsim_residual_multi_op(
                q_chunk, d_embs.codes, d_embs.resq, d_embs.scale,
                d_embs.codebook, active_mask, qm_chunk, bits=d_embs.bits,
                block_d=block_docs))
        else:
            outs.append(colbert_maxsim_multi_op(
                q_chunk, d_embs, active_mask, qm_chunk, block_d=block_docs))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def _score_block(d_embs, active_mask, q_embs, q_masks, *, backend,
                 block_docs, block_q, codec: str | None = None):
    """Score one doc array (dense fp32 or a compressed
    :class:`ResidualView`) on the resolved backend; ``None`` chunking
    knobs resolve per THIS array's shape (the autotuner keys on bucket
    shape, so packed buckets each get their own blocks).  ``codec``
    joins the tuner key — fp32/int8/residual buckets tune independently
    because decode shifts the kernel's bandwidth/compute balance."""
    if backend == backend_lib.FUSED:
        n_docs, m = active_mask.shape
        block_docs, block_q = backend_lib.tuned_serving_blocks(
            q_embs.shape[0], n_docs, m, q_embs.shape[1], q_embs.shape[-1],
            block_docs, block_q, codec=codec)
        return _maxsim_scores_fused(d_embs, active_mask, q_embs, q_masks,
                                    block_docs=block_docs, block_q=block_q)
    return _maxsim_scores_reference(d_embs, active_mask, q_embs, q_masks)


def _constrained_bucket(index: PackedIndex, b, backend):
    """The per-bucket doc array the scorers consume, sharding-constrained
    on the candidates axis.  Residual buckets stay compressed on the
    fused backend (the kernel decodes per tile); the reference backend
    decodes the whole bucket eagerly — it IS the materializing oracle."""
    if index.compression == "residual" and backend == backend_lib.FUSED:
        v = b.residual_view(index.dim)
        return ResidualView(constrain(v.codes, index.shard_axes[0], None),
                            constrain(v.resq, *index.shard_axes),
                            constrain(v.scale, *index.shard_axes),
                            v.codebook, v.bits, v.dim)
    return constrain(b.dense_embs(index.dim), *index.shard_axes)


def _maxsim_scores_packed(index: PackedIndex, q_embs, q_masks, *, backend,
                          block_docs, block_q):
    """Per-bucket sweep over the packed layout: each capacity bucket is
    a dense (n_docs_b, cap_b, dim) array scored exactly like a small
    corpus, then scattered to global doc positions via the bucket's
    doc-id remap.  Bit-identical to the masked path on the fp layout
    (max over kept tokens is subset-invariant)."""
    out = jnp.zeros((q_embs.shape[0], index.n_docs), jnp.float32)
    codec = index.codec_tag()
    for b in index.buckets:
        e = _constrained_bucket(index, b, backend)
        s = _score_block(e, b.masks, q_embs, q_masks, backend=backend,
                         block_docs=block_docs, block_q=block_q,
                         codec=codec)
        out = out.at[:, b.doc_ids].set(s)
    return out


def maxsim_scores(index: TokenIndex | PackedIndex, q_embs: jnp.ndarray,
                  q_masks: jnp.ndarray | None = None, *,
                  backend: str | None = None, block_docs: int | None = None,
                  block_q: int | None = None) -> jnp.ndarray:
    """(n_q, n_docs) exact MaxSim over the pruned index.

    Both backends and both index layouts are exact; they differ only in
    what they materialize (see module docstring).  ``backend=None``
    resolves to fused on TPU, reference elsewhere.  ``block_docs``/
    ``block_q`` default to ``None`` — picked by the shape-aware
    autotuner (per bucket shape on the packed layout); ints pin them.
    """
    backend = backend_lib.resolve_backend(backend, allow=backend_lib.SERVING)
    if isinstance(index, PackedIndex):
        return _maxsim_scores_packed(index, q_embs, q_masks, backend=backend,
                                     block_docs=block_docs, block_q=block_q)
    return _score_block(index.d_embs, index.active_mask, q_embs, q_masks,
                        backend=backend, block_docs=block_docs,
                        block_q=block_q)


# ----------------------------------------------------------------------
# Streaming top-k serving (the merge-tree dataflow; DESIGN_BACKENDS.md
# §Sharded serving).  Scores flow *up* a merge tree instead of *into* a
# dense (n_q, n_docs) matrix: every capacity bucket (and every
# candidates-axis shard of it) reduces its chunk scores to (n_q, k)
# candidates immediately, and a tournament of sort-merges produces the
# global top-k — bit-identical to ``lax.top_k`` over the materialized
# matrix, with no corpus-sized tensor anywhere in the compiled HLO.
# ----------------------------------------------------------------------


def _merge_topk(scores, ids, k: int):
    """Exact top-k merge of candidate (scores, ids) columns.

    Sorting by the two keys (-score, id) reproduces ``lax.top_k``'s
    contract over the full matrix exactly: descending score, ties to the
    lowest doc id — which is what the materialized path's tie-breaking
    (lowest column index == lowest doc id) resolves to.  Negation is
    exact in fp, so merged scores are bit-identical, not just close.
    """
    neg, sid = jax.lax.sort((-scores, ids), num_keys=2, dimension=1)
    return sid[:, :k], -neg[:, :k]


def _merge_topk_unique(scores, ids, k: int):
    """:func:`_merge_topk` that additionally dedupes doc ids — the root
    merge of *replicated* grid serving, where a doc scored by two live
    replicas of its bucket arrives once per replica and must fill one
    output slot, not several.

    Sorting by ``(id, -score)`` makes duplicates adjacent with each
    id's best candidate first; the rest collapse to the ``(-inf, -1)``
    sentinel (replicas compute bit-identical scores, so "best" is just
    "the one kept").  When finite ids are already unique — every
    unreplicated path — the surviving multiset is unchanged and the
    final ``(-score, id)`` sort returns exactly what ``_merge_topk``
    would: dedupe costs one extra ``lax.sort``, never exactness.
    """
    sid, neg = jax.lax.sort((ids, -scores), num_keys=2, dimension=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(sid[:, :1], bool), sid[:, 1:] == sid[:, :-1]],
        axis=1)
    neg = jnp.where(dup, jnp.inf, neg)
    sid = jnp.where(dup, -1, sid)
    neg, sid = jax.lax.sort((neg, sid), num_keys=2, dimension=1)
    return sid[:, :k], -neg[:, :k]


def _stream_chunk_topk(n: int, chunk: int, k: int, score_slab,
                       doc_ids=None, pad_from: int | None = None):
    """The streaming reduce loop every candidate producer shares: sweep
    the doc axis in ``chunk``-sized slabs, reduce each slab's scores
    (``score_slab(start, stop) -> (n_q, stop - start)``) to its local
    top-k (scores, global-doc-id) columns, concatenate.  Only the
    (n_q, <= n_chunks * k) candidates outlive a chunk; the score strip
    is free for XLA to recycle per chunk.

    ``doc_ids=None`` means the axis is already in corpus-global order.
    ``pad_from`` marks sentinel ids at/above it as shard-padding; ids
    below 0 are the zero-doc-bucket pads (``PackedBucket.shard_view``
    emits id ``-1`` rows when a bucket holds no documents at all).
    Both audits force the pad's candidates to -inf so a pad can never
    displace a real doc — real empty-after-prune docs score a finite
    sentinel, strictly above -inf, and without the negative-id audit an
    all-empty shard's pad row would *tie* such a doc and beat it on the
    lowest-id tie-break.  Per-chunk ``lax.top_k`` tie-breaking (lowest
    local index) agrees with the global order because doc ids ascend
    within every bucket (``bucket_plan`` emits ``np.flatnonzero`` index
    sets) and pads sit at the tail.
    """
    vals, ids = [], []
    for s0 in range(0, n, chunk):
        s = score_slab(s0, min(s0 + chunk, n))
        kb = min(k, s.shape[1])
        v, loc = jax.lax.top_k(s, kb)
        i = (s0 + loc if doc_ids is None
             else doc_ids[s0:s0 + chunk][loc]).astype(jnp.int32)
        is_pad = i < 0
        if pad_from is not None:
            is_pad = is_pad | (i >= pad_from)
        v = jnp.where(is_pad, -jnp.inf, v)
        vals.append(v)
        ids.append(i)
    return jnp.concatenate(vals, axis=1), jnp.concatenate(ids, axis=1)


def _chunk_candidates(embs, masks, doc_ids, q_embs, q_masks, k: int, *,
                      backend, block_docs, block_q, chunk_docs,
                      pad_from: int | None = None,
                      owner=None, leaf: int = 0, codec: str | None = None):
    """One doc array's exact-MaxSim candidates via the shared streaming
    reduce loop, scoring each slab with the per-backend scorers.

    ``owner``/``leaf`` is the mutation-serving stale mask
    (:class:`MutationView`): slab scores of docs this leaf does not own
    — a base copy shadowed by an upsert, a tombstoned delete — are
    forced to -inf BEFORE the slab's top-k reduction, so a stale copy
    can never crowd a live doc out of its bucket's candidate slots.
    The clip guards sentinel ids (< 0, forced to -inf by the pad
    audits regardless) against wraparound."""

    def slab(a, b):
        s = _score_block(embs[a:b], masks[a:b], q_embs, q_masks,
                         backend=backend, block_docs=block_docs,
                         block_q=block_q, codec=codec)
        if owner is not None:
            ids = (jnp.arange(a, b, dtype=jnp.int32) if doc_ids is None
                   else doc_ids[a:b])
            own = owner[jnp.clip(ids, 0, owner.shape[0] - 1)]
            s = jnp.where((own != leaf)[None, :], -jnp.inf, s)
        return s

    return _stream_chunk_topk(masks.shape[0], chunk_docs, k, slab,
                              doc_ids=doc_ids, pad_from=pad_from)


def _view_shapes(index: TokenIndex | PackedIndex):
    """(global_docs, cap) per bucket view — the single source of the
    shapes both :func:`_index_views` slices and the autotuner keys on."""
    if isinstance(index, PackedIndex):
        return [(b.n_docs, b.cap) for b in index.buckets]
    return [index.d_masks.shape]


def _codec_of(index) -> str | None:
    """The autotuner codec tag of an index view (None for dense)."""
    return index.codec_tag() if isinstance(index, PackedIndex) else None


def _index_views(index: TokenIndex | PackedIndex, n_shards: int = 1):
    """Per-bucket (embs, masks, doc_ids) views with the doc axis padded
    to place evenly over ``n_shards`` candidate shards."""
    if isinstance(index, PackedIndex):
        return [b.shard_view(index.dim, n_shards, index.n_docs)
                for b in index.buckets]
    n_docs, m = index.d_masks.shape
    e, mk = index.d_embs, index.active_mask
    ids = jnp.arange(n_docs, dtype=jnp.int32)
    pad = (-n_docs) % max(n_shards, 1)
    if pad:
        e = jnp.pad(e, ((0, pad), (0, 0), (0, 0)))
        mk = jnp.pad(mk, ((0, pad), (0, 0)))
        ids = jnp.pad(ids, (0, pad), constant_values=n_docs)
    return [(e, mk, ids if (pad or n_shards > 1) else None)]


def _streaming_plan(index, n_q, l, dim, k, *, n_shards, block_docs,
                    block_q, chunk_docs, n_groups=1, replicas=1):
    """Resolve (block_docs, block_q, chunk_docs) per bucket — one tuner
    key per shard-local bucket shape (placement-aware: ``n_groups``,
    and ``replicas`` under a replicated plan, join the key under a grid
    mesh, where a bucket's shards span only its own host group).
    Shared by :func:`topk_search` (closure build) and
    ``RetrievalServer._warm_tuner`` (eager warm outside jit), so
    in-trace resolutions always hit the cache."""
    codec = _codec_of(index)
    return [backend_lib.tuned_streaming_blocks(
        n_q, nd, cap, l, dim, k, n_shards=n_shards, n_groups=n_groups,
        replicas=replicas, block_docs=block_docs, block_q=block_q,
        chunk_docs=chunk_docs, codec=codec)
        for nd, cap in _view_shapes(index)]


def _real_docs(index: TokenIndex | PackedIndex) -> int:
    """Documents actually present in this (possibly group-sliced) view —
    ``sum(b.n_docs)`` for packed, the full doc axis for dense.  Group
    views keep the *global* ``n_docs`` (their doc ids are global), so
    this, not ``index.n_docs``, bounds how many real candidates the
    view can produce."""
    if isinstance(index, PackedIndex):
        return sum(b.n_docs for b in index.buckets)
    return index.d_masks.shape[0]


@dataclasses.dataclass(frozen=True)
class MutationView:
    """The serving view of a live delta log (``serve.mutation``): the
    extra leaves :func:`topk_search`'s sort-merge tournament scores
    beside the packed base index.

    ``deltas`` are small :class:`PackedIndex`\\ es (one per absorbed
    upsert batch, packed by the same ``bucket_plan`` machinery and
    scored by the unmodified ``colbert_maxsim`` kernels).  ``owner``
    maps every corpus-global doc id to the single *leaf* holding its
    current version — 0 for the base index, ``i + 1`` for delta ``i``,
    ``-1`` for a tombstoned/absent doc.  Each leaf's slab scores are
    masked to ``-inf`` wherever the owner disagrees (a stale base copy
    shadowed by an upsert, a tombstoned delete) *before* the per-bucket
    top-k reduction, so exactly one finite
    copy of every live doc enters the root merge: results are
    bit-identical to re-packing the mutated corpus from scratch (the
    mutation differential oracle, tests/test_mutation.py).
    ``n_live`` (live docs) replaces ``_real_docs`` as the output-width
    clamp."""

    deltas: tuple
    owner: jnp.ndarray            # (n_total,) int32; -1 = dead
    n_live: int


def _topk_search_local(index, q_embs, q_masks, k, *, backend, plan,
                       mutation=None, delta_plans=(), real_cap=None):
    leaves = [(index, plan, 0)]
    if mutation is not None:
        leaves += [(d, dp, li + 1) for li, (d, dp)
                   in enumerate(zip(mutation.deltas, delta_plans))]
    vals, ids = [], []
    for leaf_index, leaf_plan, leaf in leaves:
        leaf_codec = _codec_of(leaf_index)
        for (e, mk, di), (bd, bq, cd) in zip(_index_views(leaf_index),
                                             leaf_plan):
            # The owner mask applies INSIDE the slab scorer, before the
            # per-bucket top-k reduction: a stale copy masked only
            # after the reduction would still crowd a live doc out of
            # its bucket's k candidate slots.
            v, i = _chunk_candidates(e, mk, di, q_embs, q_masks, k,
                                     backend=backend, block_docs=bd,
                                     block_q=bq, chunk_docs=cd,
                                     owner=(None if mutation is None
                                            else mutation.owner),
                                     leaf=leaf, codec=leaf_codec)
            vals.append(v)
            ids.append(i)
    vals = jnp.concatenate(vals, axis=1)
    ids = jnp.concatenate(ids, axis=1)
    # Zero-doc buckets contribute (-inf, -1) sentinel columns; the cap
    # at the view's real doc count (live docs under mutation — stale
    # and tombstoned candidates sit at -inf) keeps them out of the
    # output.  ``real_cap`` overrides for routed bucket views, whose
    # candidate pool is the selected buckets (plus delta leaves), not
    # the corpus.
    if real_cap is not None:
        real = real_cap
    else:
        real = _real_docs(index) if mutation is None else mutation.n_live
    return _merge_topk(vals, ids, min(k, real, vals.shape[1]))


def _topk_search_sharded(index, q_embs, q_masks, k, *, backend, plan,
                         mesh, axes, n_shards):
    """Distributed streaming top-k under ``shard_map``: every bucket's
    doc axis is placed over the candidates mesh axes, each shard reduces
    its local slice to (n_q, k) candidates, and one small all-gather of
    those candidates (k * n_shards columns — never corpus-sized) feeds
    the final merge.  Replicated output; bit-identical to the
    single-device paths (the candidate set surviving each merge stage is
    a superset of the true top-k, and every merge uses the same
    (-score, id) total order)."""
    from jax.sharding import PartitionSpec as P

    views = _index_views(index, n_shards)
    n_docs = (index.n_docs if isinstance(index, PackedIndex)
              else index.d_masks.shape[0])
    codec = _codec_of(index)
    if q_masks is None:
        q_masks = jnp.ones(q_embs.shape[:2], bool)

    def body(views, q, qm):
        vals, ids = [], []
        for (e, mk, di), (bd, bq, cd) in zip(views, plan):
            v, i = _chunk_candidates(e, mk, di, q, qm, k, backend=backend,
                                     block_docs=bd, block_q=bq,
                                     chunk_docs=cd, pad_from=n_docs,
                                     codec=codec)
            vals.append(v)
            ids.append(i)
        vals = jnp.concatenate(vals, axis=1)
        ids = jnp.concatenate(ids, axis=1)
        kl = min(k, vals.shape[1])
        i, v = _merge_topk(vals, ids, kl)
        if kl < k:      # k > docs-in-shard: pad so the gather is square
            v = jnp.pad(v, ((0, 0), (0, k - kl)),
                        constant_values=-jnp.inf)
            i = jnp.pad(i, ((0, 0), (0, k - kl)), constant_values=n_docs)
        gv = jax.lax.all_gather(v, axes)             # (n_shards, n_q, k)
        gi = jax.lax.all_gather(i, axes)
        gv = jnp.moveaxis(gv, 0, 1).reshape(v.shape[0], -1)
        gi = jnp.moveaxis(gi, 0, 1).reshape(v.shape[0], -1)
        # Root merge truncates to min(k, n_docs): with k > total docs
        # the gathered columns still contain -inf/sentinel shard pads,
        # and the single-device path returns only the real docs.
        return _merge_topk(gv, gi, min(k, n_docs))

    ax = axes if len(axes) > 1 else axes[0]

    def vspec(e):
        # Residual views shard like their dense counterpart: the doc
        # axis of codes/resq/per-token scales places over the candidates
        # mesh axis; only the tiny codebook replicates to every shard.
        if isinstance(e, ResidualView):
            espec = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(e),
                [P(ax, None), P(ax, None, None), P(ax, None, None),
                 P(None, None)])
        else:
            espec = P(ax, None, None)
        return (espec, P(ax, None), P(ax))

    out = jax.shard_map(body, mesh=mesh,
                        in_specs=([vspec(e) for e, _, _ in views],
                                  P(None, None, None), P(None, None)),
                        out_specs=(P(None, None), P(None, None)),
                        check_vma=False)(views, q_embs, q_masks)
    return out


# ----------------------------------------------------------------------
# Multi-host bucket placement (the grid tier; DESIGN_BACKENDS.md
# §Placement).  Under a 2-D hosts x candidates grid mesh each capacity
# bucket is pinned to one host group (sharding.PlacementPlan) and its
# doc axis spans that group's candidates devices only.  Each group runs
# what is effectively its own serving program — the per-group tier below
# is a single shard_map over the group's device row — and the merge tree
# gains one tier: a (n_q, k) candidate block per GROUP is exchanged and
# root-merged, instead of one block per shard crossing hosts.  This
# mirrors a real multi-controller deployment, where host groups run
# independent programs over the buckets they loaded
# (index_io sub-manifests) and only k-wide candidates travel between
# hosts.
# ----------------------------------------------------------------------


def _bucket_view(index: TokenIndex | PackedIndex, bucket_ids):
    """The slice of ``index`` holding exactly ``bucket_ids`` (ascending
    original indices): a PackedIndex carrying only those buckets (doc
    ids and ``n_docs`` stay corpus-global — the remap and the pad
    sentinel must agree across groups), the whole index for the dense
    layout's single bucket, or ``None`` for an empty selection."""
    if isinstance(index, PackedIndex):
        picked = [index.buckets[i] for i in bucket_ids]
        if not picked:
            return None
        return PackedIndex(n_docs=index.n_docs, m=index.m, dim=index.dim,
                           tokens_total=index.tokens_total,
                           compression=index.compression, buckets=picked,
                           residual_bits=index.residual_bits)
    return index if bucket_ids else None


def _group_view(index: TokenIndex | PackedIndex,
                placement: PlacementPlan, group: int):
    """The slice of ``index`` host group ``group`` stores — every
    bucket with ``group`` anywhere in its replica chain — or ``None``
    for a group that stores nothing."""
    return _bucket_view(index, placement.buckets_of(group))


def _resolve_placement(index, placement: PlacementPlan | None,
                       n_groups: int) -> PlacementPlan:
    n_buckets = (len(index.buckets) if isinstance(index, PackedIndex)
                 else 1)
    if placement is None:
        covered = _real_docs(index)
        n_docs = (index.n_docs if isinstance(index, PackedIndex)
                  else covered)
        if covered < n_docs:
            # A group-loaded partial view (index_io.load_index(group=g)):
            # deriving a fresh balanced plan would scatter the group's
            # own buckets across groups and silently drop documents from
            # every merge — the caller must say which group these
            # buckets serve.
            raise ValueError(
                f"index is a partial (group-loaded) view covering "
                f"{covered} of {n_docs} documents; pass an explicit "
                "placement (e.g. PlacementPlan(n_groups, (group,) * "
                "n_buckets)) instead of relying on the derived default")
        return PlacementPlan.for_index(index, n_groups)
    if placement.n_groups != n_groups:
        raise ValueError(
            f"placement has {placement.n_groups} host groups, the active "
            f"grid mesh has {n_groups}")
    return placement.validate(n_buckets)


def topk_search_group(index: TokenIndex | PackedIndex, q_embs: jnp.ndarray,
                      *, group: int, k: int = 10,
                      q_masks: jnp.ndarray | None = None,
                      backend: str | None = None,
                      placement: PlacementPlan | None = None,
                      buckets: tuple | None = None,
                      block_docs: int | None = None,
                      block_q: int | None = None,
                      chunk_docs: int | None = None):
    """One host group's tier of the grid merge tree: ``(ids, scores)``
    candidates, each ``(n_q, min(k, n_docs))``, from the buckets the
    placement pins to ``group`` — sentinel-padded (``-inf`` scores, id
    ``-1``) up to that width when the group holds fewer candidates,
    including a group that owns no buckets at all.

    ``buckets`` narrows the group to an explicit subset of its stored
    buckets (ascending original indices) — the failover hook: when a
    replica dies, the surviving replica serves exactly the dead one's
    buckets.  Every requested bucket must actually be stored on
    ``group`` (appear in its replica chain) — the replica placement
    law; a violation raises rather than silently serving data the
    group would not hold in a real deployment.

    Requires active grid rules (``sharding.serve_rules`` with a
    ``make_serve_mesh(hosts=...)`` mesh).  This is the computation one
    host group runs in a multi-controller deployment: a single
    ``shard_map`` over the group's device row, jittable on its own —
    the HLO-cleanliness assertions lower exactly this function.  The
    cross-group exchange and root merge live in :func:`topk_search`.
    """
    backend = backend_lib.resolve_backend(backend, allow=backend_lib.SERVING)
    mesh, n_groups, n_cand, rules_placement = grid_axes_for()
    if mesh is None:
        raise ValueError(
            "topk_search_group needs active grid serving rules "
            "(sharding.serve_rules with a hosts x candidates mesh from "
            "launch.mesh.make_serve_mesh(hosts=...))")
    if not 0 <= group < n_groups:
        raise ValueError(f"group {group} outside [0, {n_groups})")
    placement = _resolve_placement(
        index, placement if placement is not None else rules_placement,
        n_groups)
    n_q, l = q_embs.shape[:2]
    dim = q_embs.shape[-1]
    n_docs = (index.n_docs if isinstance(index, PackedIndex)
              else index.d_masks.shape[0])
    w = min(k, n_docs)
    if buckets is None:
        sub = _group_view(index, placement, group)
    else:
        for b in buckets:
            if group not in placement.replicas_of(b):
                raise ValueError(
                    f"bucket {b} is not stored on group {group} (replica "
                    f"chain {placement.replicas_of(b)}) — failover may "
                    "only target groups that hold a replica")
        sub = _bucket_view(index, tuple(sorted(buckets)))
    if sub is None:
        return (jnp.full((n_q, w), -1, jnp.int32),
                jnp.full((n_q, w), -jnp.inf, jnp.float32))
    plan = _streaming_plan(sub, n_q, l, dim, k, n_shards=n_cand,
                           n_groups=n_groups, replicas=placement.replicas,
                           block_docs=block_docs,
                           block_q=block_q, chunk_docs=chunk_docs)
    if n_cand > 1:
        import numpy as np
        from jax.sharding import Mesh
        submesh = Mesh(np.asarray(mesh.devices)[group], ("candidates",))
        i, v = _topk_search_sharded(sub, q_embs, q_masks, k,
                                    backend=backend, plan=plan,
                                    mesh=submesh, axes=("candidates",),
                                    n_shards=n_cand)
    else:
        i, v = _topk_search_local(sub, q_embs, q_masks, k, backend=backend,
                                  plan=plan)
    pad = w - i.shape[1]
    if pad > 0:     # fewer real candidates in this group than w
        i = jnp.pad(i, ((0, 0), (0, pad)), constant_values=-1)
        v = jnp.pad(v, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    return i, v


def _group_search_traceable(index, q_embs, q_masks, *, group, k, backend,
                            placement, buckets, block_docs, block_q,
                            chunk_docs):
    """Positional-arg adapter so one group's tier jits with (q, qm) as
    the only traced inputs (index and knobs ride as closure constants,
    the RetrievalServer closure pattern)."""
    return topk_search_group(index, q_embs, group=group, k=k,
                             q_masks=q_masks, backend=backend,
                             placement=placement, buckets=buckets,
                             block_docs=block_docs,
                             block_q=block_q, chunk_docs=chunk_docs)


def _grid_program(index, cache_args, group: int, buckets, *,
                  max_cached: int = 32):
    """The jitted program serving ``buckets`` on ``group``'s device
    row, LRU-cached on the index object.  Keying per (group, buckets)
    rather than per full group-set means a failover program (surviving
    replica serving a dead group's buckets) compiles once and is then
    as warm as the healthy ones — and a demoted group's program is
    simply never fetched again, so the cache cannot serve a stale
    group assignment.

    ``max_cached`` bounds the LRU (``RetrievalServer`` plumbs its
    ``max_cached_closures`` through ``program_cache_size``); every
    get/move_to_end/popitem runs under the per-index lock — the
    concurrent dispatch pool fetches programs for several groups at
    once, and an unguarded OrderedDict corrupts (or evicts a program
    another thread is mid-fetch on) under that interleaving.

    ``dict.setdefault`` is atomic under the GIL, so two threads racing
    the first fetch agree on one lock and one cache object."""
    lock = index.__dict__.setdefault("_grid_cache_lock", threading.Lock())
    cache = index.__dict__.setdefault("_grid_cache",
                                      collections.OrderedDict())
    (q_shape, qm_shape, k, backend, placement, mesh,
     block_docs, block_q, chunk_docs) = cache_args
    key = (group, buckets, q_shape, qm_shape, k, backend, placement, mesh,
           block_docs, block_q, chunk_docs)
    with lock:
        fn = cache.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(
                _group_search_traceable, index, group=group, k=k,
                backend=backend, placement=placement, buckets=buckets,
                block_docs=block_docs, block_q=block_q,
                chunk_docs=chunk_docs))
            cache[key] = fn
            while len(cache) > max(1, int(max_cached)):
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return fn


def _serving_assignment(placement: PlacementPlan, buckets, live, tried):
    """Route each of ``buckets`` to the first live link of its replica
    chain not already tried for it.  Returns (``{group: (buckets,)}``
    in ascending group order — deterministic dispatch, the merge is
    order-invariant anyway — and the buckets whose every replica is
    exhausted)."""
    per: dict = {}
    lost = []
    for b in buckets:
        g = next((g for g in placement.replicas_of(b)
                  if g in live and g not in tried[b]), None)
        if g is None:
            lost.append(b)
        else:
            per.setdefault(g, []).append(b)
    return {g: tuple(bs) for g, bs in sorted(per.items())}, lost


def _topk_search_grid(index, q_embs, q_masks, k, *, backend, mesh,
                      n_groups, placement, block_docs, block_q,
                      chunk_docs, monitor=None, faults=None,
                      selected=None, route_stats=None,
                      program_cache_size: int = 32):
    """The grid merge tree: every host group reduces its own buckets to
    a ``(n_q, w)`` candidate block (:func:`topk_search_group`, one
    shard_map over the group's device row), the blocks are exchanged —
    the ONLY cross-group traffic, k-wide, never corpus-sized — and one
    root sort-merge produces the replicated global top-k.  Bit-identical
    to the single-host dense oracle: groups partition the corpus (every
    doc lives in exactly one bucket; with replication each *replica
    level* partitions it and the root merge dedupes doc ids), each tier
    keeps a superset of the global top-k, and every merge uses the same
    ``(-score, id)`` total order.

    With a :class:`repro.serve.health.FleetMonitor` the exchange is
    fault-tolerant: each bucket is served by the first live link of its
    replica chain; a failed or deadline-overrunning fetch strikes the
    group (repeated strikes demote it permanently) and the bucket fails
    over — after a bounded exponential backoff — to its next surviving
    replica.  Buckets whose every replica is down drop out of the
    answer and the result reports ``coverage < 1`` (a
    :class:`TopKResult`) instead of raising; what remains is exact over
    the surviving buckets.  A :class:`~repro.serve.health.FaultPlan`
    injects kills/delays at the same dispatch/exchange seams real
    transport failures hit, so the tested failover path is the
    production path.  Without a monitor, failures propagate
    (``GroupFailure``) — the PR 5 stall-or-poison behavior, made loud.

    The exchange fetches each group's block off its devices (the
    multi-controller simulation of the cross-host hop), so this path
    cannot run under an enclosing jit — per-group compute still
    compiles inside its own shard_map, and a single-controller caller
    that wants one jitted program uses the flat ``--mesh host`` layout
    instead.  The per-group programs ARE jitted, cached on the index
    object per (group, buckets, query shape, k, backend, placement,
    mesh) so repeated query batches pay tracing once, like the
    server's closure cache.

    ``selected`` (the candidate router's bucket shortlist,
    serve/routing.py) restricts the whole tree to those buckets: the
    router runs BEFORE group dispatch, each selected bucket is served
    by the first replica of its chain, and a group owning no selected
    bucket is never dispatched, never fault-checked, and never counts
    against coverage — "not consulted" is not "failed".
    ``route_stats`` (a dict) receives the consulted-group exchange
    count."""
    if isinstance(q_embs, jax.core.Tracer):
        raise ValueError(
            "grid-placed topk_search performs a cross-group candidate "
            "exchange between per-group programs and cannot be traced "
            "under an enclosing jit; call it eagerly (RetrievalServer "
            "does this automatically under grid rules)")
    placement = _resolve_placement(index, placement, n_groups)
    if faults is not None:
        faults.begin_round()
    n_q = q_embs.shape[0]
    n_docs = (index.n_docs if isinstance(index, PackedIndex)
              else index.d_masks.shape[0])
    cache_args = (q_embs.shape,
                  None if q_masks is None else q_masks.shape, k, backend,
                  placement, mesh, block_docs, block_q, chunk_docs)

    if monitor is None:
        # Healthy fast path (and the unmonitored legacy path): every
        # group serves every bucket replica it stores; dispatch all
        # programs first (disjoint device rows — JAX async dispatch
        # overlaps them), then collect.  An injected fault without a
        # monitor propagates loudly.  A routed call instead dispatches
        # ONLY the groups owning selected buckets (one copy per
        # bucket: the first replica of its chain), so pruned groups
        # see no dispatch, no exchange, and no fault checks.
        if selected is None:
            dispatch = {g: None for g in range(n_groups)}
        else:
            per: dict = {}
            for b in selected:
                per.setdefault(placement.replicas_of(b)[0], []).append(b)
            dispatch = {g: tuple(bs) for g, bs in sorted(per.items())}
        fns = {g: _grid_program(index, cache_args, g, bs,
                                max_cached=program_cache_size)
               for g, bs in dispatch.items()}
        if faults is not None:
            for g in dispatch:
                faults.check(g, "dispatch")
        blocks = {g: fn(q_embs, q_masks) for g, fn in fns.items()}
        vals, ids = [], []
        for g, (i, v) in blocks.items():
            if faults is not None:
                faults.check(g, "exchange")
            ids.append(jnp.asarray(jax.device_get(i)))
            vals.append(jnp.asarray(jax.device_get(v)))
        if selected is None:
            merge = (_merge_topk if placement.replicas == 1
                     else _merge_topk_unique)
            cap = min(k, n_docs)
        else:
            # Each selected bucket was served exactly once, so ids are
            # already unique; the cap is the selected candidate pool.
            merge = _merge_topk
            cap = min(k, sum(index.buckets[b].n_docs for b in selected)
                      if isinstance(index, PackedIndex) else n_docs)
            if route_stats is not None:
                route_stats.update(groups_consulted=len(dispatch),
                                   n_groups=n_groups)
        i, v = merge(jnp.concatenate(vals, axis=1),
                     jnp.concatenate(ids, axis=1), cap)
        return TopKResult(i, v, 1.0)

    def attempt(group, bucket_ids):
        """One group's dispatch + deadline-bounded candidate fetch,
        with up to ``monitor.retries`` same-group retries; returns the
        (ids, vals) block or None after striking the group."""
        for r in range(monitor.retries + 1):
            if r:
                time.sleep(monitor.backoff(r - 1))
            try:
                if faults is not None:
                    faults.check(group, "dispatch")
                out = _grid_program(index, cache_args, group, bucket_ids,
                                    max_cached=program_cache_size
                                    )(q_embs, q_masks)
                t0 = time.perf_counter()

                def fetch():
                    if faults is not None:
                        faults.check(group, "exchange")
                    return (jnp.asarray(jax.device_get(out[0])),
                            jnp.asarray(jax.device_get(out[1])))

                if monitor.exchange_timeout is None:
                    block = fetch()
                else:
                    ex = concurrent.futures.ThreadPoolExecutor(1)
                    try:
                        block = ex.submit(fetch).result(
                            timeout=monitor.exchange_timeout)
                    finally:
                        # No wait: a straggler thread must not extend
                        # the deadline it just blew.
                        ex.shutdown(wait=False)
                monitor.record_exchange(group, time.perf_counter() - t0)
                return block
            except (health_lib.GroupFailure,
                    concurrent.futures.TimeoutError):
                monitor.strike(group)
        return None

    weights = bucket_weights(index)
    # A routed call's universe is the selected buckets: a pruned
    # bucket's group is "not consulted" — it is neither dispatched nor
    # counted in the coverage denominator, and its death cannot degrade
    # a result that never needed it.
    all_buckets = (range(placement.n_buckets) if selected is None
                   else selected)
    tried = {b: set() for b in all_buckets}
    pending, lost = _serving_assignment(placement, all_buckets,
                                        monitor.live(), tried)
    answered: list = []
    blocks = []
    consulted: set = set()
    failover = 0
    while pending:
        failed: list = []
        for g, bs in pending.items():
            for b in bs:
                tried[b].add(g)
            consulted.add(g)
        # Overlap the per-group programs and their deadline-bounded
        # candidate fetches over a worker pool (one worker per pending
        # group): a straggler group then costs max(latency), not
        # sum(latency), and a deadline overrun strikes it while the
        # healthy groups' exchanges complete in parallel.  Results are
        # collected in ascending group order regardless of completion
        # order, so the root merge sees the same deterministic block
        # sequence the sequential loop produced (the merge is
        # order-invariant anyway — belt and braces).  Monitor state is
        # safe under this fan-out: FleetMonitor serializes all
        # strike/heartbeat accounting under its own lock.
        if len(pending) == 1:
            results = {g: attempt(g, bs) for g, bs in pending.items()}
        else:
            # Axis rules are thread-local: hand the caller's rule set
            # (the grid mesh + placement) to each worker, or the group
            # program would trace without its mesh.
            rules = current_rules()

            def ruled_attempt(group, bucket_ids):
                with axis_rules(rules or {}):
                    return attempt(group, bucket_ids)

            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=len(pending)) as pool:
                futs = {g: pool.submit(ruled_attempt, g, bs)
                        for g, bs in pending.items()}
                results = {g: f.result() for g, f in futs.items()}
        for g, bs in pending.items():
            block = results[g]
            if block is None:
                failed.extend(bs)
            else:
                blocks.append(block)
                answered.extend(bs)
        if not failed:
            break
        pending, dead = _serving_assignment(placement, failed,
                                            monitor.live(), tried)
        lost.extend(dead)
        if pending:
            time.sleep(monitor.backoff(failover))
            failover += 1

    if selected is not None and route_stats is not None:
        route_stats.update(groups_consulted=len(consulted),
                           n_groups=n_groups)
    denom = sum(weights[b] for b in all_buckets)
    coverage = sum(weights[b] for b in answered) / max(denom, 1)
    if isinstance(index, PackedIndex):
        live_docs = sum(index.buckets[b].n_docs for b in answered)
    else:
        live_docs = n_docs if answered else 0
    cap = min(k, live_docs)
    if not blocks or cap == 0:
        return TopKResult(jnp.zeros((n_q, 0), jnp.int32),
                          jnp.zeros((n_q, 0), jnp.float32), coverage)
    # Monitored assignment serves each bucket from exactly one group,
    # but the dedupe merge is used unconditionally: it is bit-identical
    # to _merge_topk on unique ids, and the cap at the SURVIVING doc
    # count keeps sentinels out of degraded outputs (the same law the
    # local path applies via _real_docs).
    i, v = _merge_topk_unique(
        jnp.concatenate([v for _, v in blocks], axis=1),
        jnp.concatenate([i for i, _ in blocks], axis=1), cap)
    return TopKResult(i, v, coverage)


def _topk_search_routed(index, q_embs, q_masks, k, *, backend, route,
                        routing, n_probe, route_threshold, route_stats,
                        gmesh, n_groups, placement, mesh, axes, n_shards,
                        block_docs, block_q, chunk_docs, monitor, faults,
                        mutation, program_cache_size: int = 32):
    """The candidate-routing tier in front of the merge tree
    (serve/routing.py; see :func:`topk_search` for the contract).

    Selection is host-side: the centroid pass runs on device in one
    fused-MaxSim sweep, the (n_q, n_buckets) score/bound matrices come
    back to the host (they are router-sized, never corpus-sized), and
    the shortlist masks buckets out of every downstream path BEFORE
    any slab is scored — under a grid placement this happens before
    group dispatch, so a fully-pruned group is never consulted."""
    import numpy as np

    from repro.serve import routing as routing_lib

    if route not in routing_lib.ROUTES:
        raise ValueError(f"route={route!r} not in {routing_lib.ROUTES}")
    if routing is None:
        raise ValueError(
            f"route={route!r} needs a routing table — build one with "
            "serve.routing.RoutingIndex.build(index) or load the "
            "persisted sidecar (serve.index_io.load_routing)")
    if isinstance(q_embs, jax.core.Tracer):
        raise ValueError(
            "routed topk_search selects candidate buckets host-side "
            "(like the grid exchange) and cannot be traced under an "
            "enclosing jit; call it eagerly (RetrievalServer does this "
            "automatically for routed modes)")
    routing.validate_for(index)
    if n_probe is not None and n_probe < 1:
        raise ValueError(f"n_probe must be >= 1, got {n_probe}")
    n_q, l = q_embs.shape[:2]
    dim = q_embs.shape[-1]
    probe = 1 if n_probe is None else int(n_probe)

    s, u = routing_lib.centroid_scores(routing, q_embs, q_masks,
                                       backend=backend)
    s_host = np.asarray(jax.device_get(s))
    u_host = np.asarray(jax.device_get(u))

    delta_real = (sum(_real_docs(d) for d in mutation.deltas)
                  if mutation is not None else 0)

    def run(bucket_ids, stats=None):
        if gmesh is not None:
            return _topk_search_grid(
                index, q_embs, q_masks, k, backend=backend, mesh=gmesh,
                n_groups=n_groups, placement=placement,
                block_docs=block_docs, block_q=block_q,
                chunk_docs=chunk_docs, monitor=monitor, faults=faults,
                selected=tuple(bucket_ids), route_stats=stats,
                program_cache_size=program_cache_size)
        view = _bucket_view(index, tuple(bucket_ids))
        plan = _streaming_plan(view, n_q, l, dim, k, n_shards=n_shards,
                               block_docs=block_docs, block_q=block_q,
                               chunk_docs=chunk_docs)
        if mesh is not None and n_shards > 1:
            i, v = _topk_search_sharded(view, q_embs, q_masks, k,
                                        backend=backend, plan=plan,
                                        mesh=mesh, axes=axes,
                                        n_shards=n_shards)
            # The sharded root merge caps at the corpus size; a routed
            # view can hold fewer candidates, and the surplus columns
            # would be (-inf, pad-id) sentinels.
            cap = min(k, _real_docs(view))
            return i[:, :cap], v[:, :cap]
        delta_plans = ()
        if mutation is not None:
            delta_plans = tuple(
                _streaming_plan(d, n_q, l, dim, k, n_shards=1,
                                block_docs=block_docs, block_q=block_q,
                                chunk_docs=chunk_docs)
                for d in mutation.deltas)
        real_cap = _real_docs(view) + delta_real
        if mutation is not None:
            real_cap = min(real_cap, mutation.n_live)
        return _topk_search_local(view, q_embs, q_masks, k,
                                  backend=backend, plan=plan,
                                  mutation=mutation,
                                  delta_plans=delta_plans,
                                  real_cap=real_cap)

    if route == "nprobe":
        selected, _ = routing_lib.select_nprobe(s_host, probe,
                                                route_threshold)
    else:               # bounded: seed search -> admissible-bound filter
        seeds, _ = routing_lib.select_nprobe(s_host, probe)
        seed_out = run(seeds)
        sv = np.asarray(jax.device_get(seed_out[1]))
        # tau is each query's current k-th best — a valid pruning bar
        # only when the seeds actually produced k candidates; -inf
        # (select everything) otherwise.  A -inf entry at column k-1
        # (seed pool narrower than k finite docs) degrades to -inf
        # per query by itself.
        tau = (sv[:, k - 1] if sv.shape[1] >= k
               else np.full((sv.shape[0],), -np.inf, np.float32))
        selected = routing_lib.select_bounded(u_host, tau, seeds)

    out = run(selected, stats=route_stats)
    if route_stats is not None:
        nb = routing.n_buckets
        route_stats.update(route=route, n_buckets=nb,
                           buckets_scored=len(selected),
                           fraction=len(selected) / max(nb, 1))
    return out


def topk_search(index: TokenIndex | PackedIndex, q_embs: jnp.ndarray, *,
                k: int = 10, q_masks: jnp.ndarray | None = None,
                backend: str | None = None, block_docs: int | None = None,
                block_q: int | None = None, chunk_docs: int | None = None,
                placement: PlacementPlan | None = None,
                monitor=None, faults=None,
                mutation: MutationView | None = None,
                route: str = "exhaustive", routing=None,
                n_probe: int | None = None,
                route_threshold: float | None = None,
                route_stats: dict | None = None,
                program_cache_size: int = 32):
    """Streaming exact top-k MaxSim: ``(top_idx, top_scores)``, each
    (n_q, k), identical — ids and fp scores — to ``lax.top_k`` over
    :func:`maxsim_scores`, without ever holding an (n_q, n_docs) score
    matrix (asserted on the compiled HLO in tests/test_sharded_serving).

    Dataflow: each capacity bucket (each ``chunk_docs`` slab of it, each
    candidates-axis shard of it when the active sharding rules carry a
    mesh — ``sharding.serve_rules(mesh)``) scores its local docs with
    the normal per-backend scorers and immediately reduces to (n_q, k)
    (score, global-doc-id) candidates; sort-merges by the (-score, id)
    total order combine candidates up the tree, and under a mesh one
    k-wide all-gather per shard feeds the root merge.  Under a
    multi-host grid mesh (``make_serve_mesh(hosts=...)``) the tree
    gains one more tier: each host group merges only the buckets its
    ``sharding.PlacementPlan`` pins to it, and one (n_q, k) candidate
    block per *group* is exchanged for the root merge
    (:func:`topk_search_group`; DESIGN_BACKENDS.md §Placement).
    ``chunk_docs`` (and the usual serving blocks) default to the
    shape-aware autotuner, keyed on the shard-local bucket shape.

    ``placement`` overrides the grid placement the active rules carry
    (the rebalance hook); ``monitor`` (a ``serve.health.FleetMonitor``)
    makes the grid exchange fault-tolerant — the grid path then returns
    a :class:`TopKResult` whose ``coverage`` reports the fraction of
    stored bucket bytes consulted (< 1 when every replica of some
    bucket set was unreachable, instead of raising); ``faults`` (a
    ``serve.health.FaultPlan``) injects failures for testing.  All
    three are grid-only and ignored on the flat/local paths, which
    cannot lose a host group.

    ``mutation`` (a :class:`MutationView` from ``serve.mutation``)
    scores the live delta buckets as extra tournament leaves and masks
    tombstoned/shadowed doc ids to ``-inf`` before the root merge —
    bit-identical to re-packing the mutated corpus from scratch.
    Mutation serving is single-process by design (deltas are absorbed
    and compacted locally, then the compacted epoch redeploys to the
    grid); combining it with a candidates mesh or grid placement
    raises.

    ``route`` is the candidate-routing tier (serve/routing.py;
    DESIGN_BACKENDS.md §Candidate routing): ``"exhaustive"`` (default)
    sweeps every bucket as before; ``"nprobe"``/``"bounded"`` score
    ``routing`` (a :class:`~repro.serve.routing.RoutingIndex` built
    for THIS index epoch — a stale table refuses loudly) against the
    queries first and restrict the whole merge tree — local, sharded,
    or grid — to the shortlisted buckets.  ``"nprobe"`` keeps each
    query's ``n_probe`` best centroid-MaxSim buckets (optionally
    trimmed by the ``route_threshold`` score gap); ``"bounded"`` runs
    a seed search over the ``n_probe`` most-promising buckets and
    keeps every bucket whose admissible upper bound still reaches some
    query's k-th seed score — exact, bit-identical ids and scores to
    the exhaustive sweep.  Routed selection is host-side (like the
    grid exchange) so routed calls cannot be traced under an enclosing
    jit.  Under ``mutation`` the routed base is joined by ALL delta
    leaves, scored exhaustively — a routing table built at the base
    epoch knows nothing about fresh upserts, so delta docs are never
    route-pruned.  ``route_stats`` (a dict) receives the measured
    pruning: buckets scored vs. total, and consulted host groups under
    a grid.

    ``program_cache_size`` bounds the per-index LRU of jitted
    per-(group, buckets) grid programs (``RetrievalServer`` plumbs its
    ``max_cached_closures`` here so one knob bounds both caches);
    grid-only, ignored on the flat/local paths.
    """
    backend = backend_lib.resolve_backend(backend, allow=backend_lib.SERVING)
    n_q, l = q_embs.shape[:2]
    dim = q_embs.shape[-1]
    n_docs = (index.n_docs if isinstance(index, PackedIndex)
              else index.d_masks.shape[0])
    if mutation is not None and mutation.n_live == 0:
        return (jnp.zeros((n_q, 0), jnp.int32),
                jnp.zeros((n_q, 0), jnp.float32))
    if n_docs == 0 and mutation is None:
        return (jnp.zeros((n_q, 0), jnp.int32),
                jnp.zeros((n_q, 0), jnp.float32))
    gmesh, n_groups, _, rules_placement = grid_axes_for()
    mesh, axes, n_shards = mesh_axes_for("candidates")
    if mutation is not None and (gmesh is not None
                                 or (mesh is not None and n_shards > 1)):
        raise ValueError(
            "mutation serving (delta buckets + tombstones) is "
            "single-process: compact the delta log "
            "(serve.mutation.Compactor) before serving under a "
            "candidates mesh or grid placement")
    if route != "exhaustive":
        return _topk_search_routed(
            index, q_embs, q_masks, k, backend=backend, route=route,
            routing=routing, n_probe=n_probe,
            route_threshold=route_threshold, route_stats=route_stats,
            gmesh=gmesh, n_groups=n_groups,
            placement=placement if placement is not None
            else rules_placement,
            mesh=mesh, axes=axes, n_shards=n_shards,
            block_docs=block_docs, block_q=block_q, chunk_docs=chunk_docs,
            monitor=monitor, faults=faults, mutation=mutation,
            program_cache_size=program_cache_size)
    if gmesh is not None:
        return _topk_search_grid(
            index, q_embs, q_masks, k, backend=backend, mesh=gmesh,
            n_groups=n_groups,
            placement=placement if placement is not None
            else rules_placement,
            block_docs=block_docs, block_q=block_q,
            chunk_docs=chunk_docs, monitor=monitor, faults=faults,
            program_cache_size=program_cache_size)
    plan = _streaming_plan(index, n_q, l, dim, k, n_shards=n_shards,
                           block_docs=block_docs, block_q=block_q,
                           chunk_docs=chunk_docs)
    if mesh is not None and n_shards > 1:
        return _topk_search_sharded(index, q_embs, q_masks, k,
                                    backend=backend, plan=plan, mesh=mesh,
                                    axes=axes, n_shards=n_shards)
    delta_plans = ()
    if mutation is not None:
        delta_plans = tuple(
            _streaming_plan(d, n_q, l, dim, k, n_shards=1,
                            block_docs=block_docs, block_q=block_q,
                            chunk_docs=chunk_docs)
            for d in mutation.deltas)
    return _topk_search_local(index, q_embs, q_masks, k, backend=backend,
                              plan=plan, mutation=mutation,
                              delta_plans=delta_plans)


def _streaming_first_stage(index, q_embs, n_first: int):
    """Chunked first-stage candidate selection: the pooled single-vector
    scores stream through the same sort-merge as the exact path, so the
    serving closure never holds the (n_q, n_docs) first-stage matrix
    either.  Candidate ids come back in ``lax.top_k`` order (descending
    score, ties to the lowest doc id) — identical to the materializing
    stage 1."""
    pooled = index.pooled()                           # (n_docs, dim)
    pooled = constrain(pooled, "candidates", None)
    q_pool = q_embs.mean(1)
    n_docs = pooled.shape[0]
    chunk = max(64, _pow2_at_least(2 * n_first))
    vals, ids = _stream_chunk_topk(
        n_docs, chunk, n_first, lambda a, b: q_pool @ pooled[a:b].T)
    cand, _ = _merge_topk(vals, ids, n_first)
    return cand


def _gather_view(index: TokenIndex | PackedIndex):
    """(embs, masks) with one uniform token axis for the per-query
    candidate gather of the two-stage rerank.  Dense layout: the arrays
    themselves.  Packed layout: the cap_max-wide padded scratch view —
    still compacted relative to m, built lazily and cached."""
    if isinstance(index, PackedIndex):
        return index.padded()
    return index.d_embs, index.active_mask


def _rerank_candidates(index, q_embs, q_masks, cand, *, backend,
                       block_docs, block_q, n_docs):
    """Exact MaxSim rerank of each query's own candidate set.  The
    gather is the index lookup (cap_max-wide on the packed layout); only
    the *scoring* differs per backend.  Residual indexes on the fused
    backend gather COMPRESSED rows (codes/resq/per-token scales plus
    each candidate's bucket codebook) and decode inside the rerank
    kernel — the fp32 ``padded()`` scratch is never built on that
    path."""
    residual_fused = (isinstance(index, PackedIndex)
                      and index.compression == "residual"
                      and backend == backend_lib.FUSED)
    if residual_fused:
        codes_p, resq_p, bucket_of, g_masks, cbs, scales = (
            index.padded_residual())
        bsub = bucket_of[cand]                       # (n_q, n_first)
        m_sub = g_masks[cand]
        block_docs, _ = backend_lib.tuned_serving_blocks(
            q_embs.shape[0], n_docs, g_masks.shape[1], q_embs.shape[1],
            q_embs.shape[-1], block_docs, block_q, codec=index.codec_tag())
        return colbert_maxsim_residual_rerank_op(
            q_embs, codes_p[cand], resq_p[cand], scales[cand], cbs[bsub],
            m_sub, q_masks, bits=index.residual_bits, block_d=block_docs)
    g_embs, g_masks = _gather_view(index)
    d_sub = g_embs[cand]                             # (n_q, n_first, m, dim)
    m_sub = g_masks[cand]
    if backend == backend_lib.FUSED:
        # Batched multi-query rerank: every query's candidate block goes
        # through one fused kernel launch; no (n_q, n_first, l, m) tensor.
        block_docs, _ = backend_lib.tuned_serving_blocks(
            q_embs.shape[0], n_docs, g_masks.shape[1], q_embs.shape[1],
            q_embs.shape[-1], block_docs, block_q, codec=_codec_of(index))
        return colbert_maxsim_rerank_op(q_embs, d_sub, m_sub, q_masks,
                                        block_d=block_docs)
    s = jnp.einsum("qld,qnmd->qnlm", q_embs, d_sub)
    s = jnp.where(m_sub[:, :, None, :], s, NEG_INF)
    best = s.max(-1)
    if q_masks is not None:
        best = jnp.where(q_masks[:, None, :], best, 0.0)
    return best.sum(-1)                              # (n_q, n_first)


def search(index: TokenIndex | PackedIndex, q_embs: jnp.ndarray, *,
           k: int = 10, n_first: int = 64, end_to_end: bool = False,
           q_masks: jnp.ndarray | None = None,
           backend: str | None = None, block_docs: int | None = None,
           block_q: int | None = None, chunk_docs: int | None = None,
           return_full: bool = True,
           placement: PlacementPlan | None = None,
           monitor=None, faults=None,
           mutation: MutationView | None = None,
           route: str = "exhaustive", routing=None,
           n_probe: int | None = None,
           route_threshold: float | None = None,
           route_stats: dict | None = None,
           program_cache_size: int = 32):
    """Two-stage (or e2e) retrieval.

    ``return_full=True`` (the metrics/benchmark contract) returns
    (top_idx, top_scores, full) where ``full`` is the densified
    (n_q, n_docs) score matrix — and therefore takes the materializing
    path.  ``return_full=False`` (the serving default through
    ``RetrievalServer``) returns only (top_idx, top_scores) and streams:
    the e2e path routes through :func:`topk_search`, the two-stage path
    through the chunked first stage — no (n_q, n_docs) tensor is built
    on either.  Results are identical either way.  ``block_docs``/
    ``block_q``/``chunk_docs`` default to autotuned (see maxsim_scores /
    topk_search).  ``placement``/``monitor``/``faults`` ride through to
    :func:`topk_search` on the streaming e2e route (the only route with
    a cross-group exchange to protect) and are ignored elsewhere.
    """
    backend = backend_lib.resolve_backend(backend, allow=backend_lib.SERVING)
    n_docs = (index.n_docs if isinstance(index, PackedIndex)
              else index.d_embs.shape[0])
    if mutation is not None and not (end_to_end or n_first >= n_docs):
        raise ValueError(
            "mutation serving routes through the streaming e2e path "
            "only (the two-stage pooled first stage would consult "
            "stale base vectors); pass end_to_end=True or "
            "n_first >= n_docs")
    if mutation is not None and return_full:
        raise ValueError("mutation serving is streaming-only; "
                         "return_full=False required")
    if route != "exhaustive":
        if return_full:
            raise ValueError("routed serving is streaming-only; "
                             "return_full=False required")
        if not (end_to_end or n_first >= n_docs):
            raise ValueError(
                "candidate routing applies to the streaming e2e route "
                "only (the two-stage pooled first stage is its own "
                "shortlist); pass end_to_end=True")
    if end_to_end or n_first >= n_docs:
        if not return_full:
            return topk_search(index, q_embs, k=k, q_masks=q_masks,
                               backend=backend, block_docs=block_docs,
                               block_q=block_q, chunk_docs=chunk_docs,
                               placement=placement, monitor=monitor,
                               faults=faults, mutation=mutation,
                               route=route, routing=routing,
                               n_probe=n_probe,
                               route_threshold=route_threshold,
                               route_stats=route_stats,
                               program_cache_size=program_cache_size)
        scores = maxsim_scores(index, q_embs, q_masks, backend=backend,
                               block_docs=block_docs, block_q=block_q)
        scores = constrain(scores, "batch", "candidates")
        top_scores, top_idx = jax.lax.top_k(scores, k)
        return top_idx, top_scores, scores

    if not return_full:
        cand = _streaming_first_stage(index, q_embs, n_first)
    else:
        pooled = index.pooled()                      # (n_docs, dim)
        pooled = constrain(pooled, "candidates", None)
        q_pool = q_embs.mean(1)
        first = q_pool @ pooled.T                    # (n_q, n_docs)
        _, cand = jax.lax.top_k(first, n_first)      # (n_q, n_first)

    rerank = _rerank_candidates(index, q_embs, q_masks, cand,
                                backend=backend, block_docs=block_docs,
                                block_q=block_q, n_docs=n_docs)
    top_scores, local = jax.lax.top_k(rerank, min(k, n_first))
    top_idx = jnp.take_along_axis(cand, local, axis=1)
    if not return_full:
        return top_idx, top_scores
    # densify to full score matrix for metric computation; non-candidates
    # get the same NEG_INF sentinel masked scoring uses.
    full = jnp.full((q_embs.shape[0], n_docs), NEG_INF, rerank.dtype)
    full = jax.vmap(lambda f, c, r: f.at[c].set(r))(full, cand, rerank)
    return top_idx, top_scores, full


class RetrievalServer:
    """Batched request serving over a pruned index (examples/serve).

    ``index`` is either layout: the dense masked ``TokenIndex`` or the
    compacted ``PackedIndex`` artifact (typically loaded via
    ``repro.serve.index_io``).  ``backend`` is resolved once at
    construction.  Serving runs ``search(..., return_full=False)`` — the
    streaming top-k dataflow: the e2e exact path goes through
    :func:`topk_search` (per-bucket/per-shard merge, sharded over the
    candidates mesh axis when the active ``sharding.serve_rules`` carry
    a mesh), and no (n_q, n_docs) score matrix is ever densified on the
    serving path (that matrix is the metrics benchmarks' opt-in,
    ``return_full=True``).

    ``block_docs``/``block_q``/``chunk_docs`` default to ``None`` —
    autotuned per doc-array shape (per shard-local bucket shape on the
    packed layout); :meth:`_closure_for` warms the tuner cache eagerly,
    OUTSIDE the jitted closure, so steady-state traffic with a fixed
    batch shape pays resolution exactly once.

    One closure is built per (n_q, l) query-batch shape and kept in a
    small LRU (``max_cached_closures``, default 32): under varied
    traffic shapes the cache stays bounded — evicting a shape only costs
    a re-jit on its next appearance, while the unbounded dict the server
    used to keep grew a compiled executable (plus its baked-in index
    constants) per distinct shape for the life of the process.
    ``closure_builds`` and ``closure_hits`` count the LRU's misses and
    hits.  With :mod:`repro.obs` on, ``query_batch`` is marked
    ``repro.server.query_batch`` around ``repro.server.closure`` (arg
    ``built``), ``repro.server.run`` (copy in and dispatch) and
    ``repro.server.fetch`` (``device_get``, which waits for the device).
    A jitted closure's program is named ``jit_serve_topk``.

    **Fault tolerance** (grid serving only): pass a
    ``serve.health.FleetMonitor`` as ``monitor`` and the cross-group
    exchange heartbeats, times out, retries with backoff against
    surviving replicas, and demotes repeat offenders (see
    :func:`topk_search`).  ``on_group_loss`` picks the policy when
    every replica of some bucket set is gone:

    * ``"degrade"`` (default) — answer from the surviving buckets and
      report ``coverage < 1`` on the returned :class:`TopKResult`.
    * ``"rebalance"`` — re-place the lost groups' buckets over the
      survivors (``PlacementPlan.rebalance``) and re-answer at full
      coverage (this single-controller server holds the whole index;
      a real deployment would restore the moved buckets from their
      ``index_io`` sub-manifests first).
    * ``"fail"`` — raise ``serve.health.DegradedCoverage`` instead of
      returning a partial answer.

    **Thread safety** (DESIGN_BACKENDS.md §Serving loop):
    ``query_batch`` may be called from many threads at once — the
    closure LRU is lock-guarded (misses park waiters on a per-key
    future so one thread traces while the rest wait, instead of
    serializing every warm query behind a compile), and queries run
    inside a read gate that ``swap_index``/``apply_mutation`` close:
    a mutation waits for in-flight queries to drain and blocks new
    ones while it bumps the generation counters, so a closure from a
    stale generation never executes and every answer is attributable
    to exactly one ``(generation, mutation_gen, index.epoch)``
    snapshot (reported as ``TopKResult.epoch_key``).
    """

    def __init__(self, index: TokenIndex | PackedIndex, *, k: int = 10,
                 n_first: int = 64, backend: str | None = None,
                 block_docs: int | None = None, block_q: int | None = None,
                 chunk_docs: int | None = None,
                 max_cached_closures: int = 32,
                 monitor=None, on_group_loss: str = "degrade",
                 faults=None, route: str = "exhaustive", routing=None,
                 n_probe: int | None = None,
                 route_threshold: float | None = None):
        if on_group_loss not in ("degrade", "rebalance", "fail"):
            raise ValueError(
                f"on_group_loss={on_group_loss!r} not in "
                "('degrade', 'rebalance', 'fail')")
        from repro.serve import routing as routing_lib
        if route not in routing_lib.ROUTES:
            raise ValueError(
                f"route={route!r} not in {routing_lib.ROUTES}")
        if route != "exhaustive":
            if routing is None:
                raise ValueError(
                    f"route={route!r} needs a routing table "
                    "(serve.routing.RoutingIndex.build or "
                    "index_io.load_routing)")
            routing.validate_for(index)   # stale/mismatched: fail at ctor
            if n_probe is not None and n_probe < 1:
                raise ValueError(f"n_probe must be >= 1, got {n_probe}")
        self.index = index
        self.k = k
        self.n_first = n_first
        self.backend = backend_lib.resolve_backend(backend,
                                                   allow=backend_lib.SERVING)
        self.monitor = monitor
        self.on_group_loss = on_group_loss
        self.faults = faults
        self.route = route
        self.routing = routing
        self.n_probe = n_probe
        self.route_threshold = route_threshold
        self._block_docs = block_docs
        self._block_q = block_q
        self._chunk_docs = chunk_docs
        self._max_cached = max(1, int(max_cached_closures))
        # (shape, placement, epoch, ...) -> Future[closure].  Values are
        # futures so a cache miss parks concurrent same-shape callers on
        # the builder's result instead of tracing twice (or holding the
        # state lock across a trace).
        self._search = collections.OrderedDict()
        self.closure_builds = 0         # LRU misses, under _lock
        self.closure_hits = 0
        self._placement = None          # rebalance override, grid only
        self._rebalanced_for = frozenset()
        self._mutation = None           # live MutationView, local serving
        # Epoch/generation discipline: a compaction swap or delta-log
        # update must never be answered by a closure compiled over the
        # previous index arrays — both counters join the closure cache
        # key, and a swap drops the cache outright.
        self._generation = 0
        self._mutation_gen = 0
        # Concurrency: _lock guards all mutable server state (the
        # closure LRU, the generation counters, the rebalance
        # override); the condition on it implements a read/write gate —
        # query_batch runs inside a read section, swap_index/
        # apply_mutation are writers that drain in-flight queries
        # first.  Writers take priority (new readers park while one is
        # pending) so a mutation cannot starve under sustained load.
        self._lock = threading.RLock()
        self._gate = threading.Condition(self._lock)
        self._inflight = 0
        self._writers_waiting = 0

    @staticmethod
    def _run(index, q, **kw):
        return search(index, q, return_full=False, **kw)

    @property
    def epoch_key(self) -> tuple:
        """The ``(generation, mutation_gen, index.epoch)`` triple that
        fully determines which corpus state answers a query — the same
        components the closure LRU keys on, so anything cached under
        this key (the serving loop's result cache) is invalidated by
        exactly the events that drop closures: an epoch swap or a
        delta-log update."""
        return (self._generation, self._mutation_gen,
                getattr(self.index, "epoch", 0))

    @contextlib.contextmanager
    def _read_gate(self):
        """One query's read section.  Concurrent readers overlap;
        entry parks while a writer is waiting or active."""
        with self._gate:
            while self._writers_waiting:
                self._gate.wait()
            self._inflight += 1
        try:
            yield
        finally:
            with self._gate:
                self._inflight -= 1
                if self._inflight == 0:
                    self._gate.notify_all()

    @contextlib.contextmanager
    def _write_gate(self):
        """A mutation's write section: block new queries, drain the
        in-flight ones, then mutate exclusively.  This is what
        'mutations serialized against in-flight flushes' means — a
        generation bump can only happen with zero queries running, so
        no closure from a stale generation ever executes."""
        with self._gate:
            self._writers_waiting += 1
            try:
                while self._inflight:
                    self._gate.wait()
                yield
            finally:
                self._writers_waiting -= 1
                self._gate.notify_all()

    def swap_index(self, index, *, mutation=None, routing=None):
        """Switch serving to a new index epoch (the compaction swap).
        Drops every cached closure — programs compiled over the old
        epoch's arrays can never answer a post-swap query, even if the
        new index coincidentally shares shapes (the generation counter
        keys the cache too, so a stale entry cannot collide).

        Under a routed mode the swap must bring the new epoch's
        routing table along (the Compactor rebuilds the sidecar per
        epoch): the old table is stale by definition and
        ``validate_for`` refuses it here rather than on the first
        query."""
        if self.route != "exhaustive":
            if routing is None:
                raise ValueError(
                    f"route={self.route!r}: swap_index needs the new "
                    "epoch's routing table (index_io.load_routing — "
                    "the Compactor rebuilds it beside each epoch)")
            routing.validate_for(index)
        with self._write_gate():
            self.index = index
            if routing is not None:
                self.routing = routing
            self._mutation = mutation
            self._generation += 1
            self._mutation_gen += 1
            self._search.clear()

    def apply_mutation(self, mutation: MutationView | None):
        """Serve the given live delta-log view (upserts + tombstones)
        beside the current base index.  Each distinct view compiles its
        own closures (delta shapes differ per absorbed batch); the
        mutation generation joins the cache key and stale closures are
        dropped.  Like :meth:`swap_index`, the update drains in-flight
        queries first — no answer ever mixes two delta-log states."""
        with self._write_gate():
            self._mutation = mutation
            self._mutation_gen += 1
            self._search.clear()

    def _warm_index(self):
        """Materialize the packed index's derived serving views (pooled
        first-stage vectors, the cap_max-wide gather view) eagerly,
        outside jit — built inside a trace they would be uncacheable
        tracers, recomputed per closure."""
        if not isinstance(self.index, PackedIndex):
            return
        if self.n_first < self.index.n_docs:      # two-stage path
            self.index.pooled()
            if (self.index.compression == "residual"
                    and self.backend == backend_lib.FUSED):
                self.index.padded_residual()      # compressed rerank view
            else:
                self.index.padded()

    def _warm_tuner(self, q_embs):
        """Resolve every tuned block this query shape will need, outside
        jit (measured mode must never race inside a trace); the in-jit
        resolutions then hit the tuning cache."""
        n_q, l = q_embs.shape[:2]
        dim = q_embs.shape[-1]
        n_docs = (self.index.n_docs if isinstance(self.index, PackedIndex)
                  else self.index.d_masks.shape[0])
        if self.n_first >= n_docs or self._mutation is not None:
            # e2e route only: topk_search is the sole consumer of the
            # streaming keys, and resolving them (chunk_docs per
            # shard-local bucket shape — needed on BOTH backends, the
            # merge chunking is backend-agnostic) here means the
            # closure's in-trace resolutions always hit the cache.
            gmesh, n_groups, n_cand, placement = grid_axes_for()
            if gmesh is not None:
                # Grid placement: one key set per host group's bucket
                # slice (shards span only the group's candidates row).
                if self._placement is not None:
                    placement = self._placement
                placement = _resolve_placement(self.index, placement,
                                               n_groups)
                for g in range(n_groups):
                    sub = _group_view(self.index, placement, g)
                    if sub is not None:
                        _streaming_plan(sub, n_q, l, dim, self.k,
                                        n_shards=n_cand, n_groups=n_groups,
                                        replicas=placement.replicas,
                                        block_docs=self._block_docs,
                                        block_q=self._block_q,
                                        chunk_docs=self._chunk_docs)
            else:
                _, _, n_shards = mesh_axes_for("candidates")
                _streaming_plan(self.index, n_q, l, dim, self.k,
                                n_shards=n_shards,
                                block_docs=self._block_docs,
                                block_q=self._block_q,
                                chunk_docs=self._chunk_docs)
                if self._mutation is not None:
                    # Delta leaves resolve their own tuner keys (one
                    # per delta bucket shape, unsharded) — warmed here
                    # so the in-trace resolutions hit the cache.
                    for d in self._mutation.deltas:
                        _streaming_plan(d, n_q, l, dim, self.k,
                                        n_shards=1,
                                        block_docs=self._block_docs,
                                        block_q=self._block_q,
                                        chunk_docs=self._chunk_docs)
        if self.backend != backend_lib.FUSED:
            return
        if self._block_docs is not None and self._block_q is not None:
            return
        codec = _codec_of(self.index)
        if isinstance(self.index, PackedIndex):
            for b in self.index.buckets:
                backend_lib.tuned_serving_blocks(
                    n_q, b.n_docs, b.cap, l, dim,
                    self._block_docs, self._block_q, codec=codec)
            n_docs, m = self.index.n_docs, max(self.index.cap_max, 1)
        else:
            n_docs, m = self.index.d_masks.shape
        backend_lib.tuned_serving_blocks(n_q, n_docs, m, l, dim,
                                         self._block_docs, self._block_q,
                                         codec=codec)

    def _closure_for(self, q_embs):
        # The traced dataflow bakes in the ambient sharding context
        # (topk_search resolves mesh/axes at trace time), so the mesh,
        # candidate axes, and grid placement join the cache key — a
        # closure traced outside a mesh must not keep serving
        # single-device once the caller enters serve_rules(mesh), nor
        # vice versa.
        mesh, axes, _ = mesh_axes_for("candidates")
        gmesh, n_groups, _, placement = grid_axes_for()
        # The rebalance override joins the key: a closure traced against
        # the pre-loss placement must not answer post-rebalance queries.
        # The monitor itself does NOT join it — the grid route stays
        # eager and reads liveness at call time, so demotions never
        # leave a stale group program serving (tested: a group failing
        # between warmup and query).
        # The mutation epoch and the server's generation/mutation
        # counters join the key: a compaction swap (new index object,
        # possibly identical shapes) or a delta-log update must miss
        # the cache and re-trace over the new arrays.
        key = q_embs.shape[:2] + (mesh, axes, gmesh, n_groups, placement,
                                  self._placement,
                                  getattr(self.index, "epoch", 0),
                                  self._generation, self._mutation_gen,
                                  self.route, self.n_probe,
                                  self.route_threshold)
        # Lock-guarded LRU with per-key build futures: dictionary
        # get/move_to_end/popitem are atomic under the lock (concurrent
        # unguarded access corrupts an OrderedDict), while the
        # trace/compile of a miss happens OUTSIDE it — the first caller
        # of a shape builds, concurrent same-shape callers park on the
        # future, and warm traffic on other shapes never queues behind
        # a compile.
        with obs.span("repro.server.closure") as sp:
            with self._lock:
                entry = self._search.get(key)
                if entry is not None:
                    self._search.move_to_end(key)
                    building = False
                    self.closure_hits += 1
                else:
                    entry = concurrent.futures.Future()
                    self._search[key] = entry
                    while len(self._search) > self._max_cached:
                        self._search.popitem(last=False)  # evict LRU
                    building = True
                    self.closure_builds += 1
            sp.set_metadata(built=int(building))
            if not building:
                return entry.result()
            try:
                fn = self._build_closure(q_embs)
            except BaseException as e:
                entry.set_exception(e)
                with self._lock:
                    if self._search.get(key) is entry:
                        del self._search[key]    # failed build: retryable
                raise
            entry.set_result(fn)
            return fn

    def _build_closure(self, q_embs):
        """Trace/compile one serving closure for the CURRENT server
        state (only called inside a read section, so that state is
        stable for the duration)."""
        gmesh = grid_axes_for()[0]
        self._warm_index()
        self._warm_tuner(q_embs)
        n_docs = (self.index.n_docs
                  if isinstance(self.index, PackedIndex)
                  else self.index.d_masks.shape[0])
        routed = self.route != "exhaustive"
        fn = functools.partial(
            self._run, self.index, k=self.k, n_first=self.n_first,
            backend=self.backend, block_docs=self._block_docs,
            block_q=self._block_q, chunk_docs=self._chunk_docs,
            placement=self._placement, monitor=self.monitor,
            faults=self.faults, mutation=self._mutation,
            end_to_end=self._mutation is not None or routed,
            route=self.route, routing=self.routing,
            n_probe=self.n_probe,
            route_threshold=self.route_threshold,
            program_cache_size=self._max_cached)
        if (gmesh is None or self.n_first < n_docs) and not routed:
            # Grid-placed e2e serving stays an eager composition of
            # per-group compiled programs (the cross-group candidate
            # exchange cannot live inside one jit), and routed
            # modes select their bucket shortlist host-side — both
            # stay eager; everything else jits whole as before, under
            # a name the profiler's trace shows (jit_serve_topk).
            fn.__name__ = "serve_topk"
            fn = jax.jit(fn)
        return fn

    def _maybe_rebalance(self):
        """Apply ``PlacementPlan.rebalance`` over the monitor's demoted
        set (the ``--on-group-loss rebalance`` policy): surviving
        assignments stay put, stranded buckets re-place greedy-LPT over
        the survivors.  Idempotent per demoted set."""
        if self.monitor is None or self.on_group_loss != "rebalance":
            return False
        with self._lock:
            # Under the state lock (NOT the write gate — rebalance runs
            # from inside a query's read section): concurrent degraded
            # queries must agree on one new placement, and the
            # idempotence check makes the second one a no-op.
            demoted = self.monitor.demoted
            if not demoted or demoted == self._rebalanced_for:
                return False
            gmesh, n_groups, _, placement = grid_axes_for()
            if gmesh is None:
                return False
            base = _resolve_placement(
                self.index,
                self._placement if self._placement is not None
                else placement,
                n_groups)
            self._placement = base.rebalance(
                demoted, weights=bucket_weights(self.index))
            self._rebalanced_for = demoted
            return True

    def lowered_text(self, q_embs: jnp.ndarray) -> str:
        """The lowered program that answers ``q_embs``'s batch shape —
        where a caller checks which kernels serving runs (a compiled
        Pallas kernel appears as ``tpu_custom_call``).  Only jitted
        routes are one program; grid and routed serving are eager
        compositions and raise."""
        with self._read_gate():
            fn = self._closure_for(q_embs)
            if not hasattr(fn, "lower"):
                raise ValueError("this route serves an eager composition "
                                 "of programs, not one lowered program")
            return fn.lower(q_embs).as_text()

    def query_batch(self, q_embs: jnp.ndarray):
        """Serve one query batch: :class:`TopKResult` of host arrays.
        ``result.coverage < 1`` flags a degraded answer (every replica
        of some bucket set unreachable) under the default
        ``on_group_loss="degrade"``; ``"rebalance"`` re-places and
        re-answers at full coverage; ``"fail"`` raises.

        Thread-safe: the whole batch runs inside one read section, so
        its answer comes from exactly one (generation, mutation,
        epoch) snapshot — reported as ``result.epoch_key`` — and a
        concurrent ``swap_index``/``apply_mutation`` lands strictly
        before or strictly after it, never in the middle."""
        with obs.span("repro.server.query_batch"), self._read_gate():
            epoch_key = self.epoch_key
            fn = self._closure_for(q_embs)
            with obs.span("repro.server.run"):
                out = fn(q_embs)
            coverage = getattr(out, "coverage", 1.0)
            if coverage < 1.0 and self._maybe_rebalance():
                # Answer THIS query from the rebalanced plan (new
                # closure key), not just the next one.
                out = self._closure_for(q_embs)(q_embs)
                coverage = getattr(out, "coverage", 1.0)
            if coverage < 1.0 and self.on_group_loss == "fail":
                demoted = (sorted(self.monitor.demoted)
                           if self.monitor is not None else [])
                raise health_lib.DegradedCoverage(
                    f"top-k covers {coverage:.4f} of stored bucket "
                    f"bytes (demoted groups: {demoted}); "
                    "on_group_loss='fail' refuses degraded results")
            idx, scores = out
            with obs.span("repro.server.fetch"):
                res = TopKResult(jax.device_get(idx), jax.device_get(scores),
                                 coverage)
            res.epoch_key = epoch_key
            return res
