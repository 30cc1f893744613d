"""Length-bucketed corpus pruning pipeline (offline Alg. 1 at scale).

Corpus pruning runs the paper's Alg. 1 over every document.  The naive
batch path (`pruning_order_batch`) pads every document to the corpus
max length `m` and vmaps one fixed-shape scan — a real corpus is
ragged, so short documents pay full-`m` padding cost at every one of
their `m - 1` scan steps, and any new max length recompiles the world.

This pipeline cuts both costs:

1. **Bucketing** (:func:`bucket_plan`): documents are grouped by real
   token count into a few padded shape buckets (power-of-two widths by
   default, so the number of distinct compiled shapes is O(log m) no
   matter how ragged the corpus is).
2. **Within a bucket**: the pruning scan (``shortlist_topk`` on TPU,
   the reference elsewhere) is vmapped at the bucket width — a
   32-token document in the 32-wide bucket runs a 31-step scan over
   32-token score rows instead of an (m-1)-step scan over m-token rows.
3. **Across buckets**: bucket computations are dispatched back-to-back
   without blocking — JAX's async dispatch keeps the device busy on
   bucket i while bucket i+1 is being sliced and enqueued (the
   double-buffered streaming loop); results are gathered only after
   every bucket is in flight.

A post-pruning **token-pooling** pass (:func:`pool_tokens`) optionally
merges near-duplicate kept tokens within each document before packing —
near-duplicates contribute nearly identical MaxSim maxes, so pooling
buys a smaller packed/compressed artifact at bounded score cost
(`--pool-threshold` in the serve CLI).

Exactness: a document's pruning order depends only on its own real
tokens (dead/padded columns score ``NEG_INF`` and are never selected,
and both paths' per-step reductions are elementwise in the padded
axis), so truncating at the document's *effective length* — last alive
position + 1, which handles scattered (non-prefix) masks too — and
running it in a narrower bucket changes nothing.  The
assembled (ranks, errs, orders) are **bit-identical** to the
unbucketed `pruning_order_batch` on the same corpus — asserted over
ragged corpora in tests/test_pruning_pipeline.py.  Knob choices made
per bucket by the autotuner don't break this: the shortlist path is
exact for every legal (K, R), and tile sizes never change kernel
results.

The per-bucket ``(rank == width) -> m`` / order-padding fixups translate
the bucket-local "never removed" sentinels back to corpus-global
conventions; see `_scatter_bucket`.

Multi-host note: the bucket *plan* stays host-side (it is
data-dependent layout), but the per-bucket compute no longer does —
when the active sharding rules carry a mesh with a ``data`` axis wider
than 1 (or ``sharded=True`` forces it), each bucket's doc axis is
placed over ``data`` under ``shard_map`` and every shard runs the
selected backend on its local slice (per-document pruning is
embarrassingly parallel, so results stay bit-identical — asserted
against the unsharded path in tests/test_placement.py).
`global_keep_masks` shards its merge over `data` the same way
(bitwise-selection cut, O(log) scalar collectives — see
voronoi._global_keep_masks_sharded), so prune -> pack -> serve is
distributed end to end.

With :mod:`repro.obs` on, :func:`prune_corpus` is marked ``repro.prune``
(arg ``docs``) around ``repro.prune.plan`` (:func:`bucket_plan`), one
``repro.prune.dispatch`` per dispatch block (args ``width``, ``docs``),
``repro.prune.gather`` (the scatter back, which waits for the device)
and ``repro.prune.merge`` (``global_keep_masks``; arg ``traced``, 1 when
the call traced a new merge program, see ``voronoi.merge_traces``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import backend as backend_lib
from repro.core import voronoi
from repro.core.tuning import _pow2_at_least, pruning_docs_per_block

__all__ = [
    "Bucket",
    "bucket_plan",
    "effective_lengths",
    "pool_tokens",
    "pruning_order_bucketed",
    "prune_corpus",
]


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One padded shape bucket: ``indices`` into the corpus doc axis,
    all with real length <= ``width``."""

    width: int
    indices: np.ndarray

    def __repr__(self):  # keep test failure output readable
        return f"Bucket(width={self.width}, n_docs={len(self.indices)})"


def effective_lengths(d_masks) -> np.ndarray:
    """Per-document effective length: last alive position + 1 (0 when
    fully masked).  This — not the alive COUNT — is what bucket widths
    must cover: truncating a document at its effective length drops
    only dead trailing columns, so any mask layout (prefix-padded or
    scattered, e.g. stopword-filtered) buckets correctly."""
    masks = np.asarray(d_masks)
    m = masks.shape[1]
    any_alive = masks.any(axis=1)
    last = m - np.argmax(masks[:, ::-1], axis=1)
    return np.where(any_alive, last, 0).astype(np.int64)


def bucket_plan(n_real, m: int, *, granularity: int | str = "pow2",
                min_width: int = 8) -> list[Bucket]:
    """Group documents into padded shape buckets by effective length
    (:func:`effective_lengths` — pass alive counts only for corpora
    known to be prefix-padded).

    ``granularity="pow2"`` rounds each document's length up to the next
    power of two (bounding distinct compiled shapes by O(log m));
    an integer rounds up to that multiple instead.  Widths are clamped
    to [min_width, m].  Every document lands in exactly one bucket and
    buckets are ordered by width (ascending) — the cheap buckets
    dispatch first, maximizing compute/dispatch overlap for the big
    ones.  Host-side by design: the plan is data-dependent (real
    lengths), which is exactly what fixed-shape jitted code cannot
    branch on.
    """
    n_real = np.asarray(n_real)
    if n_real.ndim != 1:
        raise ValueError(f"n_real must be 1-D, got shape {n_real.shape}")
    if granularity == "pow2":
        width_of = _pow2_at_least
    elif isinstance(granularity, int) and granularity >= 1:
        width_of = lambda x: -(-x // granularity) * granularity
    else:
        raise ValueError(f"granularity={granularity!r}: 'pow2' or int >= 1")
    widths = np.array([min(m, max(min_width, width_of(max(int(x), 1))))
                       for x in n_real], np.int64)
    return [Bucket(width=int(w), indices=np.flatnonzero(widths == w))
            for w in np.unique(widths)]


def _order_len(width: int, step_size: int) -> int:
    """Length of the flattened removal-order record a pruning backend
    emits for documents of padded length ``width`` (0 for width <= 1)."""
    n_steps = -(-(width - 1) // step_size)
    return n_steps * step_size


def _scatter_bucket(ranks, errs, orders, bucket, local, m: int):
    """Write one bucket's (rank, err, order) rows back into the
    corpus-global arrays, translating bucket-local sentinels:
    ``rank == width`` (never removed: the survivor, dead and padded
    slots) becomes the global sentinel ``m``; order rows are left-
    aligned (removal positions never exceed width - 2) and stay -1
    padded to the global record length."""
    r, e, o = (np.asarray(x) for x in local)
    w = bucket.width
    ranks[bucket.indices, :w] = np.where(r >= w, m, r)
    errs[bucket.indices, :w] = e
    orders[bucket.indices, :o.shape[1]] = o


def _bucket_order_sharded(e, k, samples, mesh, **kw):
    """One bucket's pruning orders under ``shard_map`` over ``data``:
    the doc axis is padded to a multiple of the shard count with
    all-masked documents (the pipeline already translates their
    sentinel outputs, and pad rows are dropped on the way out), every
    shard runs the normal batch path on its local slice, and the
    outputs shard straight back over ``data``.  Per-document pruning
    touches no cross-document state, so this is bit-identical to the
    unsharded dispatch."""
    from jax.sharding import PartitionSpec as P

    n_b = e.shape[0]
    n_shards = mesh.shape["data"]
    pad = (-n_b) % n_shards
    if pad:
        e = jnp.pad(e, ((0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, pad), (0, 0)))

    def body(eb, kb, s):
        return voronoi.pruning_order_batch(eb, kb, s, **kw)

    r, er, o = jax.shard_map(body, mesh=mesh,
                             in_specs=(P("data", None, None),
                                       P("data", None), P(None, None)),
                             out_specs=(P("data", None),) * 3,
                             check_vma=False)(e, k, samples)
    return r[:n_b], er[:n_b], o[:n_b]


def _doc_blocks(plan, n_samples: int):
    """Split each bucket into dispatch blocks of the tuner's
    ``pruning_docs_per_block``: ``(block, rows)`` pairs, ``rows`` being
    the document count the block runs at (the last block of a split
    bucket is padded with all-masked documents, so every block of a
    width shares one program)."""
    for bucket in plan:
        n = len(bucket.indices)
        per = pruning_docs_per_block(n_samples, bucket.width)
        if not per or per >= n:
            yield bucket, n
            continue
        for lo in range(0, n, per):
            yield Bucket(bucket.width, bucket.indices[lo:lo + per]), per


def _dispatch_block(d_embs, d_masks, samples, bucket, rows, mesh,
                    needs_tuner, kw):
    """Slice one block's documents to its bucket width, pad it to
    ``rows`` documents and dispatch its pruning orders without waiting
    for them; the pad rows are sliced off the (still pending) outputs."""
    idx = jnp.asarray(bucket.indices)
    e = jnp.take(d_embs, idx, axis=0)[:, :bucket.width]
    k = jnp.take(d_masks, idx, axis=0)[:, :bucket.width]
    pad = rows - len(bucket.indices)
    if pad:
        e = jnp.pad(e, ((0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, pad), (0, 0)))
    if mesh is not None:
        if needs_tuner:
            # Warm the tuner for this bucket shape OUTSIDE the trace: the
            # in-trace knob resolutions then hit the cache (measured mode
            # must never race inside shard_map tracing).
            backend_lib.tuned("pruning", n_samples=samples.shape[0],
                              m=bucket.width, dim=d_embs.shape[-1])
        out = _bucket_order_sharded(e, k, samples, mesh, **kw)
    else:
        out = voronoi.pruning_order_batch(e, k, samples, **kw)
    if pad:
        out = tuple(o[:len(bucket.indices)] for o in out)
    return out


def pruning_order_bucketed(d_embs, d_masks, samples, *, step_size: int = 1,
                           backend: str | None = None,
                           granularity: int | str = "pow2",
                           min_width: int = 8,
                           plan: list[Bucket] | None = None,
                           sharded: bool | None = None):
    """Length-bucketed equivalent of `voronoi.pruning_order_batch`.

    Same signature semantics and bit-identical (ranks, errs, orders);
    see the module docstring for the why and the exactness argument.
    ``plan`` overrides the computed :func:`bucket_plan` (reuse it when
    pruning several sample sets over one corpus).  ``sharded`` selects
    the ``shard_map``-over-``data`` bucket compute (:func:`_data_mesh`
    policy: auto under a data mesh, forced with ``True``); the plan
    itself is always computed once, host-side; each bucket dispatches
    in blocks that fit device memory (:func:`_doc_blocks`).
    """
    n_docs, m = d_masks.shape
    order_len = _order_len(m, step_size)
    ranks = np.full((n_docs, m), m, np.int32)
    errs = np.full((n_docs, m), np.inf, np.float32)
    orders = np.full((n_docs, order_len), -1, np.int32)
    if n_docs == 0:
        return jnp.asarray(ranks), jnp.asarray(errs), jnp.asarray(orders)

    if plan is None:
        with obs.span("repro.prune.plan"):
            plan = bucket_plan(effective_lengths(d_masks), m,
                               granularity=granularity, min_width=min_width)
    from repro.sharding.specs import data_mesh_for
    mesh = data_mesh_for(sharded, who="pruning_order_bucketed")
    # Only the kernel scan consumes the pruning tuner's knobs — skipping
    # the warm for reference keeps measured mode (REPRO_AUTOTUNE=measure)
    # from racing kernels nobody will run.
    needs_tuner = (mesh is not None
                   and voronoi.resolve_pruning_backend(
                       backend, step_size=step_size)
                   != backend_lib.REFERENCE)

    # Stream buckets: slice + dispatch everything first (async dispatch
    # overlaps bucket i's compute with bucket i+1's staging — the
    # double-buffered loop), then gather.
    in_flight = []
    kw = dict(step_size=step_size, backend=backend)
    for bucket, rows in _doc_blocks(plan, samples.shape[0]):
        with obs.span("repro.prune.dispatch", width=bucket.width,
                      docs=len(bucket.indices)):
            out = _dispatch_block(d_embs, d_masks, samples, bucket, rows,
                                  mesh, needs_tuner, kw)
        in_flight.append((bucket, out))
    with obs.span("repro.prune.gather"):
        for bucket, out in in_flight:
            _scatter_bucket(ranks, errs, orders, bucket, out, m)
        return jnp.asarray(ranks), jnp.asarray(errs), jnp.asarray(orders)


def pool_tokens(d_embs, keep, threshold: float):
    """Greedy within-document token pooling (Clavié-style): merge kept
    tokens whose cosine similarity to an earlier kept token reaches
    ``threshold`` into one representative (the mean of the pool), then
    drop the absorbed members from ``keep``.  Run between pruning and
    :meth:`PackedIndex.pack` — near-duplicate tokens contribute almost
    identical MaxSim maxes, so pooling shrinks the packed index (fewer
    kept tokens -> narrower capacity buckets) at bounded score cost.

    Host-side by design, like :func:`bucket_plan`: which tokens merge
    is data-dependent.  Greedy in original token order: each unclaimed
    kept token opens a pool, absorbs every later unclaimed kept token
    within ``threshold``, and its slot takes the pool mean (members'
    slots leave ``keep``).  ``threshold >= 1.0 - 1e-6`` only merges
    exact duplicates; lower thresholds merge more aggressively.
    Returns ``(pooled_embs, new_keep)`` as numpy arrays; slots outside
    ``new_keep`` are zeroed so the packed bytes stay deterministic.
    """
    if not 0.0 < threshold <= 1.0:
        raise ValueError(f"threshold must be in (0, 1], got {threshold}")
    embs = np.array(d_embs, np.float32)
    kp = np.array(keep, bool)
    n_docs = kp.shape[0]
    for i in range(n_docs):
        idx = np.flatnonzero(kp[i])
        if idx.size < 2:
            continue
        e = embs[i, idx]
        nrm = np.maximum(np.linalg.norm(e, axis=-1, keepdims=True), 1e-12)
        cos = (e / nrm) @ (e / nrm).T
        claimed = np.zeros(idx.size, bool)
        for a in range(idx.size):
            if claimed[a]:
                continue
            absorbed = np.flatnonzero(~claimed & (cos[a] >= threshold))
            absorbed = absorbed[absorbed > a]   # the seed joins regardless
            pool = np.concatenate([[a], absorbed])
            claimed[pool] = True
            embs[i, idx[a]] = e[pool].mean(0)
            kp[i, idx[absorbed]] = False
    embs[~kp] = 0.0
    return embs, kp


def prune_corpus(d_embs, d_masks, samples, keep_fraction: float, *,
                 backend: str | None = None, step_size: int = 1,
                 granularity: int | str = "pow2", min_width: int = 8,
                 sharded: bool | None = None):
    """Corpus-level pruning, end to end: bucketed per-doc orders merged
    into global keep masks (§4.2) under a corpus-wide token budget.
    Returns (keep_masks (n_docs, m), ranks, errs).

    ``sharded`` distributes BOTH halves over the ``data`` mesh axis —
    the per-bucket orders (:func:`pruning_order_bucketed`) and the
    global merge (``voronoi.global_keep_masks``) — with the same
    auto/force/off policy; results are bit-identical either way."""
    with obs.span("repro.prune", docs=d_masks.shape[0]):
        ranks, errs, _ = pruning_order_bucketed(
            d_embs, d_masks, samples, backend=backend, step_size=step_size,
            granularity=granularity, min_width=min_width, sharded=sharded)
        with obs.span("repro.prune.merge") as sp:
            traces = voronoi.merge_traces()
            keep = voronoi.global_keep_masks(ranks, errs, d_masks,
                                             keep_fraction, sharded=sharded)
            sp.set_metadata(traced=int(voronoi.merge_traces() > traces))
        return keep, ranks, errs
