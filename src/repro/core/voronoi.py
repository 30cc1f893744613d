"""Voronoi Pruning — the paper's core contribution (§4, Alg. 1).

Casting token pruning as Voronoi-cell mass estimation:

  *  ``V_i = {q : d_i = argmax_d q.d}``  (Eq. 5) — the cell of token i;
  *  ``Error(d_i) = E_{q in V_i}[q.d_i - second_best(q)]``  (Eq. 6–7);
  *  Monte-Carlo estimate over N unit-sphere samples (Eq. 8);
  *  iterative greedy removal with incremental cell reassignment (Alg. 1);
  *  corpus-level ("global") pruning by merging per-document orders;
  *  optional step-size > 1 and beam-search variants (ablations, §6.2).

Pruning has two paths.  ``backend="reference"`` is the materialising
oracle in pure jnp (fixed shapes, jit/vmap/scan friendly): the off-TPU
default and the only path for ``step_size > 1``.
``backend="shortlist_topk"`` — the TPU default — runs the exact top-K
shortlist scan with its periodic rescan through the
``repro.kernels.maxsim_topk`` Pallas kernel (no (N, m) score matrix,
no TopK custom-call, partitionable under GSPMD).  The platform picks
between them (:func:`resolve_pruning_backend`, ``repro.core.backend``);
tile sizes and shortlist schedules come from the shape-aware autotuner
(``repro.core.tuning``) unless pinned.  Corpus-scale jobs use the
length-bucketed pipeline (``repro.core.pruning_pipeline``).

Shape conventions: one document is (m, dim) + bool mask (m,); samples
(N, dim).  Batch versions vmap over the leading doc axis.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import backend as backend_lib
from repro.core.scoring import NEG_INF, top2_scores
from repro.kernels.maxsim_topk.ops import maxsim_topk_op

__all__ = [
    "CellState",
    "assign_cells",
    "token_errors",
    "estimate_errors",
    "pruning_order",
    "pruning_order_batch",
    "beam_pruning_order",
    "keep_mask_from_order",
    "prune_to_size",
    "global_keep_masks",
    "merge_traces",
    "mean_error",
    "mean_error_batch",
]


class CellState(NamedTuple):
    """Per-sample Voronoi bookkeeping under the current alive-token set."""

    best: jax.Array      # (N,)  best dot product
    second: jax.Array    # (N,)  second-best dot product
    bi: jax.Array        # (N,)  index of best token  (cell membership)
    si: jax.Array        # (N,)  index of second-best token


def _top2_from_scores(scores: jax.Array, alive: jax.Array) -> CellState:
    """(best, second, argbest, argsecond) over alive tokens; scores (N, m)."""
    s = jnp.where(alive[None, :], scores, NEG_INF)
    bi = jnp.argmax(s, axis=-1)
    best = jnp.take_along_axis(s, bi[:, None], axis=-1)[:, 0]
    s2 = s.at[jnp.arange(s.shape[0]), bi].set(NEG_INF)
    si = jnp.argmax(s2, axis=-1)
    second = jnp.take_along_axis(s2, si[:, None], axis=-1)[:, 0]
    return CellState(best, second, bi, si)


def assign_cells(d_emb: jax.Array, d_mask: jax.Array,
                 samples: jax.Array) -> CellState:
    """Initial cell assignment for all samples (Eq. 5)."""
    best, second, bi, si = top2_scores(samples, d_emb, d_mask)
    return CellState(best, second, bi, si)


def token_errors(state: CellState, alive: jax.Array, n_samples: int) -> jax.Array:
    """Eq. 8: per-token expected pruning error from the current cell state.

    err[i] = (1/N) * sum_{q : bi(q) = i} (best(q) - second(q)).
    Dead tokens get +inf (never selectable).  Tokens with empty cells get
    exactly 0 — removing them is free *right now*, matching Eq. 8.
    """
    m = alive.shape[0]
    gap = state.best - state.second
    err = jnp.zeros((m,), state.best.dtype).at[state.bi].add(gap) / n_samples
    return jnp.where(alive, err, jnp.inf)


def estimate_errors(d_emb: jax.Array, d_mask: jax.Array,
                    samples: jax.Array) -> jax.Array:
    """One-shot (non-iterative) Monte-Carlo error estimate per token."""
    state = assign_cells(d_emb, d_mask, samples)
    return token_errors(state, d_mask, samples.shape[0])


def _select_removals(err: jax.Array, alive: jax.Array, step_size: int):
    """One Alg. 1 removal step: pick up to ``step_size`` cheapest alive
    tokens (never the last survivor) and kill them.

    Returns (new_alive, sel_idx, sel_err, removed_any).  Ties break as
    in lax.top_k: the lowest index wins.
    """
    n_alive = jnp.sum(alive)
    k_want = jnp.minimum(step_size, jnp.maximum(n_alive - 1, 0))
    vals, idxs = jax.lax.top_k(-err, step_size)            # cheapest first
    take = jnp.arange(step_size) < k_want
    sel_idx = jnp.where(take, idxs, -1)
    sel_err = jnp.where(take, -vals, jnp.inf)
    # Single masked scatter: padded (-1) slots redirect out of bounds and
    # drop, so step_size > 1 no longer unrolls one scatter per index.
    safe_idx = jnp.where(sel_idx >= 0, sel_idx, err.shape[0])
    new_alive = alive.at[safe_idx].set(False, mode="drop")
    return new_alive, sel_idx, sel_err, k_want > 0


def _order_to_rank(order_steps, err_steps, m: int):
    """Flatten per-step removal records into (rank, err_at_removal, order)."""
    order = order_steps.reshape(-1)                        # (n_steps*step,)
    errs = err_steps.reshape(-1)
    rank = jnp.full((m,), m, jnp.int32)
    err_at_removal = jnp.full((m,), jnp.inf, errs.dtype)
    pos = jnp.arange(order.shape[0], dtype=jnp.int32)
    valid = order >= 0
    safe_order = jnp.where(valid, order, m)  # scatter pad -> dropped row
    rank = rank.at[safe_order].min(jnp.where(valid, pos, m), mode="drop")
    err_at_removal = err_at_removal.at[safe_order].min(
        jnp.where(valid, errs, jnp.inf), mode="drop")
    # Final survivor: rank m-1 equivalent (last), err inf (never prune).
    return rank, err_at_removal, order


@functools.partial(jax.jit, static_argnames=("step_size",))
def _pruning_order_reference(d_emb, d_mask, samples, *, step_size):
    """Materializing path: the (N, m) score matrix is computed once and
    stays resident; each step re-reduces the masked matrix."""
    n, m = samples.shape[0], d_emb.shape[0]
    scores = samples @ d_emb.T
    scores = jnp.where(d_mask[None, :], scores, NEG_INF)

    state0 = _top2_from_scores(scores, d_mask)
    n_steps = -(-(m - 1) // step_size)  # ceil: leave >= 1 token alive

    def body(carry, step):
        alive, st = carry
        err = token_errors(st, alive, n)
        new_alive, sel_idx, sel_err, removed_any = _select_removals(
            err, alive, step_size)
        # Incremental reassignment: only samples whose best or second died
        # need new top-2; everyone else keeps their triple (Alg.1 + §4.2
        # "only the queries previously assigned to its Voronoi cell need to
        # be reassigned").  Fixed shapes make a per-sample gather
        # impossible, so the recompute is all-or-nothing: lax.cond skips
        # the O(N*m) reduction entirely on steps where the removed tokens
        # were nobody's best or second (free removals — duplicate or
        # empty-cell tokens).  Under vmap (pruning_order_batch) the cond
        # lowers to a select and both branches run.
        died_b = ~new_alive[st.bi]
        died_s = ~new_alive[st.si]
        affected = (died_b | died_s) & removed_any

        def recompute(st):
            fresh = _top2_from_scores(scores, new_alive)
            return CellState(
                best=jnp.where(affected, fresh.best, st.best),
                second=jnp.where(affected, fresh.second, st.second),
                bi=jnp.where(affected, fresh.bi, st.bi),
                si=jnp.where(affected, fresh.si, st.si),
            )

        st2 = jax.lax.cond(jnp.any(affected), recompute, lambda st: st, st)
        return (new_alive, st2), (sel_idx, sel_err)

    (_, _), (order_steps, err_steps) = jax.lax.scan(
        body, (d_mask, state0), jnp.arange(n_steps))
    return _order_to_rank(order_steps, err_steps, m)


def resolve_pruning_backend(backend: str | None = None, *,
                            step_size: int = 1) -> str:
    """The one pruning-path resolution, shared by :func:`pruning_order`,
    :func:`pruning_order_batch`, the bucketed pipeline and the launcher:
    an explicit ``backend`` (checked against ``backend_lib.PRUNING``),
    else ``REPRO_BACKEND``, else the platform's — ``shortlist_topk`` on
    TPU, ``reference`` elsewhere.  ``step_size > 1`` has only the
    reference.  Call OUTSIDE jit."""
    allow = (backend_lib.PRUNING if step_size == 1
             else (backend_lib.REFERENCE,))
    return backend_lib.resolve_backend(backend, allow=allow)


def pruning_order(d_emb: jax.Array, d_mask: jax.Array, samples: jax.Array,
                  *, step_size: int = 1, backend: str | None = None,
                  block_s: int | None = None, block_t: int | None = None,
                  shortlist: int | None = None,
                  rescan_every: int | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Iterative Voronoi pruning (Alg. 1) producing a full removal order.

    Returns ``(rank, err_at_removal, order)`` where

      * ``rank[i]``  — removal step of token i (0 = pruned first); the final
        surviving token and padded slots get rank m-1 / m and err ``inf``;
      * ``err_at_removal[i]`` — Eq. 8 error of token i at the step it was
        removed (the quantity merged across docs for global pruning);
      * ``order[s]`` — token removed at step s (-1 for invalid steps).

    ``step_size > 1`` removes the ``step_size`` lowest-error tokens per
    iteration between recomputations (§6.2 "Effect of Step Size").

    ``backend`` selects the execution path (:func:`resolve_pruning_backend`):
    ``"reference"`` keeps the (N, m) score matrix resident;
    ``"shortlist_topk"`` runs the exact top-K shortlist scan with a
    ``maxsim_topk``-kernel rescan (:func:`pruning_order_shortlist`);
    ``None`` resolves to shortlist_topk on TPU, reference elsewhere.
    Both paths share selection semantics — orders are identical up to
    float tie-breaking (see tests/test_backend_dispatch.py).

    Tile sizes (``block_s``/``block_t``) and the shortlist schedule
    (``shortlist``/``rescan_every``) default to ``None`` — filled in by
    the shape-aware autotuner (``repro.core.tuning``) via the backend
    seam; explicit values win.

    This wrapper is deliberately NOT jitted: backend resolution (env
    var, platform) and autotuning happen eagerly at call time; only the
    per-backend implementations carry jit caches.
    """
    backend = resolve_pruning_backend(backend, step_size=step_size)
    if backend == backend_lib.SHORTLIST_TOPK:
        return pruning_order_shortlist(d_emb, d_mask, samples,
                                       shortlist=shortlist,
                                       rescan_every=rescan_every,
                                       block_s=block_s, block_t=block_t)
    return _pruning_order_reference(d_emb, d_mask, samples,
                                    step_size=step_size)


@functools.partial(jax.jit, static_argnames=("shortlist", "rescan_every",
                                              "block_s", "block_t"))
def _pruning_order_shortlist_impl(d_emb, d_mask, samples, *, shortlist,
                                  rescan_every, block_s, block_t):
    """Nested-scan shortlist pruning, rescanned through the fused
    ``maxsim_topk`` Pallas kernel: score tiles live in VMEM, no (N, m)
    matrix is ever cached, and the grid is plain data parallelism over
    sample blocks — the path that shards over samples/docs on a
    multi-host mesh.

    The inner steps are scatter-free (§Perf): validity of shortlist
    entries is maintained by compare-and-mask instead of an (N, K)
    gather + row scatter, and the Eq. 8 error accumulation is a one-hot
    matmul (an MXU-friendly segment-sum whose (N, m) one-hot is a
    transient compute intermediate, fused or freed per step — not a
    cached score matrix).

    They are gather-free too: each sample's best token index is picked
    by a compare-and-select over the K shortlist slots (exactly one slot
    is the argmax), not by ``take_along_axis``, and the removed token's
    error is ``min(e)`` rather than ``e[argmin(e)]``.  Both give the
    same values bit for bit.  On a TPU v5e the element gather (one
    index per sample, per document, per step) ran at ~12 ns a scalar
    and held ~79% of the device time of a corpus build; the select
    costs about what the argmax over the same (N, K) slots costs.
    """
    n, m = samples.shape[0], d_emb.shape[0]
    K = min(shortlist, m)
    R = rescan_every
    n_steps = m - 1
    n_outer = -(-n_steps // R) if n_steps else 0
    kcol = jax.lax.broadcasted_iota(jnp.int32, (n, K), 1)
    tok = jnp.arange(m, dtype=jnp.int32)

    def outer(carry, _):
        alive, rank, err_at, next_pos = carry
        vals, idxs = maxsim_topk_op(        # per-sample top-K of alive
            samples, d_emb, alive, k=K, block_s=block_s, block_t=block_t)
        valid0 = jnp.ones((n, K), bool)

        def inner(icarry, _):
            alive, valid, rank, err_at, pos = icarry
            v = jnp.where(valid, vals, NEG_INF)
            b1 = jnp.max(v, axis=1)
            a1 = jnp.argmax(v, axis=1)
            top = kcol == a1[:, None]       # exactly one True per row
            bi = jnp.max(jnp.where(top, idxs, jnp.iinfo(idxs.dtype).min),
                         axis=1)
            v2 = jnp.where(top, NEG_INF, v)
            b2 = jnp.max(v2, axis=1)
            gap = b1 - b2
            onehot = (tok[None, :] == bi[:, None]).astype(jnp.float32)
            e = (gap @ onehot) / n
            e = jnp.where(alive, e, jnp.inf)
            n_alive = jnp.sum(alive)
            j = jnp.argmin(e)
            do = (n_alive > 1) & (pos < n_steps)
            kill = do & (tok == j)
            alive2 = alive & ~kill
            rank2 = jnp.where(kill, pos, rank)
            err2 = jnp.where(kill, jnp.min(e), err_at)      # == e[j]
            valid2 = valid & ~(do & (idxs == j))
            order_j = jnp.where(do, j, -1)
            return (alive2, valid2, rank2, err2, pos + 1), order_j

        (alive, _, rank, err_at, next_pos), orders = jax.lax.scan(
            inner, (alive, valid0, rank, err_at, next_pos), None, length=R)
        return (alive, rank, err_at, next_pos), orders

    rank0 = jnp.full((m,), m, jnp.int32)
    err0 = jnp.full((m,), jnp.inf, jnp.float32)
    (_, rank, err_at, _), orders = jax.lax.scan(
        outer, (d_mask, rank0, err0, jnp.int32(0)), None, length=n_outer)
    order = orders.reshape(-1)[:n_steps]
    return rank, err_at, order


def _resolve_shortlist_knobs(shortlist, rescan_every, block_s, block_t,
                             *, n, m, dim):
    """Fill ``None`` shortlist knobs from the autotuner (backend seam);
    validate the exactness bound on whatever the caller pinned."""
    if None in (shortlist, rescan_every, block_s, block_t):
        cfg = backend_lib.tuned("pruning", n_samples=n, m=m, dim=dim)
        if shortlist is None:
            # grow past the tuned K if the caller pinned a longer rescan
            # interval — the exactness bound is not the tuner's to break
            shortlist = (cfg.shortlist if rescan_every is None
                         else max(cfg.shortlist, rescan_every + 1))
        if rescan_every is None:
            rescan_every = min(cfg.rescan_every, max(shortlist - 1, 1))
        block_s = cfg.block_s if block_s is None else block_s
        block_t = cfg.block_t if block_t is None else block_t
    if rescan_every > shortlist - 1:
        raise ValueError("need shortlist >= rescan_every + 1 for exactness")
    return shortlist, rescan_every, block_s, block_t


def pruning_order_shortlist(d_emb: jax.Array, d_mask: jax.Array,
                            samples: jax.Array, *,
                            shortlist: int | None = None,
                            rescan_every: int | None = None,
                            block_s: int | None = None,
                            block_t: int | None = None
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """EXACT fast path for :func:`pruning_order` (§Perf iteration): the
    ``shortlist_topk`` backend.

    The reference recomputes a masked top-2 over all m tokens for every
    sample at every removal step — O(N*m) traffic per step.  Here each
    sample instead keeps its top-`shortlist` candidate tokens; the
    per-step reduction touches only (N, K).  A full rescan runs once per
    `rescan_every` steps as the *outer* level of a nested scan (no
    data-dependent control flow), through the fused ``maxsim_topk``
    Pallas kernel: partitionable, nothing (N, m)-shaped cached.

    Exactness: between rescans at most `rescan_every - 1` tokens die, so
    the true top-2 of the alive set is always contained in the last
    rescan's top-(2 + rescan_every - 1) <= K entries; the result is
    bit-identical to the reference (tested at the boundary).

    ``shortlist``/``rescan_every``/``block_s``/``block_t`` default to
    ``None`` — resolved by the shape-aware autotuner
    (``repro.core.tuning``) from (N, m, dim) and the platform; pass
    explicit values to pin them.  Un-jitted wrapper: knob resolution is
    a call-time decision, the impl underneath carries the jit cache.
    """
    n, m = samples.shape[0], d_emb.shape[0]
    shortlist, rescan_every, block_s, block_t = _resolve_shortlist_knobs(
        shortlist, rescan_every, block_s, block_t, n=n, m=m,
        dim=d_emb.shape[-1])
    return _pruning_order_shortlist_impl(
        d_emb, d_mask, samples, shortlist=shortlist,
        rescan_every=rescan_every, block_s=block_s, block_t=block_t)


def pruning_order_batch(d_embs: jax.Array, d_masks: jax.Array,
                        samples: jax.Array, *, step_size: int = 1,
                        backend: str | None = None):
    """vmap of :func:`pruning_order` over a document batch (global pruning
    precomputation; embarrassingly parallel across the `data` mesh axis).

    ``backend`` resolves as in :func:`pruning_order`.  Ragged corpora
    should go through the length-bucketed pipeline
    (``repro.core.pruning_pipeline.pruning_order_bucketed``), which
    calls this once per width bucket; results are bit-identical.

    Backend resolution and autotuning happen HERE, once, before the
    vmap — never inside a trace.
    """
    backend = resolve_pruning_backend(backend, step_size=step_size)
    n, m, dim = samples.shape[0], d_embs.shape[1], d_embs.shape[-1]
    if backend == backend_lib.SHORTLIST_TOPK:
        K, R, bs, bt = _resolve_shortlist_knobs(None, None, None, None,
                                                n=n, m=m, dim=dim)
        fn = lambda e, k: _pruning_order_shortlist_impl(
            e, k, samples, shortlist=K, rescan_every=R, block_s=bs,
            block_t=bt)
    else:
        fn = lambda e, k: _pruning_order_reference(
            e, k, samples, step_size=step_size)
    return jax.vmap(fn)(d_embs, d_masks)


def keep_mask_from_order(rank: jax.Array, d_mask: jax.Array,
                         n_keep: jax.Array | int) -> jax.Array:
    """Keep the `n_keep` *last-removed* real tokens of one document."""
    n_real = jnp.sum(d_mask)
    n_prune = jnp.maximum(n_real - n_keep, 0)
    # Tokens with rank >= n_prune survive.
    return d_mask & (rank >= n_prune)


def prune_to_size(d_emb: jax.Array, d_mask: jax.Array, samples: jax.Array,
                  target: int, *, step_size: int = 1,
                  backend: str | None = None) -> jax.Array:
    """Alg. 1 entry point: keep-mask with exactly min(target, n_real) tokens.

    Un-jitted like :func:`pruning_order` so backend resolution stays a
    call-time decision; the heavy lifting inside is jitted."""
    rank, _, _ = pruning_order(d_emb, d_mask, samples, step_size=step_size,
                               backend=backend)
    return keep_mask_from_order(rank, d_mask, target)


def _monotone_merge_errs(ranks: jax.Array, errs: jax.Array,
                         d_masks: jax.Array) -> jax.Array:
    """Per-document admissible merge keys for global pruning (§4.2).

    Each doc's err-at-removal sequence is monotonized with a running max
    along its own removal order (a later-removed token never merges
    before an earlier one); dead/survivor slots get +inf.  Pure per-doc
    math — embarrassingly parallel over the doc axis, which is what the
    sharded merge exploits."""
    n_docs, m = ranks.shape
    # err in doc-removal order, running-max, scattered back per token.
    step_err = jnp.full((n_docs, m + 1), jnp.inf, errs.dtype)
    doc_ix = jnp.arange(n_docs)[:, None]
    safe_rank = jnp.minimum(ranks, m)
    step_err = step_err.at[doc_ix, safe_rank].set(
        jnp.where(jnp.isfinite(errs), errs, jnp.inf))
    # monotone threshold along the removal order
    step_err = jax.lax.associative_scan(jnp.maximum, step_err, axis=1)
    mono_err = jnp.take_along_axis(step_err, safe_rank, axis=1)
    return jnp.where(d_masks & jnp.isfinite(errs), mono_err, jnp.inf)


_F32_INF_BITS = 0x7f800000  # +inf: the top of the nonneg-float bit order


def _global_keep_masks_sharded(ranks, errs, d_masks, keep_fraction, *,
                               mesh, axis):
    """Distributed §4.2 merge under ``shard_map`` over the doc axis.

    Replacing the reference path's corpus-wide ``argsort`` (which would
    all-gather every shard's errors), the global budget cut becomes a
    *selection* problem: the n_prune-th smallest merge key.  Errors are
    nonnegative f32 (gaps, running-maxed, +inf sentinels), whose IEEE
    bit patterns order identically as int32 — so a 31-step bitwise
    binary search, each step one scalar psum of a local count, finds the
    exact threshold with O(log) collective traffic.  Stable tie-breaking
    (the reference argsort prunes equal-valued keys in flat-index order)
    is reproduced by an exclusive scan of per-shard tie counts: shard i
    prunes its first ``clip(r - ties_before_i, 0, local_ties)`` ties in
    local flat order, which IS global flat order because shard_map
    slices the doc axis contiguously.  Bit-identical to the reference
    (asserted in tests/test_sharded_serving.py).
    """
    n_docs, m = ranks.shape
    n_shards = mesh.shape[axis]
    pad = (-n_docs) % n_shards
    if pad:
        # Padded docs are all-masked -> +inf keys appended AFTER every
        # real entry in flat order; since n_prune <= n_total <= the real
        # entry count, the stable tie cut can never reach them.
        ranks = jnp.pad(ranks, ((0, pad), (0, 0)), constant_values=m)
        errs = jnp.pad(errs, ((0, pad), (0, 0)),
                       constant_values=jnp.inf)
        d_masks = jnp.pad(d_masks, ((0, pad), (0, 0)))

    def body(rk, er, dm):
        mono = _monotone_merge_errs(rk, er, dm).astype(jnp.float32)
        mono = jnp.where(mono == 0, jnp.float32(0), mono)  # -0.0 -> +0.0
        bits = jax.lax.bitcast_convert_type(mono, jnp.int32).reshape(-1)
        n_total = jax.lax.psum(jnp.sum(dm), axis)
        n_keep = jnp.ceil(keep_fraction * n_total).astype(jnp.int32)
        n_prune = jnp.maximum(n_total - n_keep, 0)

        def step(_, lh):
            lo, hi = lh
            mid = lo + (hi - lo) // 2
            c = jax.lax.psum(jnp.sum((bits <= mid).astype(jnp.int32)),
                             axis)
            big = c >= n_prune
            return jnp.where(big, lo, mid + 1), jnp.where(big, mid, hi)

        t, _ = jax.lax.fori_loop(
            0, 31, step, (jnp.int32(0), jnp.int32(_F32_INF_BITS)))
        c_lt = jax.lax.psum(jnp.sum((bits < t).astype(jnp.int32)), axis)
        r = n_prune - c_lt                      # ties still to prune
        eq = bits == t
        local_eq = jnp.sum(eq.astype(jnp.int32))
        eq_counts = jax.lax.all_gather(local_eq, axis)   # (n_shards,)
        sidx = jax.lax.axis_index(axis)
        eq_before = jnp.sum(jnp.where(jnp.arange(n_shards) < sidx,
                                      eq_counts, 0))
        take = jnp.clip(r - eq_before, 0, local_eq)
        eq_rank = jnp.cumsum(eq.astype(jnp.int32)) - 1   # local flat order
        pruned = (bits < t) | (eq & (eq_rank < take))
        return dm & ~pruned.reshape(dm.shape)

    from jax.sharding import PartitionSpec as P
    keep = jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis, None),) * 3,
                         out_specs=P(axis, None),
                         check_vma=False)(ranks, errs, d_masks)
    return keep[:n_docs]


_merge_lock = threading.Lock()
_merge_traces = 0


def merge_traces() -> int:
    """How many times this process has traced the single-device §4.2
    merge program (:func:`_global_keep_masks_local`): once per
    (n_docs, m, dtypes, keep_fraction) it has met.  A build loop over
    fixed-shape slabs reads 1 after its first slab and stays there."""
    return _merge_traces


@functools.partial(jax.jit, static_argnames=("keep_fraction",))
def _global_keep_masks_local(ranks, errs, d_masks, keep_fraction):
    """The unsharded §4.2 merge as one compiled program: monotonized
    merge keys, the corpus budget, and a stable argsort cut.
    ``keep_fraction`` is static, a Python float, so
    ``ceil(keep_fraction * n_total)`` is a weak-typed f32 product."""
    global _merge_traces
    with _merge_lock:
        _merge_traces += 1          # the body runs only while tracing
    n_docs, m = ranks.shape
    mono_err = _monotone_merge_errs(ranks, errs, d_masks)
    n_total = jnp.sum(d_masks)
    n_keep = jnp.ceil(keep_fraction * n_total).astype(jnp.int32)
    n_prune = jnp.maximum(n_total - n_keep, 0)
    flat = mono_err.reshape(-1)
    # Prune the n_prune smallest keys; the stable sort breaks ties in flat
    # order, so the budget is met exactly.
    sort_ix = jnp.argsort(flat)
    cut = jnp.arange(flat.shape[0]) < n_prune
    pruned_flat = jnp.zeros_like(flat, bool).at[sort_ix].set(cut)
    return d_masks & ~pruned_flat.reshape(n_docs, m)


def global_keep_masks(ranks: jax.Array, errs: jax.Array, d_masks: jax.Array,
                      keep_fraction: float, *,
                      sharded: bool | None = None) -> jax.Array:
    """Corpus-level pruning (§4.2 "Global Pruning").

    Per-document orders are merged by the error each removal introduces;
    the cheapest removals corpus-wide are applied until the global token
    budget is met.  To keep every document's own order admissible we
    monotonize each doc's error sequence with a running max before the
    merge (a later-removed token never merges before an earlier one).
    Every document always retains >= 1 token (err inf on the survivor).

    Unsharded, the merge is one jitted program
    (:func:`_global_keep_masks_local`), compiled once per shape and
    ``keep_fraction`` (:func:`merge_traces` counts its traces).
    ``sharded`` selects the distributed merge
    (:func:`_global_keep_masks_sharded`): the per-doc monotonization
    shards over the ``data`` mesh axis and the global cut runs as a
    bitwise selection with O(log) scalar collectives — no corpus-wide
    sort, no gathered error array.  ``None`` (default) auto-enables it
    when the active sharding rules carry a mesh (``"__mesh__"``) whose
    ``data`` axis is wider than 1; ``True`` requires one; results are
    bit-identical either way.

    ranks/errs/d_masks: (n_docs, m).  Returns keep masks (n_docs, m).
    """
    from repro.sharding.specs import data_mesh_for
    mesh = data_mesh_for(sharded, who="global_keep_masks")
    if mesh is not None:
        return _global_keep_masks_sharded(ranks, errs, d_masks,
                                          keep_fraction, mesh=mesh,
                                          axis="data")
    return _global_keep_masks_local(ranks, errs, d_masks,
                                    keep_fraction=float(keep_fraction))


def mean_error(d_emb: jax.Array, d_mask: jax.Array, keep_mask: jax.Array,
               samples: jax.Array, *, ball_normalized: bool = False) -> jax.Array:
    """ME of a pruned document: E_q[max_D q.d - max_keep q.d] over the
    sphere sample set (Eq. 8 aggregated over the pruned set).  With
    ``ball_normalized`` the Eq. 7 factor 1/2 converts to the ball measure.
    """
    s = samples @ d_emb.T
    s_all = jnp.where(d_mask[None, :], s, NEG_INF)
    s_keep = jnp.where((d_mask & keep_mask)[None, :], s, NEG_INF)
    me = jnp.mean(s_all.max(-1) - s_keep.max(-1))
    return 0.5 * me if ball_normalized else me


def mean_error_batch(d_embs, d_masks, keep_masks, samples, **kw):
    fn = lambda e, m, k: mean_error(e, m, k, samples, **kw)
    return jax.vmap(fn)(d_embs, d_masks, keep_masks)


# ----------------------------------------------------------------------
# Beam-search variant (§6.2 "Effect of Beam Size") — ablation only.
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("beam", "target"))
def beam_pruning_order(d_emb: jax.Array, d_mask: jax.Array,
                       samples: jax.Array, *, beam: int = 3,
                       target: int = 1) -> tuple[jax.Array, jax.Array]:
    """Beam search over removal sequences; returns (keep_mask, total_err)
    of the best beam at |D'| = target.  Exponential state is avoided by
    keeping only `beam` alive-masks + cumulative errors; candidate
    expansion scores each beam's per-token Eq. 8 error.
    """
    n, m = samples.shape[0], d_emb.shape[0]
    scores = jnp.where(d_mask[None, :], samples @ d_emb.T, NEG_INF)

    def beam_errors(alive):
        st = _top2_from_scores(scores, alive)
        return token_errors(st, alive, n)

    alive0 = jnp.tile(d_mask[None, :], (beam, 1))
    cum0 = jnp.full((beam,), jnp.inf).at[0].set(0.0)  # only beam 0 live at t=0
    n_real = jnp.sum(d_mask)
    n_steps = int(m - max(target, 1))

    def body(carry, _):
        alive, cum = carry
        errs = jax.vmap(beam_errors)(alive)               # (beam, m)
        n_alive = jnp.sum(alive, axis=1)
        cand = jnp.where((n_alive[:, None] > target) & alive, errs, jnp.inf)
        total = cum[:, None] + cand                       # (beam, m)
        flat = total.reshape(-1)
        vals, flat_ix = jax.lax.top_k(-flat, beam)
        b_ix, t_ix = flat_ix // m, flat_ix % m
        new_alive = alive[b_ix].at[jnp.arange(beam), t_ix].set(False)
        new_cum = -vals
        # If no candidate was finite (already at target), keep old beams.
        any_live = jnp.isfinite(new_cum)
        new_alive = jnp.where(any_live[:, None], new_alive, alive)
        new_cum = jnp.where(any_live, new_cum, cum)
        return (new_alive, new_cum), None

    (alive, cum), _ = jax.lax.scan(body, (alive0, cum0), None, length=n_steps)
    best = jnp.argmin(cum)
    del n_real
    return alive[best], cum[best]
