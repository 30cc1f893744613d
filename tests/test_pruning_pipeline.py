"""Length-bucketed corpus pruning pipeline: plan properties and the
bit-identical-parity contract against the flat `pruning_order_batch`.

The pipeline's whole value is that bucketing is a pure execution-shape
change: (ranks, errs, orders) must match the unbucketed batch path BIT
for BIT on ragged corpora, on both pruning paths, including degenerate
documents (one-token, fully masked) and step_size > 1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import sweep
from repro.core import pruning_pipeline as pp
from repro.core import sampling, voronoi

TOPK = "shortlist_topk"     # the pruning scan the chip runs


def _ragged_corpus(seed, n_docs, m, dim):
    k = jax.random.PRNGKey(seed)
    d = jax.random.normal(k, (n_docs, m, dim)) * 0.5
    n_real = jax.random.randint(jax.random.fold_in(k, 1), (n_docs,),
                                1, m + 1)
    masks = jnp.arange(m)[None, :] < n_real[:, None]
    return d, masks, n_real


class TestBucketPlan:
    @sweep(n_cases=8, seed=0, n_docs=[1, 7, 40], m=[8, 24, 100],
           granularity=["pow2", 8])
    def test_partition_and_bounds(self, n_docs, m, granularity):
        rng = np.random.default_rng(n_docs * m)
        n_real = rng.integers(1, m + 1, n_docs)
        plan = pp.bucket_plan(n_real, m, granularity=granularity)
        seen = np.concatenate([b.indices for b in plan])
        # exact partition of the doc axis
        assert sorted(seen.tolist()) == list(range(n_docs))
        widths = [b.width for b in plan]
        assert widths == sorted(widths)
        for b in plan:
            assert b.width <= m
            assert (n_real[b.indices] <= b.width).all()

    def test_pow2_bounds_bucket_count(self):
        n_real = np.arange(1, 513)
        plan = pp.bucket_plan(n_real, 512)
        assert len(plan) <= 8  # O(log m) shapes: 8,16,...,512

    def test_bad_inputs_rejected(self):
        with pytest.raises(ValueError, match="granularity"):
            pp.bucket_plan([3, 4], 8, granularity=0)
        with pytest.raises(ValueError, match="1-D"):
            pp.bucket_plan(np.ones((2, 2)), 8)


class TestBucketedParity:
    @sweep(n_cases=6, seed=1, n_docs=[5, 12], m=[10, 24, 33], dim=[4, 8],
           backend_kw=[{}, {"backend": "shortlist_topk"},
                       {"step_size": 3}])
    def test_bit_identical_to_flat_batch(self, n_docs, m, dim, backend_kw):
        d, masks, _ = _ragged_corpus(n_docs * m + dim, n_docs, m, dim)
        S = sampling.sample_sphere(jax.random.PRNGKey(2), 400, dim)
        flat = voronoi.pruning_order_batch(d, masks, S, **backend_kw)
        buck = pp.pruning_order_bucketed(d, masks, S, **backend_kw)
        for name, a, b in zip(("ranks", "errs", "orders"), flat, buck):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name} {backend_kw}")

    def test_scattered_non_prefix_masks(self):
        """Masks need not be prefix-padded (e.g. stopword filtering
        kills interior positions): bucket widths follow the EFFECTIVE
        length (last alive position + 1), so a doc alive at {0, 15}
        must not be truncated into a narrow bucket."""
        k = jax.random.PRNGKey(17)
        n_docs, m = 6, 16
        d = jax.random.normal(k, (n_docs, m, 8)) * 0.5
        masks = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.4,
                                     (n_docs, m))
        masks = masks.at[0].set(False).at[0, 0].set(True) \
                     .at[0, m - 1].set(True)     # alive only at {0, 15}
        S = sampling.sample_sphere(jax.random.PRNGKey(18), 400, 8)
        eff = pp.effective_lengths(masks)
        assert int(eff[0]) == m
        flat = voronoi.pruning_order_batch(d, masks, S, backend=TOPK)
        buck = pp.pruning_order_bucketed(d, masks, S, backend=TOPK)
        for name, a, b in zip(("ranks", "errs", "orders"), flat, buck):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)

    def test_degenerate_docs(self):
        """One-token and fully-masked documents survive bucketing."""
        d, masks, _ = _ragged_corpus(5, 6, 20, 8)
        masks = masks.at[0].set(False)                    # 0 real tokens
        masks = masks.at[1].set(jnp.arange(20) < 1)       # 1 real token
        S = sampling.sample_sphere(jax.random.PRNGKey(4), 300, 8)
        flat = voronoi.pruning_order_batch(d, masks, S, backend=TOPK)
        buck = pp.pruning_order_bucketed(d, masks, S, backend=TOPK)
        for a, b in zip(flat, buck):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # conventions: nothing removed, rank sentinel m, err inf
        assert bool((buck[0][0] == 20).all())
        assert bool(jnp.isinf(buck[1][1][0]))

    def test_uniform_lengths_single_bucket(self):
        d, masks, _ = _ragged_corpus(7, 4, 16, 8)
        masks = jnp.ones_like(masks)
        plan = pp.bucket_plan(np.asarray(masks.sum(1)), 16)
        assert len(plan) == 1 and plan[0].width == 16
        flat = voronoi.pruning_order_batch(d, masks, S := sampling.
                                           sample_sphere(
                                               jax.random.PRNGKey(5),
                                               200, 8))
        buck = pp.pruning_order_bucketed(d, masks, S)
        for a, b in zip(flat, buck):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_empty_corpus(self):
        d = jnp.zeros((0, 8, 4))
        masks = jnp.zeros((0, 8), bool)
        S = sampling.sample_sphere(jax.random.PRNGKey(6), 100, 4)
        r, e, o = pp.pruning_order_bucketed(d, masks, S)
        assert r.shape == (0, 8) and e.shape == (0, 8) and o.shape == (0, 7)

    def test_plan_reuse(self):
        d, masks, n_real = _ragged_corpus(9, 8, 24, 8)
        plan = pp.bucket_plan(np.asarray(n_real), 24)
        S = sampling.sample_sphere(jax.random.PRNGKey(7), 300, 8)
        a = pp.pruning_order_bucketed(d, masks, S, backend=TOPK)
        b = pp.pruning_order_bucketed(d, masks, S, backend=TOPK,
                                      plan=plan)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestDocBlocks:
    """Blocked dispatch (on TPU, ``tuning.pruning_docs_per_block``):
    buckets split into fixed-size blocks, the last padded with
    all-masked documents — a pure execution-shape change per width."""

    def test_blocks_partition_each_bucket(self, monkeypatch):
        plan = [pp.Bucket(8, np.arange(5)), pp.Bucket(16, np.arange(5, 7))]
        # off-TPU: one dispatch per bucket, unpadded
        assert [rows for _, rows in pp._doc_blocks(plan, 100)] == [5, 2]
        monkeypatch.setattr(pp, "pruning_docs_per_block", lambda n, w: 2)
        blocks = list(pp._doc_blocks(plan, 100))
        assert [(b.width, b.indices.tolist(), rows)
                for b, rows in blocks] == [
            (8, [0, 1], 2), (8, [2, 3], 2), (8, [4], 2), (16, [5, 6], 2)]

    @pytest.mark.parametrize("docs_per_block", [1, 3])
    def test_blocked_matches_whole_bucket(self, monkeypatch, docs_per_block):
        d, masks, _ = _ragged_corpus(13, 11, 24, 8)
        masks = masks.at[2].set(False)                    # 0 real tokens
        S = sampling.sample_sphere(jax.random.PRNGKey(9), 300, 8)
        whole = pp.pruning_order_bucketed(d, masks, S, backend=TOPK)
        monkeypatch.setattr(pp, "pruning_docs_per_block",
                            lambda n, w: docs_per_block)
        blocked = pp.pruning_order_bucketed(d, masks, S, backend=TOPK)
        # Orders are exact; errors only to f32 rounding: XLA vectorizes
        # a different batch size differently.
        np.testing.assert_array_equal(np.asarray(whole[0]),
                                      np.asarray(blocked[0]))
        np.testing.assert_array_equal(np.asarray(whole[2]),
                                      np.asarray(blocked[2]))
        np.testing.assert_allclose(np.asarray(whole[1]),
                                   np.asarray(blocked[1]), rtol=1e-6)


class TestPruneCorpus:
    def test_keep_masks_match_flat_global_pruning(self):
        d, masks, _ = _ragged_corpus(11, 10, 20, 8)
        S = sampling.sample_sphere(jax.random.PRNGKey(8), 500, 8)
        for frac in (0.3, 0.7):
            keep, ranks, errs = pp.prune_corpus(d, masks, S, frac,
                                                backend=TOPK)
            flat = voronoi.pruning_order_batch(d, masks, S, backend=TOPK)
            ref = voronoi.global_keep_masks(flat[0], flat[1], masks, frac)
            np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref))
            # budget + per-doc floor invariants survive the bucketing
            assert bool((keep & ~masks).sum() == 0)
            per_doc = np.asarray((keep & masks).sum(1))
            assert (per_doc[np.asarray(masks.sum(1)) > 0] >= 1).all()


def _merge_inputs(seed, n_docs, m, *, tie_levels=0, n_masked=0):
    """Per-document removal orders as the pruning scan emits them: the
    first ``n_real - 1`` real tokens of a random permutation are removed
    at ranks 0.. with nonnegative errors, the last survives (rank m, err
    inf), dead slots carry rank m and err inf.  ``tie_levels`` draws
    errors from that many values, so keys tie within and across
    documents; the first ``n_masked`` documents are fully masked."""
    rng = np.random.default_rng(seed)
    n_real = rng.integers(1, m + 1, n_docs)
    n_real[:n_masked] = 0
    masks = np.arange(m)[None, :] < n_real[:, None]
    ranks = np.full((n_docs, m), m, np.int32)
    errs = np.full((n_docs, m), np.inf, np.float32)
    for i, k in enumerate(n_real):
        gone = rng.permutation(k)[:max(k - 1, 0)]
        ranks[i, gone] = np.arange(gone.size)
        errs[i, gone] = (rng.integers(0, tie_levels, gone.size) / 4
                         if tie_levels else rng.random(gone.size))
    return ranks, errs, masks


def _merge_reference(ranks, errs, masks, keep_fraction):
    """§4.2 in plain numpy: each document's errors made monotone along
    its own removal order (running max), then the smallest
    ``n_total - ceil(keep_fraction * n_total)`` keys of the whole corpus
    pruned, ties in flat order (a stable sort)."""
    keys = np.full(masks.shape, np.inf, np.float32)
    for d in range(masks.shape[0]):
        gone = np.flatnonzero(masks[d] & np.isfinite(errs[d]))
        order = gone[np.argsort(ranks[d, gone], kind="stable")]
        keys[d, order] = np.maximum.accumulate(errs[d, order])
    n_total = int(masks.sum())
    n_prune = max(n_total - int(np.ceil(keep_fraction * n_total)), 0)
    pruned = np.zeros(keys.size, bool)
    pruned[np.argsort(keys.reshape(-1), kind="stable")[:n_prune]] = True
    return masks & ~pruned.reshape(masks.shape)


MERGE_CASES = {
    "ragged-180": dict(n_docs=64, m=180),
    "ragged-300": dict(n_docs=64, m=300),
    "tied-errors": dict(n_docs=64, m=180, tie_levels=3),
    "masked-docs": dict(n_docs=64, m=300, n_masked=5, tie_levels=2),
}


class TestGlobalMerge:
    """The compiled §4.2 merge (``voronoi.global_keep_masks``) against a
    plain numpy merge, bit for bit, at the build cells' slab shapes."""

    @pytest.mark.parametrize("keep_fraction", [0.0, 0.5, 1.0])
    @pytest.mark.parametrize("case", sorted(MERGE_CASES))
    def test_matches_numpy_reference(self, case, keep_fraction):
        ranks, errs, masks = _merge_inputs(sorted(MERGE_CASES).index(case),
                                           **MERGE_CASES[case])
        keep = voronoi.global_keep_masks(jnp.asarray(ranks),
                                         jnp.asarray(errs),
                                         jnp.asarray(masks), keep_fraction)
        np.testing.assert_array_equal(
            np.asarray(keep), _merge_reference(ranks, errs, masks,
                                               keep_fraction))


class TestPoolTokens:
    """Within-document token pooling (the pre-pack merge pass the
    residual codec composes with)."""

    def test_exact_duplicates_merge(self):
        d = np.zeros((1, 4, 8), np.float32)
        v = np.arange(8, dtype=np.float32)
        d[0, 0] = v
        d[0, 1] = 2 * v          # same direction, different norm
        d[0, 2] = -v             # opposite: must NOT merge
        d[0, 3] = np.roll(v, 1)  # different direction
        keep = np.ones((1, 4), bool)
        pooled, new_keep = pp.pool_tokens(d, keep, 0.999)
        np.testing.assert_array_equal(new_keep, [[True, False, True, True]])
        # slot 0 takes the pool mean; absorbed slot zeroed
        np.testing.assert_allclose(pooled[0, 0], 1.5 * v)
        np.testing.assert_array_equal(pooled[0, 1], np.zeros(8))
        np.testing.assert_allclose(pooled[0, 2], -v)

    def test_threshold_one_keeps_distinct_tokens(self):
        rng = np.random.default_rng(3)
        d = rng.standard_normal((4, 10, 8)).astype(np.float32)
        keep = rng.random((4, 10)) < 0.7
        pooled, new_keep = pp.pool_tokens(d, keep, 1.0)
        np.testing.assert_array_equal(new_keep, keep)
        np.testing.assert_allclose(pooled[keep], d[keep], rtol=1e-6)

    def test_never_grows_keep(self):
        rng = np.random.default_rng(4)
        d = rng.standard_normal((6, 12, 8)).astype(np.float32)
        keep = rng.random((6, 12)) < 0.8
        _, new_keep = pp.pool_tokens(d, keep, 0.8)
        assert (new_keep <= keep).all()

    def test_pooling_is_per_document(self):
        """Identical tokens in DIFFERENT docs never merge."""
        d = np.ones((2, 2, 8), np.float32)
        d[:, 1] += np.arange(8) * 0.5  # distinct second token per doc
        keep = np.ones((2, 2), bool)
        _, new_keep = pp.pool_tokens(d, keep, 0.999)
        assert new_keep.sum(axis=1).tolist() == [2, 2]

    def test_scores_survive_near_duplicate_pooling(self):
        """Pooling near-duplicates barely moves MaxSim: each pooled
        representative stands in for members that scored almost the
        same max."""
        from repro.serve.retrieval import TokenIndex, maxsim_scores
        rng = np.random.default_rng(5)
        base = rng.standard_normal((8, 4, 8)).astype(np.float32)
        # duplicate each of the 4 base tokens 3x with tiny jitter
        d = np.repeat(base, 3, axis=1)
        d += 1e-4 * rng.standard_normal(d.shape).astype(np.float32)
        masks = np.ones((8, 12), bool)
        keep = np.ones((8, 12), bool)
        pooled, new_keep = pp.pool_tokens(d, keep, 0.999)
        assert new_keep.sum() == 8 * 4   # 3x compression
        q = rng.standard_normal((3, 5, 8)).astype(np.float32)
        s0 = maxsim_scores(TokenIndex.build(jnp.asarray(d),
                                            jnp.asarray(masks)), q)
        s1 = maxsim_scores(
            TokenIndex.build(jnp.asarray(pooled), jnp.asarray(masks))
            .with_keep(jnp.asarray(new_keep)), q)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                                   atol=1e-2, rtol=1e-2)

    def test_bad_threshold_rejected(self):
        d = np.zeros((1, 2, 4), np.float32)
        keep = np.ones((1, 2), bool)
        for t in (0.0, -0.5, 1.5):
            with pytest.raises(ValueError):
                pp.pool_tokens(d, keep, t)
