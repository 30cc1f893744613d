"""Backend-dispatch seam: fused/chunked paths vs reference oracles.

Covers the contract the dispatch layer (repro.core.backend) promises:
  * pruning_order(backend="shortlist_topk") orders are IDENTICAL to the
    reference path (the shortlist scan is exact; lowest-index
    tie-breaking on both);
  * the removed pruning paths are refused by name, and step_size > 1
    resolves to the reference on every platform;
  * chunked search()/maxsim_scores(backend="fused") match the reference
    einsum path, including padded/ragged masks and query masks;
  * the compiled fused serving HLO contains NO 4-D (n_q, n_docs, l, m)
    score tensor while the reference provably does;
  * pruning_order_shortlist is exact right at the
    shortlist == rescan_every + 1 boundary (the proof's edge);
  * the env-var/argument resolution rules of repro.core.backend.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import sweep
from repro.core import backend as backend_lib
from repro.core import sampling, voronoi
from repro.serve.retrieval import TokenIndex, maxsim_scores, search


def _doc(seed, m, dim, n_real=None, radius=0.9):
    k = jax.random.PRNGKey(seed)
    d = jax.random.normal(k, (m, dim))
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True) * radius
    n_real = n_real or m
    return d, jnp.arange(m) < n_real


def _corpus(seed, n_docs, m, dim, ragged=True):
    k = jax.random.PRNGKey(seed)
    d = jax.random.normal(k, (n_docs, m, dim)) * 0.5
    if ragged:
        n_real = jax.random.randint(jax.random.fold_in(k, 1), (n_docs,),
                                    1, m + 1)
        masks = jnp.arange(m)[None, :] < n_real[:, None]
    else:
        masks = jnp.ones((n_docs, m), bool)
    return d, masks


class TestPruningBackendParity:
    def test_fused_batch_ragged_masks(self):
        """vmapped shortlist_topk path over docs of very different real
        lengths, including a one-token document (nothing to remove)."""
        d, masks = _corpus(3, 6, 12, 8)
        masks = masks.at[0].set(jnp.arange(12) < 1)   # degenerate doc
        S = sampling.sample_sphere(jax.random.PRNGKey(2), 600, 8)
        r_ref, e_ref, _ = voronoi.pruning_order_batch(
            d, masks, S, backend="reference")
        r_t, e_t, _ = voronoi.pruning_order_batch(d, masks, S,
                                                  backend="shortlist_topk")
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_t))
        # degenerate doc: sole real token survives with rank m, err inf
        assert int(r_t[0, 0]) == 12
        assert bool(jnp.isinf(e_t[0, 0]))

    @sweep(n_cases=6, seed=5, m=[6, 16, 23], dim=[4, 8], n_real=[None, 5])
    def test_shortlist_topk_identical_to_reference(self, m, dim, n_real):
        """The kernel-rescan shortlist path (shortlist_topk backend) is
        the same exact algorithm: orders, and the ranks of removed
        tokens, identical to the reference; errors equal to f32
        rounding (the two sum a token's gaps in different orders)."""
        if n_real is not None and n_real > m:
            n_real = m
        d, mask = _doc(m * dim + 1, m, dim, n_real=n_real)
        S = sampling.sample_sphere(jax.random.PRNGKey(9), 700, dim)
        r_ref, e_ref, o_ref = voronoi.pruning_order(d, mask, S,
                                                    backend="reference")
        r_t, e_t, o_t = voronoi.pruning_order(d, mask, S,
                                              backend="shortlist_topk")
        n_rm = int(jnp.sum(mask)) - 1
        removed = np.asarray(o_ref)[:n_rm]
        np.testing.assert_array_equal(removed, np.asarray(o_t)[:n_rm])
        np.testing.assert_array_equal(np.asarray(r_ref)[removed],
                                      np.asarray(r_t)[removed])
        np.testing.assert_allclose(np.asarray(e_ref)[removed],
                                   np.asarray(e_t)[removed], atol=1e-6)

    def test_shortlist_topk_batch_ragged(self):
        """Batch path, ragged masks: ranks and orders equal the
        reference's."""
        d, masks = _corpus(13, 5, 12, 8)
        S = sampling.sample_sphere(jax.random.PRNGKey(10), 500, 8)
        r_ref, _, o_ref = voronoi.pruning_order_batch(d, masks, S,
                                                      backend="reference")
        r_t, _, o_t = voronoi.pruning_order_batch(d, masks, S,
                                                  backend="shortlist_topk")
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_t))
        n_rm = np.asarray(masks.sum(1)) - 1
        for i, n in enumerate(n_rm):
            np.testing.assert_array_equal(np.asarray(o_ref)[i, :n],
                                          np.asarray(o_t)[i, :n])

    @pytest.mark.parametrize("backend", ["shortlist_topk"])
    def test_batch_identical_to_reference_at_exact_ties(self, backend):
        """Every token is duplicated and every score is a small dyadic
        number, exact whatever the summation order, so shortlist slots
        tie exactly in value and the kernel's scores equal the dense
        ones.  Ranks, errors and orders then equal the reference bit for
        bit.  Where the best slot ties, its gap is zero, so which tied
        slot the select lands on cannot change a result; a select off
        the argmax's slot does."""
        rng = np.random.default_rng(3)
        m, dim = 16, 8
        half = rng.integers(-3, 4, (4, m // 2, dim)) / 4
        d = np.concatenate([half, half], axis=1)[:, rng.permutation(m)]
        masks = np.arange(m)[None, :] < np.array([m, m - 3, 7, 2])[:, None]
        S = rng.integers(-2, 3, (512, dim)) / 2
        d, S = jnp.asarray(d, jnp.float32), jnp.asarray(S, jnp.float32)
        masks = jnp.asarray(masks)
        ref = voronoi.pruning_order_batch(d, masks, S, backend="reference")
        out = voronoi.pruning_order_batch(d, masks, S, backend=backend)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        errs = np.asarray(ref[1])
        assert (errs == 0).sum() >= 8, "oracle changed: too few ties"

    def test_no_full_m_topk_in_shortlist_topk_hlo(self):
        """Acceptance criterion: the compiled shortlist-on-maxsim_topk
        path contains no full-m lax.top_k — neither the (N, m) top_k op
        in the lowering nor a TopK custom-call on an f32[N, m] operand in
        the compiled module — while a bare lax.top_k over an (N, m)
        array provably matches both patterns (the GSPMD de-partitioning
        culprit)."""
        n, m, dim = 300, 23, 8
        d, mask = _doc(21, m, dim)
        S = sampling.sample_sphere(jax.random.PRNGKey(11), n, dim)

        def texts(fn, *args):
            lowered = jax.jit(fn).lower(*args)
            return lowered.as_text(), lowered.compile().as_text()

        def full_m_topk_call(compiled):
            # the printer may leave operand shapes off the call line:
            # look each operand's shape up at its definition
            shape_of = dict(re.findall(
                r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+\[[\d,]*\])", compiled,
                re.M))
            for ln in compiled.splitlines():
                call = re.search(r"custom-call\(([^)]*)\)", ln)
                if call is None or 'custom_call_target="TopK"' not in ln:
                    continue
                for op in call.group(1).split(","):
                    name = op.split()[-1] if op.split() else ""
                    if (f"[{n},{m}]" in op
                            or shape_of.get(name) == f"f32[{n},{m}]"):
                        return True
            return False

        low_pat = re.compile(rf"top_k[^\n]*{n}x{m}x")
        dense_low, dense_comp = texts(
            lambda ss, dd: jax.lax.top_k(ss @ dd.T, 8), S, d)
        assert low_pat.search(dense_low), \
            "oracle changed: a bare (N, m) top_k lowers without top_k"
        assert full_m_topk_call(dense_comp), \
            "oracle changed: a bare (N, m) top_k compiles without TopK"
        topk_low, topk_comp = texts(
            lambda dd, kk, ss: voronoi._pruning_order_shortlist_impl(
                dd, kk, ss, shortlist=8, rescan_every=7, block_s=64,
                block_t=16), d, mask, S)
        assert not low_pat.search(topk_low), \
            "shortlist_topk lowering still carries a full-m top_k"
        assert not full_m_topk_call(topk_comp), \
            "shortlist_topk compiled module still calls full-m TopK"

    def test_no_gather_in_shortlist_scan(self):
        """The vmapped shortlist scan's inner step is gather-free: the
        best index of each sample is a select over the K slots and the
        removed token's error a min, neither a take_along_axis nor an
        e[j] (on TPU each lowered to an element gather per step)."""
        d, masks = _corpus(17, 3, 32, 8)
        S = sampling.sample_sphere(jax.random.PRNGKey(12), 64, 8)
        fn = jax.jit(jax.vmap(
            lambda dd, kk: voronoi._pruning_order_shortlist_impl(
                dd, kk, S, shortlist=8, rescan_every=7, block_s=64,
                block_t=16)))
        text = fn.lower(d, masks).as_text()
        assert "while" in text, "oracle changed: the scan is not lowered"
        assert '"stablehlo.gather"' not in text
        assert "stablehlo.gather(" not in text

    def test_conflicting_knobs_rejected(self):
        d, mask = _doc(9, 10, 8)
        S = sampling.sample_sphere(jax.random.PRNGKey(8), 200, 8)
        # the kernel scan removes one token a step
        with pytest.raises(ValueError, match="backend"):
            voronoi.pruning_order(d, mask, S, backend="shortlist_topk",
                                  step_size=2)
        # step_size > 1 with an unresolved backend takes the reference
        r_k, _, o_k = voronoi.pruning_order(d, mask, S, step_size=2)
        r_r, _, o_r = voronoi.pruning_order(d, mask, S, step_size=2,
                                            backend="reference")
        np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))
        np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_r))

    @pytest.mark.parametrize("entry", ["pruning_order", "prune_corpus"])
    @pytest.mark.parametrize("removed", ["fused", "shortlist"])
    def test_removed_backend_names_refused(self, entry, removed):
        """The deleted pruning paths are refused by name, and the error
        names the two that remain."""
        from repro.core import pruning_pipeline
        d, masks = _corpus(19, 2, 8, 4)
        S = sampling.sample_sphere(jax.random.PRNGKey(13), 64, 4)
        with pytest.raises(ValueError,
                           match=r"\['reference', 'shortlist_topk'\]"):
            if entry == "pruning_order":
                voronoi.pruning_order(d[0], masks[0], S, backend=removed)
            else:
                pruning_pipeline.prune_corpus(d, masks, S, 0.5,
                                              backend=removed)

    def test_step_size_above_one_resolves_to_reference_on_tpu(
            self, monkeypatch):
        """On TPU the platform default is the kernel scan, which removes
        one token a step; ``step_size > 1`` still resolves to the
        reference there and returns its answer."""
        monkeypatch.delenv("REPRO_BACKEND", raising=False)
        d, mask = _doc(23, 12, 8)
        S = sampling.sample_sphere(jax.random.PRNGKey(14), 300, 8)
        want = voronoi.pruning_order(d, mask, S, step_size=2,
                                     backend="reference")
        monkeypatch.setattr(backend_lib, "on_tpu", lambda: True)
        assert voronoi.resolve_pruning_backend() == "shortlist_topk"
        assert voronoi.resolve_pruning_backend(step_size=2) == "reference"
        got = voronoi.pruning_order(d, mask, S, step_size=2)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_keep_masks_and_global_pruning_agree(self):
        """End of the pruning pipeline: global keep masks built from
        shortlist_topk orders == built from reference orders."""
        d, masks = _corpus(7, 5, 10, 8)
        S = sampling.sample_sphere(jax.random.PRNGKey(4), 700, 8)
        out_ref = voronoi.pruning_order_batch(d, masks, S,
                                              backend="reference")
        out_t = voronoi.pruning_order_batch(d, masks, S,
                                            backend="shortlist_topk")
        for frac in (0.3, 0.7):
            k_ref = voronoi.global_keep_masks(out_ref[0], out_ref[1],
                                              masks, frac)
            k_t = voronoi.global_keep_masks(out_t[0], out_t[1], masks, frac)
            np.testing.assert_array_equal(np.asarray(k_ref),
                                          np.asarray(k_t))


class TestShortlistBoundary:
    @sweep(n_cases=6, seed=2, m=[9, 16, 24], dim=[4, 8],
           every=[2, 4, 7])
    def test_exact_at_minimal_shortlist(self, m, dim, every):
        """Exactness proof edge: shortlist == rescan_every + 1 keeps the
        true top-2 inside the shortlist between rescans — the order must
        equal the reference for the MINIMAL legal K, not just K=16."""
        K = every + 1
        if K > m:
            return
        d, mask = _doc(m + dim + every, m, dim)
        S = sampling.sample_sphere(jax.random.PRNGKey(5), 900, dim)
        r_ref, _, o_ref = voronoi.pruning_order(d, mask, S,
                                                backend="reference")
        r_sl, _, o_sl = voronoi.pruning_order_shortlist(
            d, mask, S, shortlist=K, rescan_every=every)
        np.testing.assert_array_equal(np.asarray(o_ref[:m - 1]),
                                      np.asarray(o_sl[:m - 1]))
        # ranks agree on removed tokens (survivor conventions differ:
        # reference assigns the survivor rank m via the scatter default)
        removed = np.asarray(o_ref[:m - 1])
        np.testing.assert_array_equal(np.asarray(r_ref)[removed],
                                      np.asarray(r_sl)[removed])

    def test_below_boundary_rejected(self):
        d, mask = _doc(0, 12, 4)
        S = sampling.sample_sphere(jax.random.PRNGKey(6), 100, 4)
        with pytest.raises(ValueError, match="shortlist"):
            voronoi.pruning_order_shortlist(d, mask, S, shortlist=4,
                                            rescan_every=4)


class TestServingBackendParity:
    @pytest.fixture(scope="class")
    def setup(self):
        k = jax.random.PRNGKey(0)
        n_docs, m, dim, n_q, l = 33, 12, 16, 7, 6
        d, masks = _corpus(11, n_docs, m, dim)
        q = jax.random.normal(jax.random.fold_in(k, 1), (n_q, l, dim))
        qm = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.8,
                                  (n_q, l)).at[:, 0].set(True)
        return TokenIndex.build(d, masks), q, qm

    @sweep(n_cases=6, seed=4, block_docs=[4, 8, 16], block_q=[3, 16])
    def test_maxsim_scores_parity(self, block_docs, block_q):
        # sweep() calls with kwargs only; build the corpus inline
        k = jax.random.PRNGKey(0)
        d, masks = _corpus(11, 33, 12, 16)
        q = jax.random.normal(jax.random.fold_in(k, 1), (7, 6, 16))
        qm = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.8,
                                  (7, 6)).at[:, 0].set(True)
        index = TokenIndex.build(d, masks)
        ref = maxsim_scores(index, q, qm, backend="reference")
        fus = maxsim_scores(index, q, qm, backend="fused",
                            block_docs=block_docs, block_q=block_q)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(fus),
                                   rtol=1e-5, atol=1e-5)

    def test_search_parity_both_stages(self, setup):
        index, q, qm = setup
        for e2e in (True, False):
            i_r, s_r, f_r = search(index, q, k=5, n_first=16,
                                   end_to_end=e2e, q_masks=qm,
                                   backend="reference")
            i_f, s_f, f_f = search(index, q, k=5, n_first=16,
                                   end_to_end=e2e, q_masks=qm,
                                   backend="fused")
            np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_f))
            np.testing.assert_allclose(np.asarray(s_r), np.asarray(s_f),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(f_r), np.asarray(f_f),
                                       rtol=1e-5, atol=1e-4)

    def test_search_parity_on_pruned_index(self, setup):
        index, q, qm = setup
        keep = index.d_masks & (jax.random.uniform(
            jax.random.PRNGKey(9), index.d_masks.shape) < 0.6)
        keep = keep.at[:, 0].set(index.d_masks[:, 0])  # >= 1 token/doc
        pruned = index.with_keep(keep)
        r = maxsim_scores(pruned, q, qm, backend="reference")
        f = maxsim_scores(pruned, q, qm, backend="fused")
        np.testing.assert_allclose(np.asarray(r), np.asarray(f),
                                   rtol=1e-5, atol=1e-5)

    def test_no_4d_tensor_in_fused_hlo(self, setup):
        """Acceptance criterion: the compiled fused serving path never
        materializes the (n_q, n_docs, l, m) score tensor; the reference
        einsum path provably does."""
        index, q, qm = setup
        n_q, l = q.shape[:2]
        n_docs, m = index.d_masks.shape
        # both the StableHLO spelling (7x33x6x12) and HLO ([7,33,6,12])
        pat = re.compile(
            rf"{n_q}x{n_docs}x{l}x{m}|f32\[{n_q},{n_docs},{l},{m}\]")

        def texts(backend):
            fn = jax.jit(lambda qq: maxsim_scores(index, qq, qm,
                                                  backend=backend))
            lowered = fn.lower(q)
            return lowered.as_text(), lowered.compile().as_text()

        ref_low, _ = texts("reference")
        assert pat.search(ref_low), \
            "oracle changed: reference lowering no longer builds the 4-D"
        fus_low, fus_comp = texts("fused")
        assert not pat.search(fus_low) and not pat.search(fus_comp), \
            "fused path materialized the 4-D score tensor"


class TestBackendResolution:
    def test_explicit_wins(self):
        assert backend_lib.resolve_backend("fused") == "fused"
        assert backend_lib.resolve_backend("reference") == "reference"

    def test_env_var_override(self):
        old = os.environ.get("REPRO_BACKEND")
        try:
            os.environ["REPRO_BACKEND"] = "fused"
            assert backend_lib.resolve_backend(None) == "fused"
            os.environ["REPRO_BACKEND"] = "shortlist_topk"
            assert backend_lib.resolve_backend(None) == "shortlist_topk"
            # valid name outside this path's allow-set: platform default
            assert backend_lib.resolve_backend(
                None, allow=backend_lib.SERVING) in backend_lib.SERVING
            os.environ["REPRO_BACKEND"] = "fused"
            assert backend_lib.resolve_backend(
                None, allow=backend_lib.PRUNING) in backend_lib.PRUNING
            # a removed name is no backend at all: loud failure
            os.environ["REPRO_BACKEND"] = "shortlist"
            with pytest.raises(ValueError, match="REPRO_BACKEND"):
                backend_lib.resolve_backend(None)
            # typo: loud failure everywhere
            os.environ["REPRO_BACKEND"] = "fusedd"
            with pytest.raises(ValueError, match="REPRO_BACKEND"):
                backend_lib.resolve_backend(None)
        finally:
            if old is None:
                os.environ.pop("REPRO_BACKEND", None)
            else:
                os.environ["REPRO_BACKEND"] = old

    def test_platform_default(self):
        old = os.environ.pop("REPRO_BACKEND", None)
        try:
            # TPU prefers the partitionable kernel paths: shortlist_topk
            # where the caller allows it (pruning), fused otherwise
            # (serving); off-TPU the reference path wins.
            on_tpu = backend_lib.on_tpu()
            expect = "shortlist_topk" if on_tpu else "reference"
            assert backend_lib.resolve_backend(None) == expect
            expect_srv = "fused" if on_tpu else "reference"
            assert backend_lib.resolve_backend(
                None, allow=backend_lib.SERVING) == expect_srv
            assert backend_lib.resolve_backend(
                None, allow=("reference", "fused")) == expect_srv
        finally:
            if old is not None:
                os.environ["REPRO_BACKEND"] = old

    def test_invalid_rejected(self):
        with pytest.raises(ValueError, match="backend"):
            backend_lib.resolve_backend("nope")
        with pytest.raises(ValueError, match="backend"):
            backend_lib.resolve_backend("shortlist_topk",
                                        allow=backend_lib.SERVING)
        with pytest.raises(ValueError, match="backend"):
            backend_lib.resolve_backend("fused", allow=backend_lib.PRUNING)

    def test_default_interpret_policy(self):
        assert backend_lib.default_interpret(True) is True
        assert backend_lib.default_interpret(False) is False
        assert backend_lib.default_interpret(None) == (
            not backend_lib.on_tpu())
