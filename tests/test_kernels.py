"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import sweep
from repro.kernels.colbert_maxsim.ops import (colbert_maxsim_batch_op,
                                              colbert_maxsim_multi_op,
                                              colbert_maxsim_op,
                                              colbert_maxsim_rerank_op)
from repro.kernels.colbert_maxsim.ref import (colbert_maxsim_multi_ref,
                                              colbert_maxsim_ref)
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.maxsim_topk.ops import maxsim_topk_op
from repro.kernels.maxsim_topk.ref import maxsim_topk_ref
from repro.core.scoring import top2_scores


class TestMaxSimTopK:
    """maxsim_topk vs the lax.top_k oracle: the contract is BIT-identical
    output (values AND indices), sorted order and tie-breaking included —
    the shortlist_topk pruning path leans on it for exactness."""

    @sweep(n_cases=12, seed=0, N=[16, 100, 257], m=[9, 48, 130],
           k=[1, 4, 16], block_s=[32, 256], block_t=[16, 128])
    def test_matches_oracle_bitwise(self, N, m, k, block_s, block_t):
        if k > m:
            k = m
        key = jax.random.PRNGKey(N * m + k)
        S = jax.random.normal(key, (N, 16))
        D = jax.random.normal(jax.random.fold_in(key, 1), (m, 16))
        alive = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.75, (m,))
        alive = alive.at[0].set(True)
        v, i = maxsim_topk_op(S, D, alive, k=k, block_s=block_s,
                              block_t=block_t)
        rv, ri = maxsim_topk_ref(S, D, alive, k)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))

    @sweep(n_cases=6, seed=3, m=[24, 64], k=[4, 8], block_t=[16, 32])
    def test_ties_resolve_to_lowest_index(self, m, k, block_t):
        """Duplicate token rows + coarse quantization force exact score
        ties, including across tile boundaries; lax.top_k's sorted-
        descending lowest-index-first order must be reproduced."""
        key = jax.random.PRNGKey(m + k)
        S = jnp.round(jax.random.normal(key, (64, 8)) * 2) / 2
        D = jnp.round(jax.random.normal(jax.random.fold_in(key, 1),
                                        (m, 8)) * 2) / 2
        # duplicates straddling tile boundaries of every block_t swept
        D = D.at[m - 1].set(D[0]).at[m // 2].set(D[1]).at[2].set(D[1])
        alive = jnp.ones((m,), bool)
        v, i = maxsim_topk_op(S, D, alive, k=k, block_t=block_t)
        rv, ri = maxsim_topk_ref(S, D, alive, k)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))

    def test_k_equals_m_returns_full_argsort(self):
        S = jax.random.normal(jax.random.PRNGKey(0), (33, 8))
        D = jax.random.normal(jax.random.PRNGKey(1), (12, 8))
        alive = jnp.arange(12) < 9
        v, i = maxsim_topk_op(S, D, alive, k=12, block_t=8)
        rv, ri = maxsim_topk_ref(S, D, alive, 12)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
        # dead tokens trail, lowest dead index first
        assert bool((np.asarray(v)[:, 9:] <= -1e29).all())

    def test_k_above_m_rejected(self):
        S = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
        D = jax.random.normal(jax.random.PRNGKey(1), (5, 4))
        with pytest.raises(ValueError, match="exceeds token count"):
            maxsim_topk_op(S, D, jnp.ones((5,), bool), k=6)

    def test_top2_agreement(self):
        """k=2 specializes to exactly the Voronoi estimator's top-2
        (``core.scoring.top2_scores``: best, second and their tokens)."""
        S = jax.random.normal(jax.random.PRNGKey(2), (50, 16))
        D = jax.random.normal(jax.random.PRNGKey(3), (40, 16))
        alive = jax.random.bernoulli(jax.random.PRNGKey(4), 0.7, (40,))
        alive = alive.at[0].set(True).at[1].set(True)
        v, i = maxsim_topk_op(S, D, alive, k=2, block_t=16)
        b, s, bi, si = top2_scores(S, D, alive)
        np.testing.assert_array_equal(np.asarray(v[:, 0]), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(v[:, 1]), np.asarray(s))
        np.testing.assert_array_equal(np.asarray(i[:, 0]), np.asarray(bi))
        np.testing.assert_array_equal(np.asarray(i[:, 1]), np.asarray(si))


class TestColbertMaxsim:
    @sweep(n_cases=8, seed=1, n_docs=[3, 10, 33], m=[8, 24, 48],
           l=[4, 16], dim=[16, 128])
    def test_matches_oracle(self, n_docs, m, l, dim):
        k = jax.random.PRNGKey(n_docs * m + l)
        k1, k2, k3 = jax.random.split(k, 3)
        q = jax.random.normal(k1, (l, dim))
        d = jax.random.normal(k2, (n_docs, m, dim))
        msk = jax.random.bernoulli(k3, 0.85, (n_docs, m)).at[:, 0].set(True)
        out = colbert_maxsim_op(q, d, msk)
        ref = colbert_maxsim_ref(q, d, msk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_batch_op(self):
        k = jax.random.PRNGKey(9)
        q = jax.random.normal(k, (5, 8, 32))
        d = jax.random.normal(jax.random.fold_in(k, 1), (12, 16, 32))
        msk = jnp.ones((12, 16), bool)
        out = colbert_maxsim_batch_op(q, d, msk)
        ref = jnp.stack([colbert_maxsim_ref(q[i], d, msk) for i in range(5)])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_fully_masked_tokens_ignored(self):
        q = jnp.ones((2, 4))
        d = jnp.stack([jnp.ones((3, 4)), 100 * jnp.ones((3, 4))])
        msk = jnp.array([[True, True, True], [False, False, True]])
        out = colbert_maxsim_op(q, d, msk)
        # doc 1's visible token scores 400 per query token
        np.testing.assert_allclose(np.asarray(out), [8.0, 800.0], rtol=1e-5)

    def test_q_mask_zeroes_masked_query_tokens(self):
        q = jnp.ones((3, 4))
        d = jnp.stack([jnp.ones((3, 4)), 2 * jnp.ones((3, 4))])
        msk = jnp.ones((2, 3), bool)
        qm = jnp.array([True, True, False])
        out = colbert_maxsim_op(q, d, msk, qm)
        np.testing.assert_allclose(np.asarray(out), [8.0, 16.0], rtol=1e-5)


class TestColbertMaxsimMulti:
    @sweep(n_cases=8, seed=5, n_q=[1, 3, 9], n_docs=[3, 10, 33],
           m=[8, 24], l=[4, 16], dim=[16, 64])
    def test_matches_oracle(self, n_q, n_docs, m, l, dim):
        k = jax.random.PRNGKey(n_q * n_docs + m + l)
        k1, k2, k3, k4 = jax.random.split(k, 4)
        q = jax.random.normal(k1, (n_q, l, dim))
        d = jax.random.normal(k2, (n_docs, m, dim))
        msk = jax.random.bernoulli(k3, 0.85, (n_docs, m)).at[:, 0].set(True)
        qm = jax.random.bernoulli(k4, 0.7, (n_q, l)).at[:, 0].set(True)
        out = colbert_maxsim_multi_op(q, d, msk, qm)
        ref = colbert_maxsim_multi_ref(q, d, msk, qm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_agrees_with_single_query_kernel(self):
        k = jax.random.PRNGKey(11)
        q = jax.random.normal(k, (4, 8, 32))
        d = jax.random.normal(jax.random.fold_in(k, 1), (12, 16, 32))
        msk = jnp.ones((12, 16), bool)
        out = colbert_maxsim_multi_op(q, d, msk)
        per_q = jnp.stack([colbert_maxsim_op(q[i], d, msk)
                           for i in range(4)])
        np.testing.assert_allclose(np.asarray(out), np.asarray(per_q),
                                   rtol=1e-5, atol=1e-5)

    def test_rerank_op_per_query_candidates(self):
        """Each query scored against its OWN candidate block."""
        k = jax.random.PRNGKey(13)
        n_q, nc, m, l, dim = 5, 6, 10, 4, 16
        q = jax.random.normal(k, (n_q, l, dim))
        d = jax.random.normal(jax.random.fold_in(k, 1), (n_q, nc, m, dim))
        msk = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.8,
                                   (n_q, nc, m)).at[:, :, 0].set(True)
        qm = jnp.ones((n_q, l), bool).at[:, -1].set(False)
        out = colbert_maxsim_rerank_op(q, d, msk, qm)
        ref = jnp.stack([colbert_maxsim_ref(q[i], d[i], msk[i], qm[i])
                         for i in range(n_q)])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestEmbeddingBag:
    @sweep(n_cases=8, seed=2, V=[32, 500], D=[8, 64, 128],
           n_bags=[4, 32], nnz=[1, 3, 7])
    def test_matches_oracle(self, V, D, n_bags, nnz):
        k = jax.random.PRNGKey(V + D)
        k1, k2 = jax.random.split(k)
        table = jax.random.normal(k1, (V, D))
        ids = jax.random.randint(k2, (n_bags, nnz), 0, V)
        for mode in ("sum", "mean"):
            out = embedding_bag_op(table, ids, mode=mode)
            ref = embedding_bag_ref(table, ids, mode)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)

    def test_repeated_ids(self):
        table = jnp.eye(4)
        ids = jnp.array([[2, 2, 2]])
        out = embedding_bag_op(table, ids)
        np.testing.assert_allclose(np.asarray(out),
                                   [[0.0, 0.0, 3.0, 0.0]], atol=1e-6)


class TestFlashAttention:
    @sweep(n_cases=8, seed=4, H=[2, 4], S=[48, 100], d=[16, 32],
           causal=[False, True], window=[None, 24])
    def test_matches_oracle(self, H, S, d, causal, window):
        from repro.kernels.flash_attention.ops import flash_attention_op
        from repro.kernels.flash_attention.ref import flash_attention_ref
        k0 = jax.random.PRNGKey(H * S + d)
        kq, kk, kv = jax.random.split(k0, 3)
        q = jax.random.normal(kq, (H, S, d))
        k = jax.random.normal(kk, (H, S, d))
        v = jax.random.normal(kv, (H, S, d))
        out = flash_attention_op(q, k, v, causal=causal, window=window)
        ref = flash_attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_gqa_broadcast(self):
        from repro.kernels.flash_attention.ops import flash_attention_op
        from repro.kernels.flash_attention.ref import flash_attention_ref
        k0 = jax.random.PRNGKey(0)
        q = jax.random.normal(k0, (4, 32, 16))
        k = jax.random.normal(jax.random.fold_in(k0, 1), (2, 32, 16))
        v = jax.random.normal(jax.random.fold_in(k0, 2), (2, 32, 16))
        out = flash_attention_op(q, k, v, causal=True)
        ref = flash_attention_ref(q, jnp.repeat(k, 2, 0),
                                  jnp.repeat(v, 2, 0), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_matches_model_attention_numerics(self):
        """The kernel reproduces the jnp attention path used by the LM
        (softmax in f32, same masking semantics)."""
        from repro.kernels.flash_attention.ops import flash_attention_op
        k0 = jax.random.PRNGKey(3)
        H, S, d = 2, 40, 16
        q = jax.random.normal(k0, (H, S, d))
        k = jax.random.normal(jax.random.fold_in(k0, 1), (H, S, d))
        v = jax.random.normal(jax.random.fold_in(k0, 2), (H, S, d))
        s = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(d)
        ii, jj = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        s = jnp.where((jj <= ii)[None], s, -1e30)
        ref = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v)
        out = flash_attention_op(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)


class TestColbertMaxsimResidual:
    """Interpret-mode parity for the fused residual-decode paths
    (deliverable of the residual codec PR): the compressed kernels must
    match eager decode + fp32 reference within quantization-noise
    tolerance, and the unchanged fp32 path must stay bitwise."""

    @staticmethod
    def _compressed(key, n_docs, m, dim, bits, C=6):
        from repro.train import compress
        rng = np.random.default_rng(int(key))
        codebook = rng.standard_normal((C, dim)).astype(np.float32)
        codes = rng.integers(0, C, (n_docs, m)).astype(np.int8)
        d = (codebook[codes.astype(int)]
             + 0.3 * rng.standard_normal((n_docs, m, dim))
             ).astype(np.float32)
        r = jnp.asarray(d) - jnp.asarray(codebook)[jnp.asarray(codes, jnp.int32)]
        resq, scale = compress.quantize_residual(r, bits)
        decoded = compress.dequantize_residual(
            resq, scale, jnp.asarray(codes), jnp.asarray(codebook), bits)
        return (jnp.asarray(codes), resq, scale, jnp.asarray(codebook),
                decoded)

    @sweep(n_cases=8, seed=7, n_q=[1, 4], n_docs=[5, 17], m=[8, 24],
           dim=[16, 32], bits=[2, 4])
    def test_multi_matches_eager_decode(self, n_q, n_docs, m, dim, bits):
        from repro.kernels.colbert_maxsim.ops import (
            colbert_maxsim_residual_multi_op)
        codes, resq, scale, cb, decoded = self._compressed(
            n_q * n_docs + m + bits, n_docs, m, dim, bits)
        k = jax.random.PRNGKey(n_docs + bits)
        q = jax.random.normal(k, (n_q, 6, dim))
        msk = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.85,
                                   (n_docs, m)).at[:, 0].set(True)
        qm = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.7,
                                  (n_q, 6)).at[:, 0].set(True)
        out = colbert_maxsim_residual_multi_op(q, codes, resq, scale, cb,
                                               msk, qm, bits=bits)
        ref = colbert_maxsim_multi_ref(q, decoded, msk, qm)
        # The decode itself is bitwise (one-hot MXU gather + exact
        # unpack); the residual tolerance covers XLA reassociating the
        # fused decode+score arithmetic.
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    @sweep(n_cases=4, seed=8, n_q=[2, 3], nc=[4, 9], m=[8, 12], bits=[2, 4])
    def test_rerank_matches_eager_decode(self, n_q, nc, m, bits):
        """Per-query gathered candidate blocks, each row decoding
        against its own codebook + per-token scales."""
        from repro.kernels.colbert_maxsim.ops import (
            colbert_maxsim_residual_rerank_op)
        dim = 16
        per_q = [self._compressed(100 * n_q + i + bits, nc, m, dim, bits)
                 for i in range(n_q)]
        codes = jnp.stack([p[0] for p in per_q])
        resq = jnp.stack([p[1] for p in per_q])
        scale = jnp.stack([p[2] for p in per_q])
        # per-candidate codebooks: candidate j of query i decodes with
        # its own (C, dim) table, as the mixed-bucket gather produces
        cbs = jnp.stack([jnp.broadcast_to(p[3], (nc,) + p[3].shape)
                         for p in per_q])
        decoded = jnp.stack([p[4] for p in per_q])
        k = jax.random.PRNGKey(nc + bits)
        q = jax.random.normal(k, (n_q, 5, dim))
        msk = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.8,
                                   (n_q, nc, m)).at[:, :, 0].set(True)
        qm = jnp.ones((n_q, 5), bool).at[:, -1].set(False)
        out = colbert_maxsim_residual_rerank_op(q, codes, resq, scale,
                                                cbs, msk, qm, bits=bits)
        ref = jnp.stack([colbert_maxsim_ref(q[i], decoded[i], msk[i], qm[i])
                         for i in range(n_q)])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_fp32_multi_stays_bitwise(self):
        """The codec must not perturb the uncompressed path: fused fp32
        multi-query scores equal the reference EXACTLY in interpret
        mode."""
        k = jax.random.PRNGKey(17)
        q = jax.random.normal(k, (3, 6, 32))
        d = jax.random.normal(jax.random.fold_in(k, 1), (14, 10, 32))
        msk = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.9,
                                   (14, 10)).at[:, 0].set(True)
        qm = jnp.ones((3, 6), bool)
        out = colbert_maxsim_multi_op(q, d, msk, qm)
        ref = colbert_maxsim_multi_ref(q, d, msk, qm)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_onehot_gather_is_bitwise(self):
        """The in-kernel codebook gather (one-hot matmul) reproduces
        codebook[codes] exactly — decode error comes only from the
        residual quantizer, never the gather."""
        from repro.kernels.colbert_maxsim.colbert_maxsim import (
            _gather_codebook)
        rng = np.random.default_rng(5)
        cb = jnp.asarray(rng.standard_normal((7, 24)), jnp.float32)
        codes = jnp.asarray(rng.integers(0, 7, (9, 13)), jnp.int8)
        out = _gather_codebook(codes, cb)
        ref = cb[codes.astype(jnp.int32)]
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
