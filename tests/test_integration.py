"""Integration tests: end-to-end pipeline + fault-tolerant restart.

These exercise the same code paths as examples/ and the launch drivers:
train -> checkpoint -> kill -> resume (bit-exact continuation), and
encode -> prune -> serve with quality ordering guarantees.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, metrics, voronoi
from repro.core.sampling import sample_sphere
from repro.data import synthetic
from repro.launch import train as train_driver
from repro.serve.retrieval import RetrievalServer, TokenIndex, search
from repro.train import checkpoint


class TestTrainDriverRestart:
    def test_resume_is_bit_exact(self, tmp_path):
        """Training 12 steps straight == training 6, 'crashing', resuming
        for 6 more — the checkpoint + step-indexed pipeline contract."""
        ck1 = str(tmp_path / "a")
        ck2 = str(tmp_path / "b")
        full = train_driver.run("dcn-v2", steps=12, batch=4, ckpt_dir=ck1,
                                ckpt_every=100, log_every=0)
        part = train_driver.run("dcn-v2", steps=12, batch=4, ckpt_dir=ck2,
                                ckpt_every=3, log_every=0, stop_after=6)
        resumed = train_driver.run("dcn-v2", steps=12, batch=4, ckpt_dir=ck2,
                                   ckpt_every=100, log_every=0)
        assert resumed["start"] == 6
        np.testing.assert_allclose(resumed["final_loss"],
                                   full["final_loss"], rtol=1e-5)

    def test_resume_skips_corrupt_checkpoint(self, tmp_path):
        ck = str(tmp_path / "c")
        train_driver.run("dcn-v2", steps=8, batch=4, ckpt_dir=ck,
                         ckpt_every=2, log_every=0, stop_after=6)
        steps = checkpoint.list_steps(ck)
        assert steps, "expected checkpoints"
        # corrupt the newest (body filename depends on optional compression)
        newest_dir = os.path.join(ck, f"step_{steps[-1]:09d}")
        (newest,) = [os.path.join(newest_dir, n)
                     for n in os.listdir(newest_dir)
                     if n.startswith("leaves.msgpack")]
        with open(newest, "r+b") as f:
            f.seek(20)
            f.write(b"\xde\xad\xbe\xef")
        out = train_driver.run("dcn-v2", steps=8, batch=4,
                               ckpt_dir=ck, ckpt_every=100, log_every=0)
        assert out["start"] in steps[:-1]  # fell back to an older valid one

    @pytest.mark.parametrize("arch", ["gin-tu", "bert4rec"])
    def test_driver_covers_families(self, arch, tmp_path):
        out = train_driver.run(arch, steps=4, batch=4,
                               ckpt_dir=str(tmp_path / arch), ckpt_every=2,
                               log_every=0)
        assert np.isfinite(out["final_loss"])


class TestEndToEndRetrieval:
    @pytest.fixture(scope="class")
    def corpus(self):
        return synthetic.embedding_corpus(seed=0, n_docs=128, n_q=32,
                                          dim=16, m=24, stop_frac=0.5,
                                          noise=0.5, n_topics=16)

    def test_vp_beats_random_and_firstk_at_half_budget(self, corpus):
        c = corpus
        index = TokenIndex.build(c.d_embs, c.d_masks)
        samples = sample_sphere(jax.random.PRNGKey(1), 3000, 16)
        ranks, errs, _ = voronoi.pruning_order_batch(c.d_embs, c.d_masks,
                                                     samples)
        keep = voronoi.global_keep_masks(ranks, errs, c.d_masks, 0.5)

        def ndcg(k):
            s, g = search(index.with_keep(k), c.q_embs, k=10,
                          end_to_end=True)[2], c.gains
            return float(metrics.ndcg_at_k(s, g, 10))

        vp = ndcg(keep)
        rnd = ndcg(baselines.random_prune(jax.random.PRNGKey(2),
                                          c.d_masks, 0.5))
        fk = ndcg(baselines.first_k(c.d_masks, 0.5))
        assert vp >= rnd and vp >= fk, (vp, rnd, fk)

    def test_two_stage_close_to_exact(self, corpus):
        c = corpus
        index = TokenIndex.build(c.d_embs, c.d_masks)
        _, _, full_exact = search(index, c.q_embs, k=10, end_to_end=True)
        _, _, full_2stage = search(index, c.q_embs, k=10, n_first=48)
        m_exact = float(metrics.mrr_at_k(full_exact, c.rel, 10))
        m_2stage = float(metrics.mrr_at_k(full_2stage, c.rel, 10))
        assert m_2stage >= 0.9 * m_exact

    def test_server_batching_consistent(self, corpus):
        c = corpus
        index = TokenIndex.build(c.d_embs, c.d_masks)
        server = RetrievalServer(index, k=5, n_first=32)
        idx_all, _ = server.query_batch(c.q_embs[:8])
        idx_one, _ = server.query_batch(c.q_embs[:1])
        np.testing.assert_array_equal(idx_all[0], idx_one[0])

    def test_storage_accounting(self, corpus):
        c = corpus
        index = TokenIndex.build(c.d_embs, c.d_masks)
        keep = baselines.first_k(c.d_masks, 0.25)
        st = index.with_keep(keep).storage()
        assert st["tokens_kept"] < st["tokens_total"]
        assert st["bytes_fp32"] == st["tokens_kept"] * 16 * 4
        assert 20.0 <= st["remain_pct"] <= 35.0

    def test_me_guided_budget_selection(self, corpus):
        """§6.4 workflow: pick the smallest budget whose ME is under a
        threshold; the resulting nDCG must be within the linear-fit
        prediction's neighborhood (sanity of the guidance loop)."""
        c = corpus
        samples = sample_sphere(jax.random.PRNGKey(3), 3000, 16)
        ranks, errs, _ = voronoi.pruning_order_batch(c.d_embs, c.d_masks,
                                                     samples)
        mes, nds = [], []
        index = TokenIndex.build(c.d_embs, c.d_masks)
        for b in (0.8, 0.6, 0.4, 0.2):
            keep = voronoi.global_keep_masks(ranks, errs, c.d_masks, b)
            mes.append(float(voronoi.mean_error_batch(
                c.d_embs, c.d_masks, keep, samples).mean()))
            s = search(index.with_keep(keep), c.q_embs, k=10,
                       end_to_end=True)[2]
            nds.append(float(metrics.ndcg_at_k(s, c.gains, 10)))
        # ME monotone in pruning aggressiveness; nDCG anti-correlates
        assert all(a <= b + 1e-9 for a, b in zip(mes, mes[1:]))
        fit = metrics.linear_fit(mes, nds)
        assert fit["slope"] < 0

    def test_fast_and_reference_orders_agree(self, corpus):
        """The fast path (the shortlist_topk scan) removes tokens in the
        reference's order on the retrieval corpus."""
        c = corpus
        samples = sample_sphere(jax.random.PRNGKey(4), 1000, 16)
        r_ref, _, _ = voronoi.pruning_order_batch(
            c.d_embs[:8], c.d_masks[:8], samples, backend="reference")
        r_sl, _, _ = voronoi.pruning_order_batch(
            c.d_embs[:8], c.d_masks[:8], samples, backend="shortlist_topk")
        assert bool((r_ref == r_sl).all())
