"""CPU checks of the yardstick: work counts against hand-computed ones,
the peaks table, the inputs drawn from seeds, the plain references
against the program at small shapes, and the trace reduction against a
trace recorded on a TPU v5e."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import inputs, layers, peaks, reference, trace, work  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "serve_res2.xplane.pb.gz")
FP32 = {"compression": "none"}
RES2 = {"compression": "residual", "residual_bits": 2}


# -- work counts -----------------------------------------------------------

def test_maxsim_counts_by_hand():
    assert work.maxsim_flops(2, 3, 4, 5) == 2 * 2 * 3 * 4 * 5
    assert work.token_bytes(128, FP32) == 128 * 4
    assert work.token_bytes(128, RES2) == 1 + 32 + 4
    # 17 kept tokens x 16 bytes + queries 2x3x4 f32 + scores 2x5 f32
    assert work.maxsim_bytes(2, 3, 4, 5, 17, FP32) == 272 + 96 + 40
    assert work.maxsim_bytes(2, 3, 4, 5, 17, RES2, n_centroids=7) == (
        17 * (1 + 1 + 4) + 7 * 4 * 4 + 96 + 40)
    flops, nbytes = work.serve_call_work(2, 3, 4, [(5, 17), (1, 6)], FP32)
    assert flops == 2 * 2 * 3 * 4 * (17 + 6)
    assert nbytes == 408 + (6 * 16 + 96 + 8)
    assert work.serve_query_flops(32, 128, 1000) == 2 * 32 * 128 * 1000


def test_real_rows_leave_out_loop_padding():
    from benchlib import serving
    q = np.arange(5 * 2 * 3, dtype=np.float32).reshape(5, 2, 3)
    assert serving.real_rows(q) == 5
    padded = np.concatenate([q[:3], np.broadcast_to(q[:1], (5,) + q.shape[1:])])
    assert serving.real_rows(padded) == 3
    assert serving.real_rows(q[:1]) == 1


def test_encoder_and_voronoi_counts_by_hand():
    model = {"n_layers": 2, "d_model": 4, "d_ff": 8, "out_dim": 3}
    per_layer = 8 * 10 * 16 + 4 * 100 * 4 + 6 * 10 * 4 * 8
    assert work.encoder_flops(10, model) == 2 * per_layer + 2 * 10 * 4 * 3
    assert work.voronoi_least_flops(10, 100, 4) == 2 * 100 * 4 * 10


def test_least_time_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.flops == 197e12 and p.hbm_bw == 819e9
    assert work.least_time(197e12, 819e9 / 2, p) == (1.0, "compute")
    assert work.least_time(197e12 / 4, 819e9, p) == (1.0, "memory")
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")


# -- inputs ---------------------------------------------------------------

def test_inputs_repeat_for_a_seed_over_32_bits():
    seed = 2 ** 40 + 3
    a = inputs.poisson_schedule(inputs.rng_for(seed, 1), 500.0, 2.0)
    b = inputs.poisson_schedule(inputs.rng_for(seed, 1), 500.0, 2.0)
    c = inputs.poisson_schedule(inputs.rng_for(seed + 1, 1), 500.0, 2.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # every seed sends the same count, over the same span, same gaps
    assert len(a) == len(c) == 1000
    gaps = [np.sort(np.append(np.diff(x), 2.0 - x[-1])) for x in (a, c)]
    assert np.allclose(*gaps)
    assert a[-1] < 2.0


def test_slab_counts_follow_the_law():
    law = {"median": 70, "sigma": 0.55, "min": 8, "max": 180}
    edges = [(0, 8), (8, 16), (16, 32), (32, 64), (64, 128), (128, 180)]
    counts = inputs.range_counts(law, edges, 64)
    assert sum(counts) == 64
    lens = inputs.slab_lengths(inputs.rng_for(5), law, edges, counts)
    for (lo, hi), c in zip(edges, counts):
        assert ((lens > lo) & (lens <= hi)).sum() == c
    big = inputs.lognormal_lengths(inputs.rng_for(6), 200_000, law)
    share = [((big > lo) & (big <= hi)).mean() * 64 for lo, hi in edges]
    assert np.allclose(counts, share, atol=1.0)


# -- references -----------------------------------------------------------

def _corpus(n=20, m=32, dim=16, seed=1):
    rng = inputs.rng_for(seed)
    lens = inputs.lognormal_lengths(rng, n, {"median": 12, "sigma": 0.6,
                                             "min": 2, "max": m})
    e = rng.standard_normal((n, m, dim)).astype(np.float32)
    e /= np.linalg.norm(e, axis=-1, keepdims=True)
    return e, np.arange(m)[None] < lens[:, None]


@pytest.mark.parametrize("backend", ["reference", "shortlist_topk"])
def test_voronoi_reference_matches_program(backend):
    from repro.core import pruning_pipeline
    e, mk = _corpus()
    s = inputs.sphere_samples(3, 300, e.shape[-1])
    keep, _, _ = pruning_pipeline.prune_corpus(
        jnp.asarray(e), jnp.asarray(mk), jnp.asarray(s), 0.5,
        backend=backend)
    ref = reference.keep_reference(e, mk, s, 0.5)
    assert reference.keep_mismatch(keep, ref, mk) == 0.0
    assert ref.sum() == int(np.ceil(0.5 * mk.sum()))
    assert (ref & mk).sum(1).min() >= 1


def test_decode_and_pack_checks_match_program():
    from repro.serve.index import PackedIndex
    e, mk = _corpus()
    keep = mk & (np.arange(mk.shape[1])[None] % 2 == 0)
    fp = PackedIndex.pack(jnp.asarray(e), jnp.asarray(mk), jnp.asarray(keep))
    stored = reference.stored_arrays(fp)
    assert reference.pack_mismatch(stored, e, keep) == 0
    stored[0]["embs"] = stored[0]["embs"].copy()
    stored[0]["embs"][0, 0, 0] += 1.0
    assert reference.pack_mismatch(stored, e, keep) == 1
    res = PackedIndex.pack(jnp.asarray(e), jnp.asarray(mk), jnp.asarray(keep),
                           compression="residual", residual_bits=2,
                           n_centroids=8)
    docs, masks = reference.stored_tokens(reference.stored_arrays(res),
                                          res.n_docs, res.dim, 2)
    for b in res.buckets:
        ids = np.asarray(b.doc_ids)
        assert np.array_equal(docs[ids, :b.cap],
                              np.asarray(b.dense_embs(res.dim)))
        assert np.array_equal(masks[ids, :b.cap], np.asarray(b.masks))


def test_answer_gap_tells_rounding_from_a_wrong_answer():
    e, mk = _corpus()
    q = e[:4, :8]
    ref = reference.maxsim_scores(q, e, mk)
    ids, sc = reference.topk(ref, 5)
    assert reference.answer_gap(ids, sc, ref) == {"gap": 0.0, "bad_ids": 0}
    wrong = ids.copy()
    wrong[:, 0] = ids[:, -1] + 1
    assert reference.answer_gap(wrong, sc, ref)["gap"] > 0.05
    dup = ids.copy()
    dup[0, 1] = dup[0, 0]
    assert reference.answer_gap(dup, sc, ref)["bad_ids"] == 1


def test_control_precisions_are_lower():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 128)).astype(np.float32)
    b = rng.standard_normal((32, 128)).astype(np.float32)
    exact = a.astype(np.float64) @ b.T.astype(np.float64)
    err = {p: np.abs(np.asarray(reference.einsum(
        "nd,md->nm", jnp.asarray(a), jnp.asarray(b), p)) - exact).max()
        for p in reference.PRECISIONS}
    steps = [err[p] for p in reference.PRECISIONS]
    assert all(10 * a < b for a, b in zip(steps, steps[1:])), err
    assert reference.lower("highest") == "high"
    assert reference.lower("bf16") == "fp8"


# -- the trace reduction, on a trace recorded on a TPU v5e ----------------
# Six RetrievalServer.query_batch calls (3 of 32 queries, 3 of one) on a
# 2-bit residual index of 2048 documents at ColBERT widths.

@pytest.fixture(scope="module")
def recorded():
    return trace.reduce_xspace(trace.load_xspace(FIXTURE), ("server_call",))


def test_recorded_trace_reduces(recorded):
    assert recorded["window_ns"] == 21568819.0
    assert len(recorded["devices"]["/device:TPU:0"]) == 596
    assert [s[3]["n"] for s in recorded["spans"]] == [32] * 3 + [1] * 3
    assert trace.busy_ns(recorded) == 5141915.0
    assert trace.idle_share(recorded) == pytest.approx(0.76160424, abs=1e-8)


def test_recorded_trace_kernel_time_and_breakdown(recorded):
    kernel_s = trace.op_seconds(recorded,
                                lambda n: bool(layers.SCORER.search(n)))
    assert kernel_s == pytest.approx(0.004882721, abs=1e-12)
    b = trace.breakdown(recorded)
    assert b["device_ops"][0][0] == "colbert_maxsim_residual_multi"
    assert b["device_ops"][0][1] == pytest.approx(0.004882721, abs=1e-12)
    assert len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "server_call"
    assert b["idle_gaps"][0][1] == pytest.approx(0.016426904, abs=1e-9)


def test_roofline_counts_real_rows_against_every_kernel_event():
    p = peaks.peaks_for("TPU v5 lite")
    facts = {"buckets": [(4, 100), (2, 50)], "codec": FP32, "n_centroids": 0,
             "l": 32, "dim": 128}
    # two calls in the window (32 rows, 20 of them real; 8 rows, all
    # real) and one kernel event of a call that began before the window
    red = {"window_ns": 1e6, "spans": [
        ["server_call", 10, 100, {"n": 32, "n_real": 20}],
        ["server_call", 500, 100, {"n": 8, "n_real": 8}]],
        "devices": {"/device:TPU:0": [["colbert_maxsim_multi", 0, 5],
                                      ["colbert_maxsim_multi", 20, 40],
                                      ["fusion", 60, 10],
                                      ["colbert_maxsim_multi", 510, 30]]}}
    # each bucket call reads its kept tokens (512 B each), the real query
    # rows (16 KiB each) and writes 4 B per (row, doc): memory-bound here
    nbytes = sum(t * 512 + n_q * (32 * 128 * 4 + n * 4)
                 for n_q in (20, 8) for n, t in facts["buckets"])
    assert nbytes == 379200 + 353440 + 182400 + 156736
    got = layers.maxsim_roofline({"trace": red, "facts": facts, "peaks": p})
    assert got == pytest.approx(100 * nbytes / p.hbm_bw / 75e-9)
    assert layers.maxsim_roofline({"trace": None, "facts": facts,
                                   "peaks": p}) is None


def test_gc_pauses_are_counted():
    import gc
    from benchlib import common
    pauses = common.GcPauses()
    gc.collect()
    pauses.close()
    gc.collect()
    assert pauses.n == 1 and pauses.gen == 2 and pauses.longest > 0
    assert "1 gc collections" in str(pauses)


def test_reduction_by_hand():
    red = {"window_ns": 100.0, "spans": [["a", 0, 50, {}], ["b", 60, 40, {}],
                                         ["a", 70, 10, {}]],
           "devices": {"/device:TPU:0": [["while", 10, 30, ], ["fusion", 15, 10],
                                         ["k", 20, 5], ["k", 75, 10]]}}
    red["devices"]["/device:TPU:0"] = [list(o) for o in
                                       red["devices"]["/device:TPU:0"]]
    assert trace.busy_ns(red) == 40
    assert trace.idle_share(red) == pytest.approx(0.6)
    # gaps [0,10) in a, [40,75) mid 57.5 in none, [85,100) in b
    assert dict(trace.idle_gaps(red)) == pytest.approx(
        {"a": 10e-9, "none": 35e-9, "b": 15e-9})
    # self time: while 30 - fusion 10 = 20; fusion 10 - k 5 = 5; k 15
    assert dict(trace.top_ops(red)) == pytest.approx(
        {"while": 20e-9, "fusion": 5e-9, "k": 15e-9})
    assert trace.op_seconds(red, lambda n: n == "k") == pytest.approx(15e-9)
