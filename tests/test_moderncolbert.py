"""GTE-ModernColBERT-v1 (ModernBERT-base block) against its plain fp32
reference, at a smoke size on the CPU: global, local, local, global
layers of width 64, 4 heads, GeGLU 96, a window of 8 (|i - j| <= 4),
documents of 40 tokens.  Also: the local layers' reach, the planted
faults the parity must catch, the query expansion mask, the default
ColBERT encoder pinned to its outputs before the second backbone, and
the launcher's retrieval path with the new arch.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.launch import serve
from repro.models import colbert
from repro.models import reference_modernbert as ref
from repro.models import transformer as tfm

CFG = configs.get("gte-moderncolbert").smoke
# fp32 throughout: the program's scan, fused projections and its own
# reduction order against the reference's plain layer loop differ by a
# few fp32 roundings per layer (2.4e-7 in 1 - cos and 3.5e-7 per value
# measured here), so 1e-5 leaves a margin of 30x and is still 50x below
# the smallest planted fault (a LayerNorm in layer 0, 5.6e-4 in 1 - cos).
TOL_COS = 1e-5
TOL_ABS = 1e-5


@pytest.fixture(scope="module")
def params():
    return colbert.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def doc_ids():
    rng = np.random.default_rng(0)
    ids = rng.integers(4, CFG.vocab, (4, CFG.doc_len)).astype(np.int32)
    ids[:, 0] = 2
    ids[1, 20:] = 0
    ids[2, 9:] = 0
    return ids


def _gaps(got, want, mask):
    got, want = np.asarray(got), np.asarray(want)
    mask = np.asarray(mask, bool)
    cos = (got * want).sum(-1)
    return float((1 - cos)[mask].max()), float(np.abs(got - want)[mask].max())


def _encode_docs(params, cfg, ids):
    with jax.default_matmul_precision("highest"):
        return colbert.encode_docs(params, cfg, jnp.asarray(ids))


def test_docs_match_reference(params, doc_ids):
    got, mask = _encode_docs(params, CFG, doc_ids)
    want, want_mask = ref.encode_docs(params, CFG, doc_ids)
    assert np.array_equal(np.asarray(mask), want_mask)
    gap_cos, gap_abs = _gaps(got, want, mask)
    assert gap_cos <= TOL_COS and gap_abs <= TOL_ABS


def test_queries_match_reference(params, doc_ids):
    q = doc_ids[:, :5].copy()
    q[:, 0] = 1
    q[3, 3:] = 0
    with jax.default_matmul_precision("highest"):
        got, mask = colbert.encode_queries(params, CFG, jnp.asarray(q))
    want, want_mask = ref.encode_queries(params, CFG, q)
    assert got.shape == (4, CFG.query_len, CFG.out_dim)
    assert np.asarray(mask).all() and want_mask.all()
    gap_cos, gap_abs = _gaps(got, want, mask)
    assert gap_cos <= TOL_COS and gap_abs <= TOL_ABS


def test_param_count_is_modernbert_base():
    full = configs.get("gte-moderncolbert").config
    # ModernBERT-base: 149,014,272 backbone parameters, plus the
    # 768 x 128 ColBERT projection.
    assert full.lm_config().param_count() == 149_014_272
    assert full.param_count() == 149_014_272 + 768 * 128
    shapes = jax.eval_shape(lambda k: colbert.init_params(k, CFG),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == CFG.param_count()


@pytest.mark.parametrize("moved,changed", [(20, False), (35, False),
                                           (12, True), (3, True)])
def test_local_layers_reach_layers_times_half_window(params, moved,
                                                     changed):
    """Two local layers (band 4 each) carry a change at most 8 positions:
    changing token ``moved`` leaves position 11 bit for bit the same at
    distance > 8 and changes it within 8."""
    lm = CFG.lm_config()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, CFG.doc_len, CFG.d_model)),
                    jnp.float32)
    attend = jnp.ones((1, CFG.doc_len), bool)
    layers = [ref.layer_params(params["backbone"], i, CFG.global_every)
              for i in (1, 2)]

    def local_stack(h):
        for lp in layers:
            h = tfm._modernbert_block(lm, h, lp, attend, local=True)
        return h

    base = local_stack(x)
    bumped = local_stack(x.at[0, moved].add(1.0))
    same = np.array_equal(np.asarray(base[0, 11]), np.asarray(bumped[0, 11]))
    assert same is not changed


def _swapped_rope(params, monkeypatch):
    return dataclasses.replace(CFG, rope_theta=CFG.local_rope_theta,
                               local_rope_theta=CFG.rope_theta), params


def _no_window(params, monkeypatch):
    return dataclasses.replace(CFG, local_window=10 * CFG.doc_len), params


def _swiglu(params, monkeypatch):
    def swiglu(x, w_in, w_out):
        a, g = jnp.split(x @ w_in, 2, axis=-1)
        return (jax.nn.silu(a) * g) @ w_out
    monkeypatch.setattr(tfm, "geglu", swiglu)
    return CFG, params


def _layer0_norm(params, monkeypatch):
    bb = dict(params["backbone"])
    bb["layer0"] = dict(bb["layer0"],
                        ln1=jnp.ones((CFG.d_model,), jnp.float32))
    return CFG, dict(params, backbone=bb)


@pytest.mark.parametrize("fault", [_swapped_rope, _no_window, _swiglu,
                                   _layer0_norm])
def test_planted_fault_breaks_parity(params, doc_ids, monkeypatch, fault):
    cfg, p = fault(params, monkeypatch)
    got, mask = _encode_docs(p, cfg, doc_ids)
    want, _ = ref.encode_docs(params, CFG, doc_ids)
    gap_cos, _ = _gaps(got, want, mask)
    assert gap_cos > 10 * TOL_COS


def test_expansion_tokens_are_scored_but_not_attended(params, doc_ids):
    """Real query tokens read the same whatever the expansion length,
    since no token attends to [MASK] expansion; attending to it (the
    ColBERTv2 convention) changes them.  Every position is scored."""
    q = doc_ids[:, :5].copy()
    q[:, 0] = 1
    wide = dataclasses.replace(CFG, query_len=2 * CFG.query_len)
    with jax.default_matmul_precision("highest"):
        short, mask = colbert.encode_queries(params, CFG, jnp.asarray(q))
        long, long_mask = colbert.encode_queries(params, wide,
                                                 jnp.asarray(q))
        attended, _ = colbert.encode_queries(
            params, dataclasses.replace(CFG, attend_expansion=True),
            jnp.asarray(q))
    assert np.asarray(mask).all() and np.asarray(long_mask).all()
    np.testing.assert_allclose(np.asarray(short[:, :5]),
                               np.asarray(long[:, :5]), atol=1e-6)
    assert not np.allclose(np.asarray(short[:, :5]),
                           np.asarray(attended[:, :5]), atol=1e-3)


def test_default_colbert_encoder_unchanged():
    """The default backbone's smoke encoder reads as it did before the
    ModernBERT block was added (values taken on that tree, fp32 CPU)."""
    cfg = configs.get("colbert").smoke
    assert cfg.backbone == "rmsnorm_swiglu"
    p = colbert.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(4, cfg.vocab, (3, cfg.doc_len)).astype(np.int32)
    ids[:, 0] = 2
    ids[1, 15:] = 0
    ids[2, 7:] = 0
    e, m = colbert.encode_docs(p, cfg, jnp.asarray(ids))
    q, _ = colbert.encode_queries(p, cfg, jnp.asarray(ids[:, :5]))
    np.testing.assert_allclose(np.asarray(e[:, :2, :3]), [
        [[0.08467736, -0.099949405, 0.106630765],
         [-0.075472265, 0.050717246, -0.012975892]],
        [[0.03078359, -0.0144994855, 0.15229957],
         [0.025443222, 0.044792533, 0.33581087]],
        [[-0.099020995, 0.04167925, -0.14018656],
         [-0.14091155, 0.2033454, -0.15774539]]], atol=2e-6)
    np.testing.assert_allclose(np.asarray((e * m[..., None]).sum((1, 2))),
                               [-15.555154, -13.928833, 1.9718449],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(q[:, -2:, :3]), [
        [[0.34000063, -0.105035104, 0.2463526],
         [0.3880203, -0.12043343, 0.29315066]],
        [[-0.025152687, -0.07997696, 0.20936096],
         [-0.01202071, -0.25941014, 0.24455865]],
        [[0.37866664, 0.07262206, -0.008619022],
         [0.37073162, 0.09461072, -0.040732335]]], atol=2e-6)
    np.testing.assert_allclose(np.asarray(q.sum((1, 2))),
                               [7.7693686, -6.517005, 4.044487], atol=2e-5)


def test_encode_corpus_counts_and_marks_its_dispatches(params, doc_ids,
                                                       tmp_path):
    """Every dispatch adds its real tokens and slots (padding rows of the
    last batch included) to the stats and is one ``repro.encode`` span
    with the same numbers."""
    stats = serve.EncodeStats()
    trace_dir = str(tmp_path / "trace")
    obs.enable(True)
    jax.profiler.start_trace(trace_dir)
    try:
        e, mk = serve.encode_corpus(params, CFG, doc_ids, batch=3,
                                    stats=stats)
        e.block_until_ready()
    finally:
        jax.profiler.stop_trace()
        obs.enable(False)
    real = int((doc_ids != 0).sum())
    assert e.shape == (4, CFG.doc_len, CFG.out_dim) and e.dtype == jnp.float32
    assert (stats.real_tokens, stats.slots) == (real, 6 * CFG.doc_len)
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = [dict(ev.stats) for plane in
             jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "repro.encode"]
    assert len(spans) == 2
    assert sorted(s["docs"] for s in spans) == [1, 3]
    assert sum(s["real_tokens"] for s in spans) == real
    assert all(s["slots"] == 3 * CFG.doc_len for s in spans)
    assert all(s["backbone"] == "modernbert" for s in spans)


def test_launcher_accepts_retrieval_archs():
    assert serve.is_retrieval("colbert")
    assert serve.is_retrieval("gte-moderncolbert")
    assert not serve.is_retrieval("mixtral-8x7b")
    args = serve.parse_args(["--arch", "gte-moderncolbert", "--preset",
                             "full", "--serve-loop"])
    assert args.arch == "gte-moderncolbert" and args.preset == "full"


def test_launcher_serves_gte_moderncolbert_smoke(capsys):
    """build -> prune -> pack -> serve through the launcher's normal path,
    at the smoke config, on the CPU."""
    idx, scores = serve.serve_retrieval(arch="gte-moderncolbert",
                                        n_docs=24, n_queries=4)
    out = capsys.readouterr().out
    assert "[serve] storage: codec=fp32" in out
    assert np.asarray(idx).shape == (4, 10)
    assert np.isfinite(np.asarray(scores)).all()
    ids = np.asarray(idx)
    assert ((ids >= 0) & (ids < 24)).all()
