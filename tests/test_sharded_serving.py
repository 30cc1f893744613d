"""Streaming / sharded top-k serving: the merge-tree dataflow vs the
materialize-then-top-k oracle.

Covers the contract of repro.serve.retrieval.topk_search and the
distributed pieces around it (DESIGN_BACKENDS.md §Sharded serving):
  * streaming top-k is IDENTICAL — ids and fp scores — to ``lax.top_k``
    over the materialized score matrix, per backend, per index layout,
    including empty-after-prune documents and query masks;
  * the compiled streaming HLO contains no (n_q, n_docs)-shaped
    intermediate, while the materializing path provably does (the twin
    of the no-4-D-einsum assertion);
  * under a 2-device mesh (subprocess with a forced host device count,
    the tests/test_sharded_exec.py pattern) the shard_map merge over the
    candidates axis stays bit-identical, including k > docs-in-shard;
  * the sharded ``global_keep_masks`` merge (bitwise selection over the
    data axis) matches the single-host argsort bit for bit, including
    tie-heavy corpora and doc counts that don't divide the shard count;
  * ``sharding.constrain`` swallows ONLY the outside-mesh case and
    re-raises genuine sharding errors.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import auto_mesh, make_serve_mesh
from repro.serve.retrieval import (RetrievalServer, TokenIndex,
                                   maxsim_scores, search, topk_search)
from repro.sharding import axis_rules, constrain, mesh_axes_for, serve_rules

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run_subprocess(code: str, n_devices: int = 2) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


# Shared corpus builder: ragged masks, bernoulli keep, selected docs
# pruned to zero tokens (the empty-after-prune edge).  Mirrored verbatim
# inside the subprocess snippets below.
_CORPUS_SRC = """
def _pruned_corpus(seed, n_docs, m, dim, empty=()):
    import jax, jax.numpy as jnp
    from repro.serve.retrieval import TokenIndex
    k = jax.random.PRNGKey(seed)
    d = jax.random.normal(k, (n_docs, m, dim)) * 0.5
    n_real = jax.random.randint(jax.random.fold_in(k, 1), (n_docs,),
                                1, m + 1)
    masks = jnp.arange(m)[None, :] < n_real[:, None]
    keep = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.6, (n_docs, m))
    for i in empty:
        keep = keep.at[i].set(False)
    return TokenIndex.build(d, masks).with_keep(keep)


def _queries(seed, n_q, l, dim):
    import jax, jax.numpy as jnp
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (n_q, l, dim))
    qn = jax.random.randint(jax.random.fold_in(k, 1), (n_q,), 1, l + 1)
    return q, jnp.arange(l)[None, :] < qn[:, None]
"""
exec(_CORPUS_SRC)


class TestStreamingParity:
    @pytest.mark.parametrize("backend", ["reference", "fused"])
    @pytest.mark.parametrize("layout", ["masked", "packed"])
    def test_topk_identical_to_materializing(self, backend, layout):
        """Streaming merge == lax.top_k over the full matrix: ids AND fp
        scores bitwise, odd chunking, empty-after-prune docs."""
        masked = _pruned_corpus(0, 37, 20, 8, empty=(0, 17))
        index = masked if layout == "masked" else masked.pack()
        q, qm = _queries(1, 6, 5, 8)
        full = maxsim_scores(index, q, qm, backend=backend)
        ref_s, ref_i = jax.lax.top_k(full, 7)
        top_i, top_s = topk_search(index, q, k=7, q_masks=qm,
                                   backend=backend, chunk_docs=7)
        np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(top_i))
        np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(top_s))

    @pytest.mark.parametrize("backend", ["reference", "fused"])
    def test_search_streaming_matches_materializing(self, backend):
        """search(return_full=False) — both stages — equals the
        materializing 3-tuple path's top-k."""
        masked = _pruned_corpus(2, 33, 16, 8, empty=(9,))
        q, qm = _queries(3, 5, 4, 8)
        for index in (masked, masked.pack()):
            for kw in (dict(end_to_end=True), dict(n_first=12)):
                i_m, s_m, _ = search(index, q, k=5, q_masks=qm,
                                     backend=backend, **kw)
                out = search(index, q, k=5, q_masks=qm, backend=backend,
                             return_full=False, **kw)
                assert len(out) == 2        # no densified matrix returned
                np.testing.assert_array_equal(np.asarray(i_m),
                                              np.asarray(out[0]))
                np.testing.assert_array_equal(np.asarray(s_m),
                                              np.asarray(out[1]))

    def test_server_serves_streaming(self):
        """RetrievalServer defaults to return_full=False and matches the
        materializing oracle on both its e2e and two-stage routes."""
        masked = _pruned_corpus(4, 29, 16, 8, empty=(5,))
        packed = masked.pack()
        q, _ = _queries(5, 4, 4, 8)
        for n_first in (64, 12):            # e2e route / two-stage route
            srv = RetrievalServer(packed, k=5, n_first=n_first)
            i_srv, s_srv = srv.query_batch(q)
            i_ref, s_ref, _ = search(packed, q, k=5, n_first=n_first)
            np.testing.assert_array_equal(i_srv, np.asarray(i_ref))
            np.testing.assert_array_equal(s_srv, np.asarray(s_ref))

    def test_empty_corpus(self):
        from repro.serve.index import PackedIndex
        packed = PackedIndex.pack(np.zeros((0, 8, 4)),
                                  np.zeros((0, 8), bool))
        i, s = topk_search(packed, jnp.ones((2, 3, 4)), k=4,
                           backend="reference")
        assert i.shape == (2, 0) and s.shape == (2, 0)

    def test_explicit_chunk_wins_and_autotuned_default(self):
        masked = _pruned_corpus(6, 18, 16, 8)
        q, _ = _queries(7, 4, 4, 8)
        a = topk_search(masked, q, k=4, chunk_docs=5)
        b = topk_search(masked, q, k=4)     # autotuned chunk
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


class TestStreamingHLO:
    @pytest.mark.parametrize("backend", ["reference", "fused"])
    def test_no_corpus_sized_matrix_in_streaming_hlo(self, backend):
        """Acceptance criterion: the compiled streaming serving path
        contains no (n_q, n_docs)-shaped tensor; the materializing path
        provably does (the oracle half keeps the pattern honest)."""
        n_q, n_docs, m, l, dim = 7, 64, 16, 6, 8
        k = jax.random.PRNGKey(0)
        index = TokenIndex.build(jax.random.normal(k, (n_docs, m, dim)),
                                 jnp.ones((n_docs, m), bool))
        q = jax.random.normal(jax.random.fold_in(k, 1), (n_q, l, dim))
        # StableHLO spelling (7x64x...) and compiled-HLO shapes of any
        # rank led by (n_q, n_docs): f32[7,64] and f32[7,64,...] both
        # count as corpus-sized.
        pat = re.compile(rf"{n_q}x{n_docs}x|\[{n_q},{n_docs}[\],]")

        f_mat = jax.jit(lambda qq: search(index, qq, k=5, end_to_end=True,
                                          backend=backend)[:2])
        f_str = jax.jit(lambda qq: topk_search(index, qq, k=5,
                                               backend=backend,
                                               chunk_docs=16))
        mat_low = f_mat.lower(q).as_text()
        assert pat.search(mat_low), \
            "oracle changed: materializing path lost the full matrix"
        lowered = f_str.lower(q)
        str_low, str_comp = lowered.as_text(), lowered.compile().as_text()
        assert not pat.search(str_low) and not pat.search(str_comp), \
            "streaming path materialized an (n_q, n_docs) tensor"


class TestShardedServing:
    def test_sharded_identical_to_single_device(self):
        """2-device candidates mesh: the shard_map merge returns the
        same ids and bitwise scores as the single-device streaming AND
        materializing paths, on both backends and layouts (odd doc
        counts exercise the shard padding)."""
        code = _CORPUS_SRC + """
import jax, jax.numpy as jnp, numpy as np
from repro.serve.retrieval import maxsim_scores, topk_search
from repro.sharding import axis_rules, serve_rules
from repro.launch.mesh import make_serve_mesh

mesh = make_serve_mesh()
assert mesh.shape["model"] == 2, mesh
masked = _pruned_corpus(0, 37, 20, 8, empty=(0, 17))
q, qm = _queries(1, 6, 5, 8)
for layout in (masked, masked.pack()):
    for be in ("reference", "fused"):
        full = maxsim_scores(layout, q, qm, backend=be)
        ref_s, ref_i = jax.lax.top_k(full, 7)
        with axis_rules(serve_rules(mesh)):
            sh_i, sh_s = topk_search(layout, q, k=7, q_masks=qm,
                                     backend=be)
            jit_i, jit_s = jax.jit(lambda qq: topk_search(
                layout, qq, k=7, q_masks=qm, backend=be))(q)
        np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(sh_i))
        np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(sh_s))
        np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(jit_i))
        np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(jit_s))
print("SHARDED_TOPK_OK")
"""
        assert "SHARDED_TOPK_OK" in _run_subprocess(code)

    def test_k_exceeds_docs_in_shard(self):
        """k larger than a shard's local doc count (and a doc count that
        doesn't divide the shard count): the -inf/sentinel padding keeps
        the merge exact."""
        code = _CORPUS_SRC + """
import jax, jax.numpy as jnp, numpy as np
from repro.serve.retrieval import maxsim_scores, topk_search
from repro.sharding import axis_rules, serve_rules
from repro.launch.mesh import make_serve_mesh

mesh = make_serve_mesh()
masked = _pruned_corpus(3, 3, 12, 8, empty=(1,))   # 3 docs over 2 shards
q, qm = _queries(4, 5, 4, 8)
for layout in (masked, masked.pack()):
    for be in ("reference", "fused"):
        full = maxsim_scores(layout, q, qm, backend=be)
        ref_s, ref_i = jax.lax.top_k(full, 3)      # k=3 > 2 docs/shard
        with axis_rules(serve_rules(mesh)):
            sh_i, sh_s = topk_search(layout, q, k=3, q_masks=qm,
                                     backend=be)
        np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(sh_i))
        np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(sh_s))
        # k > TOTAL docs: both paths truncate to the real docs — the
        # sharded merge must not leak -inf/sentinel shard pads.
        lo_i, lo_s = topk_search(layout, q, k=5, q_masks=qm, backend=be)
        with axis_rules(serve_rules(mesh)):
            sp_i, sp_s = topk_search(layout, q, k=5, q_masks=qm,
                                     backend=be)
        assert lo_i.shape == sp_i.shape == (q.shape[0], 3), sp_i.shape
        assert int(np.asarray(sp_i).max()) < 3     # no sentinel ids
        np.testing.assert_array_equal(np.asarray(lo_i), np.asarray(sp_i))
        np.testing.assert_array_equal(np.asarray(lo_s), np.asarray(sp_s))
print("SHARD_EDGE_OK")
"""
        assert "SHARD_EDGE_OK" in _run_subprocess(code)

    def test_sharded_server_roundtrip(self):
        """RetrievalServer built under serve_rules(mesh) serves the
        sharded streaming path and matches the unsharded server."""
        code = _CORPUS_SRC + """
import jax, numpy as np
from repro.serve.retrieval import RetrievalServer
from repro.sharding import axis_rules, serve_rules
from repro.launch.mesh import make_serve_mesh

mesh = make_serve_mesh()
packed = _pruned_corpus(5, 26, 16, 8, empty=(7,)).pack()
q, _ = _queries(6, 4, 4, 8)
i_ref, s_ref = RetrievalServer(packed, k=5, n_first=64).query_batch(q)
with axis_rules(serve_rules(mesh)):
    i_sh, s_sh = RetrievalServer(packed, k=5, n_first=64).query_batch(q)
np.testing.assert_array_equal(i_ref, i_sh)
np.testing.assert_array_equal(s_ref, s_sh)
# One server crossing mesh contexts must re-trace, not silently reuse
# the closure traced under the other context (cache key carries the
# mesh): same (n_q, l) shape -> two cached closures, identical results.
srv = RetrievalServer(packed, k=5, n_first=64)
i_a, s_a = srv.query_batch(q)                    # traced unsharded
with axis_rules(serve_rules(mesh)):
    i_b, s_b = srv.query_batch(q)                # must trace sharded
assert len(srv._search) == 2, len(srv._search)
np.testing.assert_array_equal(i_a, i_b)
np.testing.assert_array_equal(s_a, s_b)
print("SHARDED_SERVER_OK")
"""
        assert "SHARDED_SERVER_OK" in _run_subprocess(code)


class TestShardedGlobalKeepMasks:
    def test_sharded_merge_identical(self):
        """The bitwise-selection merge over the data axis reproduces the
        single-host argsort cut bit for bit: assorted keep fractions, a
        doc count that doesn't divide the shard count, and a tie-heavy
        corpus (duplicated docs => duplicated merge keys)."""
        code = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import sampling, voronoi
from repro.launch.mesh import auto_mesh
from repro.sharding import axis_rules

mesh = auto_mesh((2, 1), ("data", "model"))
k = jax.random.PRNGKey(0)
n_docs, m, dim = 5, 12, 8
d = jax.random.normal(k, (n_docs, m, dim)) * 0.5
n_real = jax.random.randint(jax.random.fold_in(k, 1), (n_docs,), 1, m + 1)
masks = jnp.arange(m)[None] < n_real[:, None]
S = sampling.sample_sphere(jax.random.PRNGKey(2), 600, dim)
ranks, errs, _ = voronoi.pruning_order_batch(d, masks, S)
d2 = jnp.concatenate([d, d[:2]], 0)       # tie-heavy: duplicate docs
m2 = jnp.concatenate([masks, masks[:2]], 0)
r2, e2, _ = voronoi.pruning_order_batch(d2, m2, S)
for rk, er, dm in ((ranks, errs, masks), (r2, e2, m2)):
    for frac in (0.05, 0.3, 0.7, 0.95, 1.0):
        ref = voronoi.global_keep_masks(rk, er, dm, frac)
        with axis_rules({"__mesh__": mesh}):
            sh = voronoi.global_keep_masks(rk, er, dm, frac)
            ex = voronoi.global_keep_masks(rk, er, dm, frac, sharded=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(sh))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ex))
print("GLOBAL_MERGE_OK")
"""
        assert "GLOBAL_MERGE_OK" in _run_subprocess(code)

    def test_sharded_true_requires_mesh(self):
        from repro.core import voronoi
        ranks = jnp.zeros((4, 6), jnp.int32)
        errs = jnp.zeros((4, 6), jnp.float32)
        masks = jnp.ones((4, 6), bool)
        with pytest.raises(ValueError, match="__mesh__"):
            voronoi.global_keep_masks(ranks, errs, masks, 0.5, sharded=True)


class TestShardingPlumbing:
    def test_constrain_noop_outside_mesh(self):
        with axis_rules({"candidates": ("model",)}):
            out = constrain(jnp.ones((4,)), "candidates")
        np.testing.assert_array_equal(np.asarray(out), np.ones((4,)))

    def test_constrain_reraises_real_errors(self):
        """Only the outside-mesh RuntimeError is swallowed; a wrong-rank
        spec (genuine sharding bug) must surface."""
        mesh = auto_mesh((1,), ("model",))
        with mesh:
            with axis_rules({"candidates": ("model",)}):
                with pytest.raises(ValueError):
                    constrain(jnp.ones((4,)), "candidates", None)

    def test_serve_rules_and_mesh(self):
        r = serve_rules()
        assert r["candidates"] == ("model",) and r["batch"] is None
        assert "__mesh__" not in r
        mesh = make_serve_mesh()
        r = serve_rules(mesh)
        assert r["__mesh__"] is mesh
        with axis_rules(r):
            got_mesh, axes, n = mesh_axes_for("candidates")
        if len(jax.devices()) > 1:
            assert got_mesh is mesh and axes == ("model",) and n > 1
        else:                       # 1-device host: sharding is a no-op
            assert got_mesh is None and n == 1

    def test_mesh_axes_for_replicated_and_bare(self):
        assert mesh_axes_for("candidates") == (None, (), 1)
        mesh = make_serve_mesh()
        with axis_rules({"__mesh__": mesh, "candidates": None}):
            assert mesh_axes_for("candidates") == (None, (), 1)
