"""The 4-device grid differential cases (multi-host bucket placement).

One implementation, two consumers:

* ``tests/test_placement.py`` runs each check in a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the
  tests/test_sharded_exec.py pattern);
* ``scripts/smoke.sh`` (and CI through it) runs :func:`main` directly
  under the same forced device count, so the grid merge tier is
  exercised on every push without paying the pytest subprocess spawn
  twice.

Every check asserts **bitwise** parity — ids and fp scores — against
the single-host dense oracle: the grid merge tree keeps a superset of
the true top-k at every tier and all merges share the ``(-score, id)``
total order, so any divergence is a real placement bug, not tolerance
noise.
"""

from __future__ import annotations

import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

GRID_HOSTS, GRID_CAND = 2, 2
N_DEVICES = GRID_HOSTS * GRID_CAND


def _require_devices():
    n = len(jax.devices())
    assert n >= N_DEVICES, (
        f"grid cases need {N_DEVICES} devices (run under XLA_FLAGS="
        f"--xla_force_host_platform_device_count={N_DEVICES}); got {n}")


def _pruned_corpus(seed, n_docs, m, dim, empty=()):
    """Ragged masks, bernoulli keep, selected docs pruned to zero tokens
    (the empty-after-prune edge) — the shared corpus builder of
    tests/test_sharded_serving.py."""
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
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (n_q, l, dim))
    qn = jax.random.randint(jax.random.fold_in(k, 1), (n_q,), 1, l + 1)
    return q, jnp.arange(l)[None, :] < qn[:, None]


def _grid_mesh():
    from repro.launch.mesh import make_serve_mesh
    mesh = make_serve_mesh(hosts=GRID_HOSTS)
    assert mesh.shape["hosts"] == GRID_HOSTS
    assert mesh.shape["candidates"] == GRID_CAND
    return mesh


def _placements(n_buckets):
    """The placement sweep: the bytes-balanced default, everything
    pinned to each single group (one group serves pure sentinels), and
    round-robin."""
    from repro.sharding import PlacementPlan
    return [("default", None),
            ("pinned_g0", PlacementPlan.pinned(n_buckets, GRID_HOSTS, 0)),
            ("pinned_g1", PlacementPlan.pinned(n_buckets, GRID_HOSTS, 1)),
            ("round_robin", PlacementPlan.round_robin(n_buckets,
                                                      GRID_HOSTS))]


def check_topk_parity():
    """topk_search under the grid: backend x layout x placement sweep,
    bit-identical to lax.top_k over the materialized oracle — including
    empty-after-prune docs, k > docs-in-group, and k > total docs."""
    _require_devices()
    from repro.serve.retrieval import maxsim_scores, topk_search
    from repro.sharding import axis_rules, serve_rules

    mesh = _grid_mesh()
    masked = _pruned_corpus(0, 37, 20, 8, empty=(0, 17))
    q, qm = _queries(1, 6, 5, 8)
    for layout, lname in ((masked, "masked"), (masked.pack(), "packed")):
        n_buckets = len(getattr(layout, "buckets", [None]))
        for be in ("reference", "fused"):
            full = maxsim_scores(layout, q, qm, backend=be)
            ref_s, ref_i = jax.lax.top_k(full, 7)
            for pname, plc in _placements(n_buckets):
                with axis_rules(serve_rules(mesh, placement=plc)):
                    sh_i, sh_s = topk_search(layout, q, k=7, q_masks=qm,
                                             backend=be)
                ctx = f"{lname}/{be}/{pname}"
                np.testing.assert_array_equal(np.asarray(ref_i),
                                              np.asarray(sh_i), ctx)
                np.testing.assert_array_equal(np.asarray(ref_s),
                                              np.asarray(sh_s), ctx)
    # k > docs-in-group AND k > total docs: 3 docs over a 2x2 grid, one
    # pruned empty — sentinel pads must never displace or leak.
    tiny = _pruned_corpus(3, 3, 12, 8, empty=(1,))
    q2, qm2 = _queries(4, 5, 4, 8)
    for layout in (tiny, tiny.pack()):
        n_buckets = len(getattr(layout, "buckets", [None]))
        for be in ("reference", "fused"):
            for k in (2, 3, 5):             # k < / = / > total docs
                lo_i, lo_s = topk_search(layout, q2, k=k, q_masks=qm2,
                                         backend=be)
                for pname, plc in _placements(n_buckets):
                    with axis_rules(serve_rules(mesh, placement=plc)):
                        sp_i, sp_s = topk_search(layout, q2, k=k,
                                                 q_masks=qm2, backend=be)
                    assert sp_i.shape == lo_i.shape == (q2.shape[0],
                                                        min(k, 3))
                    sp = np.asarray(sp_i)
                    assert sp.min() >= 0 and sp.max() < 3, \
                        f"sentinel id leaked: {pname} k={k}"
                    np.testing.assert_array_equal(np.asarray(lo_i), sp)
                    np.testing.assert_array_equal(np.asarray(lo_s),
                                                  np.asarray(sp_s))
    # The grid exchange is a cross-program hop: tracing it under an
    # enclosing jit must refuse loudly, not silently mis-serve.
    with axis_rules(serve_rules(mesh)):
        try:
            jax.jit(lambda qq: topk_search(masked, qq, k=3))(q)
        except ValueError as e:
            assert "cross-group" in str(e), e
        else:
            raise AssertionError("grid topk_search traced under jit")
    print("GRID_TOPK_PARITY_OK")


def check_prune_parity():
    """Sharded corpus pruning over the data axis: prune_corpus and
    pruning_order_bucketed under shard_map are bit-identical to the
    single-host path (ranks, errs, keep masks), pow2 and fixed-width
    bucket granularities, shortlist backend included."""
    _require_devices()
    from repro.core import pruning_pipeline, sampling
    from repro.launch.mesh import auto_mesh
    from repro.sharding import axis_rules

    mesh = auto_mesh((N_DEVICES, 1), ("data", "model"))
    k = jax.random.PRNGKey(0)
    n_docs, m, dim = 13, 24, 8
    d = jax.random.normal(k, (n_docs, m, dim)) * 0.5
    n_real = jax.random.randint(jax.random.fold_in(k, 1), (n_docs,),
                                1, m + 1)
    masks = jnp.arange(m)[None] < n_real[:, None]
    S = sampling.sample_sphere(jax.random.PRNGKey(2), 400, dim)

    for frac in (0.3, 0.7):
        ref = pruning_pipeline.prune_corpus(d, masks, S, frac)
        with axis_rules({"__mesh__": mesh}):
            auto = pruning_pipeline.prune_corpus(d, masks, S, frac)
            forced = pruning_pipeline.prune_corpus(d, masks, S, frac,
                                                   sharded=True)
        for got in (auto, forced):
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for kw in (dict(backend="shortlist_topk"), dict(granularity=6)):
        ref = pruning_pipeline.pruning_order_bucketed(d, masks, S, **kw)
        with axis_rules({"__mesh__": mesh}):
            got = pruning_pipeline.pruning_order_bucketed(d, masks, S, **kw)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the §4.2 global merge alone, 4-way data-sharded vs the single-host
    # argsort cut (prune_corpus covers the composition; this isolates it)
    from repro.core import voronoi
    ranks, errs, _ = voronoi.pruning_order_batch(d, masks, S)
    for frac in (0.1, 0.5, 0.9):
        ref = voronoi.global_keep_masks(ranks, errs, masks, frac)
        with axis_rules({"__mesh__": mesh}):
            got = voronoi.global_keep_masks(ranks, errs, masks, frac,
                                            sharded=True)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    print("GRID_PRUNE_PARITY_OK")


def check_hlo_clean():
    """The compiled per-group program (what one host group runs) holds
    no (n_q, n_docs) or full-corpus tensor; the materializing oracle
    provably does (the twin assertion keeping the pattern honest)."""
    _require_devices()
    from repro.serve.retrieval import TokenIndex, search, topk_search_group
    from repro.sharding import axis_rules, serve_rules

    mesh = _grid_mesh()
    n_q, n_docs, m, l, dim = 7, 64, 16, 6, 8
    key = jax.random.PRNGKey(0)
    index = TokenIndex.build(jax.random.normal(key, (n_docs, m, dim)),
                             jnp.ones((n_docs, m), bool))
    packed = index.pack()
    q = jax.random.normal(jax.random.fold_in(key, 1), (n_q, l, dim))
    # StableHLO spelling (7x64x...) and compiled-HLO shapes of any rank
    # led by (n_q, n_docs) both count as corpus-sized; the dense corpus
    # (n_docs, m, dim) itself may appear — it is the index, not a score
    # temp.
    pat = re.compile(rf"{n_q}x{n_docs}x|\[{n_q},{n_docs}[\],]")
    mat = jax.jit(lambda qq: search(index, qq, k=5, end_to_end=True)[:2])
    assert pat.search(mat.lower(q).as_text()), \
        "oracle changed: materializing path lost the full matrix"
    with axis_rules(serve_rules(mesh)):
        for layout in (index, packed):
            for g in range(GRID_HOSTS):
                f = jax.jit(lambda qq, g=g, lay=layout: topk_search_group(
                    lay, qq, group=g, k=5))
                low = f.lower(q)
                txt, comp = low.as_text(), low.compile().as_text()
                assert not pat.search(txt) and not pat.search(comp), \
                    f"group {g} program materialized an (n_q, n_docs) " \
                    f"tensor"
    print("GRID_HLO_OK")


def check_artifact_roundtrip():
    """The multi-host artifact lifecycle: save with a placement, each
    host group loads ONLY its buckets (sub-manifest + per-group body),
    group programs serve their tier from the partial load, and the
    cross-group merge of those tiers is bit-identical to serving the
    fully reassembled index — and to the dense oracle.  Also pins the
    grid-aware RetrievalServer (closure cache keys carry the grid)."""
    _require_devices()
    from repro.serve import index_io
    from repro.serve.retrieval import (RetrievalServer, _merge_topk,
                                       maxsim_scores, topk_search,
                                       topk_search_group)
    from repro.sharding import PlacementPlan, axis_rules, serve_rules

    mesh = _grid_mesh()
    packed = _pruned_corpus(5, 26, 16, 8, empty=(7,)).pack()
    q, qm = _queries(6, 4, 4, 8)
    full = maxsim_scores(packed, q, qm)
    ref_s, ref_i = jax.lax.top_k(full, 5)
    plc = PlacementPlan.for_index(packed, GRID_HOSTS)
    with tempfile.TemporaryDirectory() as td:
        index_io.save_index(td, packed, placement=plc)
        assert index_io.has_index(td)
        assert index_io.load_placement(td) == plc
        # full reassembly serves identically
        whole = index_io.load_index(td)
        with axis_rules(serve_rules(mesh, placement=plc)):
            i_w, s_w = topk_search(whole, q, k=5, q_masks=qm)
        np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(i_w))
        np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(s_w))
        # multi-controller path: each group restores only its buckets
        # and serves its own tier; the k-wide exchange merges them.
        vals, ids = [], []
        for g in range(plc.n_groups):
            sub = index_io.load_index(td, group=g)
            assert len(sub.buckets) == len(plc.buckets_of(g))
            assert sub.n_docs == packed.n_docs      # global ids intact
            # a partial view with no explicit placement must refuse —
            # the derived default would scatter this group's buckets
            # and silently drop documents
            if len(sub.buckets) < len(packed.buckets):
                with axis_rules(serve_rules(mesh)):
                    try:
                        topk_search(sub, q, k=5, q_masks=qm)
                    except ValueError as e:
                        assert "partial" in str(e), e
                    else:
                        raise AssertionError(
                            "partial group view served without an "
                            "explicit placement")
            sub_plan = PlacementPlan(
                n_groups=plc.n_groups,
                groups=(g,) * len(sub.buckets))
            with axis_rules(serve_rules(mesh)):
                gi, gv = topk_search_group(sub, q, group=g, k=5,
                                           q_masks=qm, placement=sub_plan)
            ids.append(np.asarray(gi))
            vals.append(np.asarray(gv))
        mi, mv = _merge_topk(jnp.asarray(np.concatenate(vals, 1)),
                             jnp.asarray(np.concatenate(ids, 1)), 5)
        np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(mi))
        np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(mv))
    # grid-aware server: same results as the unsharded server, and a
    # server crossing rule contexts re-traces instead of reusing the
    # wrong closure (the cache key carries the grid + placement).
    srv = RetrievalServer(packed, k=5, n_first=packed.n_docs)
    i_a, s_a = srv.query_batch(q)
    with axis_rules(serve_rules(mesh, placement=plc)):
        i_b, s_b = srv.query_batch(q)
    assert len(srv._search) == 2, len(srv._search)
    np.testing.assert_array_equal(i_a, i_b)
    np.testing.assert_array_equal(s_a, s_b)
    print("GRID_ARTIFACT_OK")


def _restricted_oracle(packed, surviving_buckets, q, qm, k):
    """The single-host streaming oracle over ONLY ``surviving_buckets``
    — what a degraded grid answer must equal bitwise (doc ids stay
    corpus-global, so no renumbering)."""
    from repro.serve.retrieval import _bucket_view, topk_search
    sub = _bucket_view(packed, tuple(surviving_buckets))
    if sub is None:
        return (np.zeros((q.shape[0], 0), np.int32),
                np.zeros((q.shape[0], 0), np.float32))
    i, v = topk_search(sub, q, k=k, q_masks=qm)
    return np.asarray(i), np.asarray(v)


def check_fault_tolerance():
    """The fault-injection differential gate (topk_search level).

    * replicas=2: killing ANY single host group — at dispatch, mid-
      exchange, or via a deadline-overrunning delay — yields top-k ids
      and fp scores bit-identical to the no-failure oracle (failover to
      the surviving replica, dedupe merge), at coverage 1.0.
    * replicas=1: the degraded result equals the single-host oracle
      restricted to the surviving buckets, reports coverage < 1, and
      contains no NaNs/sentinels — including k > docs-in-surviving-
      groups and every-replica-lost (empty result, coverage 0).
    * no monitor: injected faults propagate loudly (GroupFailure), and
      a replicated plan with ALL groups live dedupes to oracle parity.
    """
    _require_devices()
    from repro.serve import health
    from repro.serve.retrieval import maxsim_scores, topk_search
    from repro.sharding import PlacementPlan, axis_rules, serve_rules
    from repro.sharding.placement import bucket_weights

    mesh = _grid_mesh()
    packed = _pruned_corpus(7, 29, 18, 8, empty=(3, 11)).pack()
    q, qm = _queries(8, 5, 4, 8)
    k = 6
    full = maxsim_scores(packed, q, qm)
    ref_s, ref_i = jax.lax.top_k(full, k)
    ref_i, ref_s = np.asarray(ref_i), np.asarray(ref_s)
    n_buckets = len(packed.buckets)
    weights = bucket_weights(packed)

    # --- replicated plan: unmonitored (all replicas answer; the root
    # merge must dedupe doc ids, not double-count them) ---------------
    plc2 = PlacementPlan.for_index(packed, GRID_HOSTS, replicas=2)
    assert plc2.replicas == 2
    with axis_rules(serve_rules(mesh, placement=plc2)):
        i2, v2 = topk_search(packed, q, k=k, q_masks=qm)
    np.testing.assert_array_equal(ref_i, np.asarray(i2), "replicated dedupe")
    np.testing.assert_array_equal(ref_s, np.asarray(v2))

    # --- replicas=2, kill any single group: bit-identical failover ---
    fault_mixes = [
        ("dispatch", lambda g: health.kill_group(g)),
        ("mid-exchange", lambda g: health.kill_group(g, when="after")),
        ("deadline", lambda g: health.delay_group(g, 0.5)),
    ]
    for fname, mk in fault_mixes:
        for lost in range(GRID_HOSTS):
            mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                                      backoff_base=0.001,
                                      exchange_timeout=(
                                          0.05 if fname == "deadline"
                                          else None))
            faults = health.FaultPlan([mk(lost)])
            with axis_rules(serve_rules(mesh, placement=plc2)):
                res = topk_search(packed, q, k=k, q_masks=qm,
                                  monitor=mon, faults=faults)
                ctx = f"replicas=2/{fname}/lost={lost}"
                assert res.coverage == 1.0, (ctx, res.coverage)
                np.testing.assert_array_equal(ref_i, np.asarray(res[0]), ctx)
                np.testing.assert_array_equal(ref_s, np.asarray(res[1]), ctx)
                assert mon.demoted == frozenset({lost}), (ctx, mon.demoted)
                # next query: the demoted group is never dispatched
                # again (no strikes left to absorb) — still exact.
                res2 = topk_search(packed, q, k=k, q_masks=qm,
                                   monitor=mon, faults=faults)
                np.testing.assert_array_equal(ref_i, np.asarray(res2[0]))
                assert res2.coverage == 1.0

    # --- replicas=1: degraded coverage == restricted oracle ----------
    plc1 = PlacementPlan.for_index(packed, GRID_HOSTS)
    for lost in range(GRID_HOSTS):
        surviving = [b for b in range(n_buckets)
                     if plc1.group_of(b) != lost]
        assert surviving and len(surviving) < n_buckets
        for kk in (k, 10 * packed.n_docs):   # incl. k > surviving docs
            mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                                      backoff_base=0.001)
            faults = health.FaultPlan([health.kill_group(lost)])
            with axis_rules(serve_rules(mesh, placement=plc1)):
                res = topk_search(packed, q, k=kk, q_masks=qm,
                                  monitor=mon, faults=faults)
            oi, ov = _restricted_oracle(packed, surviving, q, qm, kk)
            ctx = f"replicas=1/lost={lost}/k={kk}"
            want_cov = sum(weights[b] for b in surviving) / sum(weights)
            assert abs(res.coverage - want_cov) < 1e-12, ctx
            assert res.coverage < 1.0, ctx
            np.testing.assert_array_equal(oi, np.asarray(res[0]), ctx)
            np.testing.assert_array_equal(ov, np.asarray(res[1]), ctx)
            got_v = np.asarray(res[1])
            assert np.isfinite(got_v).all(), f"NaN/inf leaked: {ctx}"
            ids = np.asarray(res[0])
            assert ids.min() >= 0 and ids.max() < packed.n_docs, ctx

    # --- every replica lost: empty result, coverage 0, no raise ------
    mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                              backoff_base=0.001)
    faults = health.FaultPlan([health.kill_group(g)
                               for g in range(GRID_HOSTS)])
    with axis_rules(serve_rules(mesh, placement=plc1)):
        res = topk_search(packed, q, k=k, q_masks=qm,
                          monitor=mon, faults=faults)
    assert res.coverage == 0.0 and res[0].shape == (q.shape[0], 0)
    assert mon.demoted == frozenset(range(GRID_HOSTS))

    # --- no monitor: faults surface loudly, never a silent stall -----
    faults = health.FaultPlan([health.kill_group(0)])
    with axis_rules(serve_rules(mesh, placement=plc1)):
        try:
            topk_search(packed, q, k=k, q_masks=qm, faults=faults)
        except health.GroupFailure:
            pass
        else:
            raise AssertionError("unmonitored fault did not propagate")
    print("GRID_FAULT_TOLERANCE_OK")


def check_failover_server():
    """RetrievalServer-level failover: the on_group_loss policies, the
    coverage contract on query_batch, and the group-fails-between-
    warmup-and-query scenario (closure/program caches must not serve a
    stale group assignment)."""
    _require_devices()
    from repro.serve import health
    from repro.serve.retrieval import (RetrievalServer, maxsim_scores,
                                       TopKResult)
    from repro.sharding import PlacementPlan, axis_rules, serve_rules

    mesh = _grid_mesh()
    packed = _pruned_corpus(9, 23, 16, 8, empty=(2,)).pack()
    q, qm = _queries(10, 4, 4, 8)
    k = 5
    full = maxsim_scores(packed, q, None)
    ref_s, ref_i = jax.lax.top_k(full, k)
    ref_i, ref_s = np.asarray(ref_i), np.asarray(ref_s)
    n_buckets = len(packed.buckets)
    plc2 = PlacementPlan.for_index(packed, GRID_HOSTS, replicas=2)
    plc1 = PlacementPlan.for_index(packed, GRID_HOSTS)

    # --- group dies between warmup and query: an external health
    # signal demotes it; the warmed server must not dispatch the stale
    # group program (replicas=2 -> still bit-identical) ---------------
    for lost in range(GRID_HOSTS):
        mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                                  backoff_base=0.001)
        srv = RetrievalServer(packed, k=k, n_first=packed.n_docs,
                              monitor=mon)
        with axis_rules(serve_rules(mesh, placement=plc2)):
            warm = srv.query_batch(q)              # healthy warmup
            assert warm.coverage == 1.0
            np.testing.assert_array_equal(ref_i, warm[0])
            mon.demote(lost)                       # dies before query 2
            res = srv.query_batch(q)
            assert res.coverage == 1.0
            np.testing.assert_array_equal(ref_i, res[0],
                                          f"stale program? lost={lost}")
            np.testing.assert_array_equal(ref_s, res[1])

    # --- same scenario via an injected fault at round 1 (the fault
    # fires between the warmup round and the serving round) -----------
    mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                              backoff_base=0.001)
    faults = health.FaultPlan([health.kill_group(0, from_round=1)])
    srv = RetrievalServer(packed, k=k, n_first=packed.n_docs,
                          monitor=mon, faults=faults)
    with axis_rules(serve_rules(mesh, placement=plc2)):
        warm = srv.query_batch(q)                  # round 0: healthy
        assert warm.coverage == 1.0 and not mon.demoted
        res = srv.query_batch(q)                   # round 1: kill fires
        assert res.coverage == 1.0 and mon.demoted == frozenset({0})
        np.testing.assert_array_equal(ref_i, res[0])
        np.testing.assert_array_equal(ref_s, res[1])

    # --- on_group_loss="degrade" (default): coverage surfaces --------
    mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                              backoff_base=0.001)
    faults = health.FaultPlan([health.kill_group(1)])
    srv = RetrievalServer(packed, k=k, n_first=packed.n_docs,
                          monitor=mon, faults=faults)
    with axis_rules(serve_rules(mesh, placement=plc1)):
        res = srv.query_batch(q)
    assert isinstance(res, TopKResult) and res.coverage < 1.0
    surviving = [b for b in range(n_buckets) if plc1.group_of(b) != 1]
    oi, ov = _restricted_oracle(packed, surviving, q, None, k)
    np.testing.assert_array_equal(oi, res[0])
    np.testing.assert_array_equal(ov, res[1])

    # --- on_group_loss="rebalance": lost buckets re-place over the
    # survivors and THIS query re-answers at full coverage ------------
    mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                              backoff_base=0.001)
    faults = health.FaultPlan([health.kill_group(1)])
    srv = RetrievalServer(packed, k=k, n_first=packed.n_docs,
                          monitor=mon, on_group_loss="rebalance",
                          faults=faults)
    with axis_rules(serve_rules(mesh, placement=plc1)):
        res = srv.query_batch(q)
        assert res.coverage == 1.0, res.coverage
        np.testing.assert_array_equal(ref_i, res[0])
        np.testing.assert_array_equal(ref_s, res[1])
        assert srv._placement is not None
        assert all(1 not in srv._placement.replicas_of(b)
                   for b in range(n_buckets))
        # steady state on the rebalanced plan
        res2 = srv.query_batch(q)
        assert res2.coverage == 1.0
        np.testing.assert_array_equal(ref_i, res2[0])

    # --- on_group_loss="fail": refuse degraded results ---------------
    mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                              backoff_base=0.001)
    faults = health.FaultPlan([health.kill_group(1)])
    srv = RetrievalServer(packed, k=k, n_first=packed.n_docs,
                          monitor=mon, on_group_loss="fail", faults=faults)
    with axis_rules(serve_rules(mesh, placement=plc1)):
        try:
            srv.query_batch(q)
        except health.DegradedCoverage:
            pass
        else:
            raise AssertionError("fail policy returned a degraded result")
    print("GRID_FAILOVER_SERVER_OK")


def check_routed_serving():
    """Candidate routing under the grid: the router runs BEFORE group
    dispatch, so a fully-pruned host group is *not consulted* — no
    group program, no exchange, no fault bookkeeping — rather than
    "failed".

    * bounded route: bit-identical ids AND fp scores against the
      single-host exhaustive oracle across the placement sweep,
      replicated plans included (each selected bucket is served by the
      first replica of its chain, so the merge sees unique ids);
    * nprobe route with concentrated queries: consults a strict subset
      of host groups (``groups_consulted`` recorded), and killing a
      never-consulted group is invisible — same answer, no demotion.
    """
    _require_devices()
    from repro.core import metrics
    from repro.serve import health
    from repro.serve.retrieval import TokenIndex, topk_search
    from repro.serve.routing import RoutingIndex
    from repro.sharding import PlacementPlan, axis_rules, serve_rules

    mesh = _grid_mesh()
    # clustered corpus with kept-token count tied to the cluster, so
    # capacity buckets carry content structure the router can exploit
    # (the shape of tests/test_routing.py's _clustered_corpus)
    rng = np.random.default_rng(12)
    n_docs, m, dim, n_clusters = 64, 32, 8, 4
    centers = rng.normal(size=(n_clusters, dim))
    centers /= np.linalg.norm(centers, axis=-1, keepdims=True)
    lab = np.repeat(np.arange(n_clusters), n_docs // n_clusters)
    emb = centers[lab][:, None, :] + 0.08 * rng.normal(
        size=(n_docs, m, dim))
    emb = (emb / np.linalg.norm(emb, axis=-1, keepdims=True)).astype(
        np.float32)
    kept = np.maximum(((lab + 1) * m) // n_clusters, 1)
    keep = np.arange(m)[None, :] < kept[:, None]
    packed = TokenIndex.build(
        jnp.asarray(emb), jnp.ones((n_docs, m), bool)).with_keep(
            jnp.asarray(keep)).pack()
    n_buckets = len(packed.buckets)
    assert n_buckets >= 3, [b.cap for b in packed.buckets]
    routing = RoutingIndex.build(packed, n_centroids=4)
    rng2 = np.random.default_rng(13)
    q = centers[1][None, None, :] + 0.05 * rng2.normal(size=(6, 5, dim))
    q = jnp.asarray((q / np.linalg.norm(q, axis=-1,
                                        keepdims=True)).astype(np.float32))
    k = 5
    oi, ov = topk_search(packed, q, k=k)
    oi, ov = np.asarray(oi), np.asarray(ov)

    # --- bounded: bitwise oracle parity across the placement sweep ---
    plans = _placements(n_buckets) + [
        ("replicas2", PlacementPlan.for_index(packed, GRID_HOSTS,
                                              replicas=2))]
    for pname, plc in plans:
        st = {}
        with axis_rules(serve_rules(mesh, placement=plc)):
            bi, bv = topk_search(packed, q, k=k, route="bounded",
                                 routing=routing, route_stats=st)
        ctx = f"bounded/{pname}"
        np.testing.assert_array_equal(oi, np.asarray(bi), ctx)
        np.testing.assert_array_equal(ov, np.asarray(bv), ctx)
        assert 0 < st["groups_consulted"] <= st["n_groups"], (ctx, st)
        assert st["n_groups"] == GRID_HOSTS, (ctx, st)

    # --- nprobe: strict subset of buckets AND host groups ------------
    plc = PlacementPlan.round_robin(n_buckets, GRID_HOSTS)
    st = {}
    with axis_rules(serve_rules(mesh, placement=plc)):
        ri, rv = topk_search(packed, q, k=k, route="nprobe",
                             routing=routing, n_probe=1, route_stats=st)
    assert st["buckets_scored"] < st["n_buckets"], st
    assert st["groups_consulted"] < st["n_groups"], st
    rec = metrics.recall_at_k(np.asarray(ri), oi)
    assert rec >= 0.99, (rec, st)

    # --- a never-consulted group is invisible to fault handling ------
    immune = 0
    for g in range(GRID_HOSTS):
        mon = health.FleetMonitor(GRID_HOSTS, retries=0, max_strikes=1,
                                  backoff_base=0.001)
        faults = health.FaultPlan([health.kill_group(g)])
        with axis_rules(serve_rules(mesh, placement=plc)):
            res = topk_search(packed, q, k=k, route="nprobe",
                              routing=routing, n_probe=1,
                              monitor=mon, faults=faults)
        if not mon.demoted:
            immune += 1
            np.testing.assert_array_equal(np.asarray(ri),
                                          np.asarray(res[0]),
                                          f"immune group {g}")
            assert res.coverage == 1.0
    assert immune == GRID_HOSTS - st["groups_consulted"], \
        (immune, st["groups_consulted"])
    print("GRID_ROUTED_SERVING_OK")


def check_serve_loop():
    """The concurrent micro-batched serving loop over a grid-placed
    server: client threads stream single queries through a
    ``ServeLoop`` whose dispatcher (inheriting the thread-local serve
    rules captured at construction) micro-batches them into pow2
    shapes and fans the per-group programs out over the monitored
    concurrent exchange — with one epoch swap landing mid-run.  Every
    streamed answer must be bitwise the single-host dense oracle, at
    full coverage, attributed to a scheduled generation."""
    _require_devices()
    import threading

    from repro.serve import health
    from repro.serve.loop import ServeLoop
    from repro.serve.retrieval import RetrievalServer, maxsim_scores
    from repro.sharding import PlacementPlan, axis_rules, serve_rules

    mesh = _grid_mesh()
    packed = _pruned_corpus(40, 24, 16, 8, empty=(3,)).pack()
    q, _ = _queries(41, 8, 4, 8)
    q = np.asarray(q, np.float32)
    k = 5
    full = maxsim_scores(packed, jnp.asarray(q), None)
    ref_s, ref_i = jax.lax.top_k(full, k)
    ref_i, ref_s = np.asarray(ref_i), np.asarray(ref_s)
    plc = PlacementPlan.for_index(packed, GRID_HOSTS)
    mon = health.FleetMonitor(GRID_HOSTS)
    srv = RetrievalServer(packed, k=k, n_first=packed.n_docs,
                          monitor=mon)
    results = [None] * len(q)
    errors = []

    def client(lo, hi):
        try:
            for i in range(lo, hi):
                results[i] = sl.query(q[i])
        except Exception as e:
            errors.append(e)

    with axis_rules(serve_rules(mesh, placement=plc)):
        with ServeLoop(srv, flush_ms=2.0, max_batch=4) as sl:
            threads = [threading.Thread(target=client,
                                        args=(2 * c, 2 * c + 2))
                       for c in range(4)]
            for t in threads:
                t.start()
            # Mid-run epoch swap (same corpus, new generation): the
            # write gate drains in-flight flushes, so answers stay
            # bitwise identical and only epoch_key moves.
            sl.swap_index(packed)
            for t in threads:
                t.join()
    assert not errors, errors[0]
    gens = set()
    for i, r in enumerate(results):
        assert r.coverage == 1.0, (i, r.coverage)
        gens.add(r.epoch_key[0])
        np.testing.assert_array_equal(ref_i[i], np.asarray(r.top_idx),
                                      f"query {i}")
        np.testing.assert_array_equal(ref_s[i], np.asarray(r.top_scores),
                                      f"query {i}")
    assert gens <= {0, 1} and gens, gens
    assert mon.demoted == frozenset()
    print("GRID_SERVE_LOOP_OK")


def main():
    _require_devices()
    check_topk_parity()
    check_prune_parity()
    check_hlo_clean()
    check_artifact_roundtrip()
    check_fault_tolerance()
    check_failover_server()
    check_routed_serving()
    check_serve_loop()
    print("GRID_CASES_OK")


if __name__ == "__main__":
    main()
