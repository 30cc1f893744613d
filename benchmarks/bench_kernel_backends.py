"""Backend-dispatch perf record: reference vs kernel hot paths.

Measures the two hot paths the dispatch seam (repro.core.backend)
routes — iterative Voronoi pruning (both paths + the bucketed corpus
pipeline + the ragged-corpus comparison) and MaxSim serving —
plus the packed-vs-masked index-layout comparison (same pruned corpus
served from the dense masked `TokenIndex` and from the compacted
`PackedIndex`, throughput AND measured bytes) and the serving-dataflow
comparison (materialize-then-top-k vs the streaming per-chunk merge of
``topk_search``: q/s, peak live temp bytes of the compiled
executables, and whether the streaming HLO holds any corpus-sized
score tensor), prints the harness CSV lines, and APPENDS a timestamped
entry to ``BENCH_kernel_backends.json`` at the repo root so the perf
trajectory of the kernel-backed paths accumulates PR over PR instead
of being overwritten.

Shapes are CPU-scaled but chosen so the *serving* comparison is
meaningful off-TPU too: at the rerank shape the reference einsum's 4-D
(n_q, n_docs, l, m) tensor exceeds LLC and the chunked kernel path wins
outright even through the Pallas interpreter.  The pruning comparison
off-TPU prices the interpreter per scan step for the shortlist_topk
path, so its docs/sec is a lower bound (the TPU numbers are the ones
that matter); the reference and bucketed figures are real either way.

``python -m benchmarks.bench_kernel_backends --check`` re-reads the
last trajectory entry and fails (exit 1) if the bucketed pruning
pipeline on the platform's path regressed below the same run's
reference-path docs/sec, if packed serving
dropped below the masked path, if streaming serving dropped below the
materializing path (or its results diverged), if a corpus-sized
(n_q, n_docs) score tensor reappeared in the compiled streaming
serving HLO, or if fault-tolerant serving regressed (replicated
failover after one lost host group no longer bit-identical to the
no-failure oracle, or degraded unreplicated serving not reporting
0 < coverage < 1), or if live-mutation serving regressed (post-crash
recovery no longer bit-identical to the pre-crash live view, or
compaction no longer bit-identical to the delta-log view it folds), or
if the concurrent serving loop regressed (loop q/s below
one-query-per-call serial serving under the mixed query+upsert
workload, a loop answer diverging bitwise from the serial oracle, or
the per-epoch result cache replaying a stale answer across a delta-log
update or epoch swap), or
if routed serving regressed (nprobe recall@k < 0.99 against the
exhaustive oracle, routed q/s below the exhaustive sweep, the router
scoring every bucket, or the bounded route losing bit-exactness), or
if the residual codec regressed (residual-4 bytes_stored above 0.25x
the dense fp32 corpus, top-k recall@k < 0.99 vs the fp32 packed
oracle, a bucket-sized fp32 tensor appearing in the fused residual
program, the eagerly-decoding reference twin losing the pattern the
fused gate greps for, or fused residual q/s falling below the int8
path — gated strictly on TPU, floored at 0.5x under the interpreter)
— the smoke scripts/smoke.sh runs after recording.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.bench_speedup import (PRUNING_BACKENDS, run_pruning_backends,
                                      run_ragged_pruning)
from repro.serve.retrieval import (TokenIndex, maxsim_scores, search,
                                   topk_search)

OUT_PATH = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir,
                                        "BENCH_kernel_backends.json"))

# Rerank benchmark shape: 4-D reference tensor = 32*256*32*128 f32
# = 134 MB — large enough that materializing it is the bottleneck.
RERANK = dict(n_q=32, n_docs=256, m=128, l=32, dim=128, block_docs=64)


def run_rerank_backends(n_q=32, n_docs=256, m=128, l=32, dim=128,
                        block_docs=64):
    """Rerank latency (queries/sec) for reference einsum vs chunked
    kernel serving at the benchmark shape, plus the autotuned-blocks
    row (block_docs/block_q resolved by repro.core.tuning).
    Returns {backend: q_per_s}."""
    k = jax.random.PRNGKey(0)
    d = jax.random.normal(k, (n_docs, m, dim))
    masks = jnp.ones((n_docs, m), bool)
    q = jax.random.normal(jax.random.fold_in(k, 1), (n_q, l, dim))
    index = TokenIndex.build(d, masks)

    f_ref = jax.jit(lambda qq: maxsim_scores(index, qq,
                                             backend="reference"))
    f_fus = jax.jit(lambda qq: maxsim_scores(index, qq, backend="fused",
                                             block_docs=block_docs,
                                             block_q=n_q))
    f_tuned = jax.jit(lambda qq: maxsim_scores(index, qq, backend="fused"))
    t_ref, _ = common.timeit(lambda: f_ref(q), repeat=2)
    t_fus, _ = common.timeit(lambda: f_fus(q), repeat=2)
    t_tuned, _ = common.timeit(lambda: f_tuned(q), repeat=2)
    return {
        "reference": n_q / t_ref,
        "fused": n_q / t_fus,
        "fused_autotuned": n_q / t_tuned,
        "speedup_fused_over_reference": t_ref / t_fus,
        "shape": dict(n_q=n_q, n_docs=n_docs, m=m, l=l, dim=dim,
                      block_docs=block_docs),
    }


def run_packed_serving(n_q=32, n_docs=256, m=128, l=32, dim=128,
                       keep_fraction=0.5):
    """Index-layout comparison at the rerank shape: the same pruned
    corpus served from the dense masked index vs the packed artifact
    (platform-default backend on both), plus the measured-bytes story.
    The keep mask holds exactly ``keep_fraction * m`` scattered tokens
    per doc, so the packed capacity buckets are tight and the layout
    effect isolates from pruning-quality noise.
    Returns {masked|packed: q_per_s, bytes..., shape}."""
    k = jax.random.PRNGKey(0)
    d = jax.random.normal(k, (n_docs, m, dim))
    masks = jnp.ones((n_docs, m), bool)
    q = jax.random.normal(jax.random.fold_in(k, 1), (n_q, l, dim))
    n_keep = int(m * keep_fraction)
    rng = np.random.default_rng(0)
    keep = np.zeros((n_docs, m), bool)
    for i in range(n_docs):                 # scattered, exact-count keeps
        keep[i, rng.choice(m, n_keep, replace=False)] = True
    masked = TokenIndex.build(d, masks).with_keep(jnp.asarray(keep))
    packed = masked.pack()

    f_mask = jax.jit(lambda qq: maxsim_scores(masked, qq))
    f_pack = jax.jit(lambda qq: maxsim_scores(packed, qq))
    t_mask, _ = common.timeit(lambda: f_mask(q), repeat=2)
    t_pack, _ = common.timeit(lambda: f_pack(q), repeat=2)
    pst = packed.storage()
    return {
        "masked": n_q / t_mask,
        "packed": n_q / t_pack,
        "speedup_packed_over_masked": t_mask / t_pack,
        "bytes_masked_resident": n_docs * m * dim * 4,
        "bytes_packed_stored": pst["bytes_stored"],
        "bytes_ratio_packed_over_dense":
            pst["bytes_stored"] / (n_docs * m * dim * 4),
        "shape": dict(n_q=n_q, n_docs=n_docs, m=m, l=l, dim=dim,
                      keep_fraction=keep_fraction),
    }


def _peak_temp_bytes(compiled):
    """Peak live temp bytes of a compiled executable (buffer-assignment
    view; None when the backend exposes no memory analysis)."""
    try:
        ma = compiled.memory_analysis()
        return None if ma is None else int(ma.temp_size_in_bytes)
    except Exception:
        return None


def run_streaming_serving(n_q=32, n_docs=256, m=128, l=32, dim=128, k=10):
    """Serving-dataflow comparison at the bench shape: the
    materialize-then-top-k path (full (n_q, n_docs) score matrix +
    global lax.top_k) vs the streaming per-chunk merge (topk_search).
    Records q/s, peak live temp bytes of the compiled executables, a
    results-identical sanity bit, and whether the streaming compiled
    HLO is free of any corpus-sized (n_q, n_docs) tensor — the gate
    ``--check`` enforces so the dense matrix cannot silently
    reappear on the serving path.
    Returns {materializing|streaming: q_per_s, ...}."""
    key = jax.random.PRNGKey(0)
    d = jax.random.normal(key, (n_docs, m, dim))
    masks = jnp.ones((n_docs, m), bool)
    q = jax.random.normal(jax.random.fold_in(key, 1), (n_q, l, dim))
    index = TokenIndex.build(d, masks)

    f_mat = jax.jit(lambda qq: search(index, qq, k=k, end_to_end=True)[:2])
    f_str = jax.jit(lambda qq: topk_search(index, qq, k=k))
    i_mat, s_mat = (np.asarray(x) for x in f_mat(q))
    i_str, s_str = (np.asarray(x) for x in f_str(q))
    identical = bool((i_mat == i_str).all() and (s_mat == s_str).all())

    t_mat, _ = common.timeit(lambda: f_mat(q), repeat=2)
    t_str, _ = common.timeit(lambda: f_str(q), repeat=2)
    # One AOT lower+compile per path, shared by the HLO gate and the
    # memory analysis (AOT compiles don't share the jit cache; don't pay
    # them twice).  Pattern covers the StableHLO spelling (32x256x...)
    # and compiled-HLO shapes of ANY rank led by (n_q, n_docs) —
    # f32[32,256] and f32[32,256,...] both count as corpus-sized.
    lowered = f_str.lower(q)
    comp_str = lowered.compile()
    comp_mat = f_mat.lower(q).compile()
    pat = re.compile(rf"{n_q}x{n_docs}x|\[{n_q},{n_docs}[\],]")
    hlo_clean = not (pat.search(lowered.as_text())
                     or pat.search(comp_str.as_text()))
    return {
        "materializing": n_q / t_mat,
        "streaming": n_q / t_str,
        "speedup_streaming_over_materializing": t_mat / t_str,
        "peak_temp_bytes_materializing": _peak_temp_bytes(comp_mat),
        "peak_temp_bytes_streaming": _peak_temp_bytes(comp_str),
        "results_identical": identical,
        "hlo_no_corpus_matrix": bool(hlo_clean),
        "shape": dict(n_q=n_q, n_docs=n_docs, m=m, l=l, dim=dim, k=k),
    }


# Grid-placement bench shape: small enough that the 2x2 forced-device
# subprocess stays fast, big enough for several capacity buckets.
GRID = dict(n_q=8, n_docs=96, m=32, l=8, dim=32, k=10, hosts=2)


# What a forced-device child result is: the host CPU standing in for a
# 4-chip grid.  Never a device number.
CPU_REHEARSAL = "cpu rehearsal: 4 forced host-platform devices"


def _cpu_grid_child(flag: str, shape: dict) -> dict:
    """Run this module's ``flag`` worker in a child on 4 forced CPU
    devices (``JAX_PLATFORMS=cpu``: the child never reaches for an
    accelerator the parent may hold) and return its result, labelled
    as a CPU rehearsal."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(os.path.join(os.path.dirname(__file__),
                                      os.pardir))]
        + [os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        os.pardir, "src"))]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_kernel_backends",
         flag, json.dumps(shape)],
        env=env, capture_output=True, text=True, timeout=540)
    if out.returncode != 0:
        raise RuntimeError(f"{flag} bench child failed:\n"
                           f"{out.stderr[-2000:]}")
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return dict(json.loads(line[len("RESULT "):]), platform=CPU_REHEARSAL)


def _grid_hosts(shape: dict) -> int:
    from repro.launch.mesh import default_serve_hosts
    hosts = int(shape["hosts"])
    n_dev = len(jax.devices())
    if n_dev < 2 * hosts or default_serve_hosts() < 2:
        raise RuntimeError(f"the grid needs {2 * hosts} devices, "
                           f"{n_dev} found")
    return hosts


def run_grid_serving(**shape):
    """Multi-host placement comparison (DESIGN_BACKENDS.md §Placement):
    the flat single-tier candidates layout vs the 2-D grid (buckets
    pinned to host groups, per-group merge + cross-group candidate
    exchange), on a 4-device forced CPU grid in a child process (a
    rehearsal, labelled so).  Records q/s for both layouts, the wire
    bytes the candidate exchange moves (total and the cross-host share
    — the number placement exists to shrink), a results-identical bit
    against the single-device oracle, and whether the compiled
    per-group HLO is free of corpus-sized tensors.  ``--check`` gates
    the parity and HLO bits."""
    return _cpu_grid_child("--grid-worker", GRID | shape)


def _grid_worker(shape: dict) -> dict:
    """Runs inside the forced-device child; returns its result."""
    import re as re_

    from repro.launch.mesh import make_serve_mesh
    from repro.serve.retrieval import topk_search, topk_search_group
    from repro.sharding import PlacementPlan, axis_rules, serve_rules

    hosts = _grid_hosts(shape)
    n_dev = len(jax.devices())
    n_q, n_docs, m, l, dim, k = (shape[x] for x in
                                 ("n_q", "n_docs", "m", "l", "dim", "k"))
    key = jax.random.PRNGKey(0)
    d = jax.random.normal(key, (n_docs, m, dim))
    n_real = jax.random.randint(jax.random.fold_in(key, 1), (n_docs,),
                                1, m + 1)
    masks = jnp.arange(m)[None] < n_real[:, None]
    keep = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.6,
                                (n_docs, m))
    packed = TokenIndex.build(d, masks).with_keep(keep).pack()
    q = jax.random.normal(jax.random.fold_in(key, 3), (n_q, l, dim))

    i_ref, s_ref = topk_search(packed, q, k=k)      # single-device oracle
    flat_mesh = make_serve_mesh()                   # every device, one tier
    grid_mesh = make_serve_mesh(hosts=hosts)
    placement = PlacementPlan.for_index(packed, hosts)
    n_cand = grid_mesh.shape["candidates"]

    with axis_rules(serve_rules(flat_mesh)):
        f_flat = jax.jit(lambda qq: topk_search(packed, qq, k=k))
        i_f, s_f = f_flat(q)
        t_flat, _ = common.timeit(lambda: f_flat(q), repeat=2)
    with axis_rules(serve_rules(grid_mesh, placement=placement)):
        i_g, s_g = topk_search(packed, q, k=k)      # eager: x-group hop
        t_grid, _ = common.timeit(lambda: topk_search(packed, q, k=k),
                                  repeat=2)
        pat = re_.compile(rf"{n_q}x{n_docs}x|\[{n_q},{n_docs}[\],]")
        hlo_clean = True
        for g in range(hosts):
            low = jax.jit(lambda qq, g=g: topk_search_group(
                packed, qq, group=g, k=k)).lower(q)
            if pat.search(low.as_text()) or pat.search(
                    low.compile().as_text()):
                hlo_clean = False
    identical = all(
        bool((np.asarray(a) == np.asarray(b)).all())
        for a, b in ((i_ref, i_f), (s_ref, s_f), (i_ref, i_g),
                     (s_ref, s_g)))

    # Candidate-exchange wire bytes per query batch (8 = f32 score +
    # i32 id).  Flat: every shard all-gathers its (n_q, k) block to
    # every other; with shards laid out in host rows of n_cand, the
    # receives from outside a device's row cross hosts.  Grid: tier-1
    # gathers stay inside a group (intra-host); tier-2 ships one
    # (n_q, k) block per group — the only cross-host bytes.
    cand = n_q * k * 8
    bytes_flat = n_dev * (n_dev - 1) * cand
    bytes_flat_cross = n_dev * (n_dev - n_cand) * cand
    bytes_grid = hosts * n_cand * (n_cand - 1) * cand + hosts * cand
    bytes_grid_cross = hosts * cand
    return {
        "flat": n_q / t_flat,
        "grid": n_q / t_grid,
        "speedup_grid_over_flat": t_flat / t_grid,
        "results_identical": identical,
        "hlo_no_corpus_matrix": bool(hlo_clean),
        "exchange_bytes": {"flat": bytes_flat, "grid": bytes_grid,
                           "flat_cross_host": bytes_flat_cross,
                           "grid_cross_host": bytes_grid_cross},
        "cross_host_bytes_ratio_flat_over_grid":
            bytes_flat_cross / bytes_grid_cross,
        "shape": dict(shape, n_devices=n_dev, n_cand=n_cand),
    }


def run_fault_tolerance(**shape):
    """Fault-tolerant replicated serving (DESIGN_BACKENDS.md §Failure
    semantics) on the 4-device forced grid: q/s of replicas=2 monitored
    serving at full health, the failover-recovery latency (wall time of
    the FIRST query after a host group is demoted — failover routing +
    the replica programs' compile), post-failover steady-state q/s, a
    parity bit (failover results bit-identical to the no-failure
    oracle), and the degraded coverage fraction an unreplicated plan
    reports after the same loss, on the same forced CPU grid.
    ``--check`` gates the parity bit and the degraded-coverage
    contract."""
    return _cpu_grid_child("--fault-worker", GRID | shape)


def _fault_worker(shape: dict) -> dict:
    """Runs inside the forced-device child; returns its result."""
    from repro.launch.mesh import make_serve_mesh
    from repro.serve import health
    from repro.sharding import PlacementPlan, axis_rules, serve_rules

    hosts = _grid_hosts(shape)
    n_dev = len(jax.devices())
    n_q, n_docs, m, l, dim, k = (shape[x] for x in
                                 ("n_q", "n_docs", "m", "l", "dim", "k"))
    key = jax.random.PRNGKey(0)
    d = jax.random.normal(key, (n_docs, m, dim))
    n_real = jax.random.randint(jax.random.fold_in(key, 1), (n_docs,),
                                1, m + 1)
    masks = jnp.arange(m)[None] < n_real[:, None]
    keep = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.6,
                                (n_docs, m))
    packed = TokenIndex.build(d, masks).with_keep(keep).pack()
    q = jax.random.normal(jax.random.fold_in(key, 3), (n_q, l, dim))

    i_ref, s_ref = topk_search(packed, q, k=k)      # no-failure oracle
    grid_mesh = make_serve_mesh(hosts=hosts)
    lost = 0

    # Replicated plan: full-coverage failover after losing any group.
    plc2 = PlacementPlan.for_index(packed, hosts, replicas=2)
    mon2 = health.FleetMonitor(hosts)
    with axis_rules(serve_rules(grid_mesh, placement=plc2)):
        run2 = lambda: topk_search(packed, q, k=k, monitor=mon2)
        i_h, s_h = run2()                           # warm primary programs
        t_rep, _ = common.timeit(run2, repeat=2)
        mon2.demote(lost)
        t0 = time.perf_counter()
        i_f, s_f = run2()       # first query after loss: reroute + compile
        t_failover = time.perf_counter() - t0
        t_post, _ = common.timeit(run2, repeat=2)
    same = lambda a, b: bool((np.asarray(a) == np.asarray(b)).all())
    parity_healthy = same(i_ref, i_h) and same(s_ref, s_h)
    parity_failover = same(i_ref, i_f) and same(s_ref, s_f)

    # Unreplicated plan: the same loss degrades with explicit coverage.
    plc1 = PlacementPlan.for_index(packed, hosts)
    mon1 = health.FleetMonitor(hosts)
    mon1.demote(lost)
    with axis_rules(serve_rules(grid_mesh, placement=plc1)):
        out = topk_search(packed, q, k=k, monitor=mon1)
    coverage = float(getattr(out, "coverage", 1.0))

    return {
        "replicated": n_q / t_rep,
        "post_failover": n_q / t_post,
        "failover_recovery_s": t_failover,
        "parity_healthy": parity_healthy,
        "parity_failover_identical": parity_failover,
        "degraded_coverage": coverage,
        "degraded_scores_finite": bool(
            np.isfinite(np.asarray(out.top_scores)).all()),
        "shape": dict(shape, n_devices=n_dev, replicas=2,
                      lost_group=lost),
    }


# Routed-serving bench shape: big enough that bucket scoring dominates
# the router's centroid pass + host-side selection (the point of the
# comparison), clustered so the capacity buckets carry content
# structure (kept-token count tied to the cluster) — the regime
# Voronoi-as-IVF routing exists for.  Queries concentrate on one
# cluster, the realistic serving mix for a routed index.
ROUTED = dict(n_q=16, n_docs=1024, m=32, l=8, dim=32, k=10,
              n_clusters=4, n_centroids=4, n_probe=1)


def run_routed_serving(**shape):
    """Candidate-routing comparison (DESIGN_BACKENDS.md §Candidate
    routing): the exhaustive streaming sweep vs the routed modes on the
    SAME eager ``topk_search`` machinery (routed selection is
    host-side, so neither side gets an enclosing jit).  Records q/s for
    exhaustive / nprobe / bounded, recall@k of the nprobe route against
    the exhaustive oracle, the fraction of buckets each routed mode
    scored, and a bit-exactness bit for the bounded route.  ``--check``
    gates recall >= 0.99, routed q/s >= exhaustive q/s, fraction < 1,
    and bounded exactness."""
    from repro.core import metrics
    from repro.serve.routing import RoutingIndex

    shape = ROUTED | shape
    n_q, n_docs, m, l, dim, k = (shape[x] for x in
                                 ("n_q", "n_docs", "m", "l", "dim", "k"))
    n_clusters, n_centroids = shape["n_clusters"], shape["n_centroids"]
    rng = np.random.default_rng(0)
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
    routing = RoutingIndex.build(packed, n_centroids=n_centroids)
    q = centers[1][None, None, :] + 0.05 * rng.normal(size=(n_q, l, dim))
    q = jnp.asarray((q / np.linalg.norm(q, axis=-1,
                                        keepdims=True)).astype(np.float32))

    def run(**kw):
        return jax.block_until_ready(topk_search(packed, q, k=k, **kw))

    i_ex, s_ex = run()                          # warm + oracle
    st_np, st_bd = {}, {}
    i_np, s_np = run(route="nprobe", routing=routing,
                     n_probe=shape["n_probe"], route_stats=st_np)
    i_bd, s_bd = run(route="bounded", routing=routing, route_stats=st_bd)
    t_ex, _ = common.timeit(lambda: run(), repeat=2)
    t_np, _ = common.timeit(
        lambda: run(route="nprobe", routing=routing,
                    n_probe=shape["n_probe"]), repeat=2)
    t_bd, _ = common.timeit(
        lambda: run(route="bounded", routing=routing), repeat=2)
    same = lambda a, b: bool((np.asarray(a) == np.asarray(b)).all())
    return {
        "exhaustive": n_q / t_ex,
        "nprobe": n_q / t_np,
        "bounded": n_q / t_bd,
        "speedup_nprobe_over_exhaustive": t_ex / t_np,
        "speedup_bounded_over_exhaustive": t_ex / t_bd,
        "recall_nprobe": metrics.recall_at_k(np.asarray(i_np),
                                             np.asarray(i_ex)),
        "bounded_exact": same(i_ex, i_bd) and same(s_ex, s_bd),
        "fraction_buckets_nprobe": st_np["fraction"],
        "fraction_buckets_bounded": st_bd["fraction"],
        "n_buckets": st_np["n_buckets"],
        "shape": dict(shape),
    }


# Mutation bench shape: small enough that the per-round retrace of the
# delta-view program stays cheap on CPU, big enough for several
# capacity buckets per leaf.
MUTATION = dict(n_q=8, n_docs=192, m=24, l=8, dim=32, k=10,
                rounds=5, upsert_batch=12)


def run_mutation_serving(**shape):
    """Live-mutation serving bench (DESIGN_BACKENDS.md §Mutation):
    sustained q/s under a mixed query+upsert workload (every round
    appends one durable upsert batch through the WAL, reloads the
    delta log, and serves a query batch against the refreshed live
    view — WAL fsyncs, delta packing, and the view retrace are all
    inside the clock), steady-state q/s on the final view, the
    recovery latency after a simulated crash (an uncommitted compact
    intent on the WAL — exactly what a kill at the compact-intent
    point leaves — timed through ``recover`` + state reload + first
    query), and two parity bits ``--check`` gates: recovery must
    re-serve the pre-crash live view bit-identically, and compaction
    must fold the delta log into an epoch that serves bit-identically
    to the view it replaces."""
    import tempfile

    from repro.serve import index_io, mutation
    from repro.serve.index import PackedIndex

    shape = MUTATION | shape
    n_q, n_docs, m, l, dim, k = (shape[x] for x in
                                 ("n_q", "n_docs", "m", "l", "dim", "k"))
    rounds, batch = shape["rounds"], shape["upsert_batch"]
    rng = np.random.default_rng(0)
    embs = rng.normal(size=(n_docs, m, dim)).astype(np.float32)
    masks = rng.random((n_docs, m)) < 0.85
    q = rng.normal(size=(n_q, l, dim)).astype(np.float32)

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "artifact")
        index_io.save_index(path, PackedIndex.pack(embs, masks))

        n_queries = 0
        i_live = s_live = None
        t0 = time.perf_counter()
        for r in range(rounds):
            ids = list(range(n_docs + r * batch, n_docs + (r + 1) * batch))
            d = rng.normal(size=(batch, m, dim)).astype(np.float32)
            dm = rng.random((batch, m)) < 0.85
            mutation.append_upsert(path, d, dm, ids)
            log = mutation.load_state(path)
            i_live, s_live = topk_search(log.base, q, k=k,
                                         mutation=log.view())
            jax.block_until_ready(s_live)
            n_queries += n_q
        t_mixed = time.perf_counter() - t0
        oracle = (np.asarray(i_live), np.asarray(s_live))

        log = mutation.load_state(path)
        view = log.view()
        f_view = lambda: jax.block_until_ready(
            topk_search(log.base, q, k=k, mutation=view))
        t_view, _ = common.timeit(f_view, repeat=2)

        # Simulated crash: an intent on the WAL with no commit is the
        # durable state a kill at compact-intent leaves behind.
        records = index_io.wal_read(path)
        index_io.wal_append(path, {"op": "compact",
                                   "seq": mutation._next_seq(records),
                                   "epoch": log.epoch + 1,
                                   "deltas": []})
        t0 = time.perf_counter()
        index_io.recover(path)
        rlog = mutation.load_state(path)
        i_rec, s_rec = topk_search(rlog.base, q, k=k, mutation=rlog.view())
        jax.block_until_ready(s_rec)
        t_recover = time.perf_counter() - t0
        same = lambda a, b: bool((np.asarray(a) == np.asarray(b)).all())
        parity_recover = (same(oracle[0], i_rec)
                          and same(oracle[1], s_rec))

        new_index = mutation.Compactor(path).run()
        reloaded = index_io.load_index(path)
        i_c, s_c = topk_search(reloaded, q, k=k)
        parity_compact = (new_index is not None
                          and same(oracle[0], i_c)
                          and same(oracle[1], s_c))
        orphans = index_io.list_orphans(path)

    return {
        "mixed_q_per_s": n_queries / t_mixed,
        "view_q_per_s": n_q / t_view,
        "upserts_per_s": rounds * batch / t_mixed,
        "recovery_s": t_recover,
        "recovery_parity_identical": parity_recover,
        "post_compact_parity_identical": parity_compact,
        "orphans_after_recovery": len(orphans),
        "epoch_after_compact": int(reloaded.epoch),
        "shape": dict(shape),
    }


# Serve-loop bench shape: the mutation shape plus loop knobs, with the
# query stream passing over the query set twice per round so the
# result cache's steady-state hit path is priced alongside cold
# serves (second pass replays bitwise from the cache).
# max_batch matches n_clients so a full client wave flushes on count,
# not on the flush_ms deadline — with blocking clients the in-flight
# row count can never exceed the client count, so a larger max_batch
# would just tax every flush with the full deadline wait.
SERVE_LOOP = dict(n_q=8, n_docs=192, m=24, l=8, dim=32, k=10,
                  rounds=3, upsert_batch=12, passes=2,
                  flush_ms=2.0, max_batch=4, n_clients=4)


def run_serve_loop(**shape):
    """Concurrent micro-batched serving-loop bench (DESIGN_BACKENDS.md
    §Serving loop): the SAME mixed query+upsert workload served two
    ways — one query per ``query_batch`` call, serially (the
    pre-serve-loop deployment), and streamed by ``n_clients`` threads
    through a :class:`repro.serve.loop.ServeLoop` (micro-batched pow2
    flushes + per-epoch result cache).  The delta-log updates between
    rounds churn both servers (cache invalidation + closure retraces)
    but stay off the clock — the WAL fsync is identical work on both
    sides and run_mutation_serving already prices it — and each
    round's closures are compiled untimed before the clock starts
    (with distinct warmup queries, so the result cache stays cold for
    the timed stream): both q/s numbers are steady-state serving of
    the mutating view.  Records sustained q/s for
    both, client-observed p50/p99 latency, and the two bits
    ``--check`` gates: every loop answer bitwise equal to the serial
    answer for its (round, query), and the result cache invalidating
    on a delta-log update AND an epoch swap (a tombstoned top doc must
    vanish from the post-mutation answer, never replay from cache)."""
    import tempfile
    import threading

    from repro.serve import index_io, mutation
    from repro.serve.index import PackedIndex
    from repro.serve.loop import ServeLoop
    from repro.serve.retrieval import RetrievalServer

    shape = SERVE_LOOP | shape
    n_q, n_docs, m, l, dim, k = (shape[x] for x in
                                 ("n_q", "n_docs", "m", "l", "dim", "k"))
    rounds, batch = shape["rounds"], shape["upsert_batch"]
    rng = np.random.default_rng(0)
    embs = rng.normal(size=(n_docs, m, dim)).astype(np.float32)
    masks = rng.random((n_docs, m)) < 0.85
    q = rng.normal(size=(n_q, l, dim)).astype(np.float32)
    # Warmup queries: same shapes as the stream but distinct values, so
    # compiling each round's closures (the view arrays grow every
    # round) never seeds the result cache for the timed queries.  The
    # serial path only ever sees batch 1; the loop pads flushes to
    # pow2, so it warms every pow2 batch size a flush can produce.
    q_warm = rng.normal(size=(n_q, l, dim)).astype(np.float32)
    warm_sizes = [1]
    while warm_sizes[-1] < min(n_q, shape["max_batch"]):
        warm_sizes.append(warm_sizes[-1] * 2)
    # One delta schedule, replayed identically against both artifacts.
    deltas = []
    for r in range(rounds):
        ids = list(range(n_docs + r * batch, n_docs + (r + 1) * batch))
        deltas.append((rng.normal(size=(batch, m, dim)).astype(np.float32),
                       rng.random((batch, m)) < 0.85, ids))
    stream = list(range(n_q)) * shape["passes"]

    def fresh(tmp, name):
        path = os.path.join(tmp, name)
        index_io.save_index(path, PackedIndex.pack(embs, masks))
        return path, RetrievalServer(index_io.load_index(path), k=k,
                                     n_first=0x7FFFFFFF)

    with tempfile.TemporaryDirectory() as tmp:
        # -- serial oracle + baseline: one query per call -------------
        path_a, srv_a = fresh(tmp, "serial")
        oracle = {}
        t_serial = 0.0
        for r in range(rounds):
            # Mutations churn the view (cache invalidation + closure
            # retraces) but stay off the clock: the WAL fsync is
            # identical work on both sides — run_mutation_serving
            # prices it — and its jitter would bury the serving
            # difference these q/s numbers exist to measure.
            mutation.append_upsert(path_a, *deltas[r])
            log = mutation.load_state(path_a)
            srv_a.apply_mutation(log.view())
            # untimed: compile this round's batch-1 closure
            jax.block_until_ready(
                srv_a.query_batch(jnp.asarray(q_warm[:1])).top_scores)
            t0 = time.perf_counter()
            for i in stream:
                out = srv_a.query_batch(jnp.asarray(q[i:i + 1]))
                oracle[(r, i)] = (np.asarray(out.top_idx[0]),
                                  np.asarray(out.top_scores[0]))
                jax.block_until_ready(out.top_scores)
            t_serial += time.perf_counter() - t0
        serial_qps = rounds * len(stream) / t_serial

        # -- the loop: n_clients threads stream the same workload -----
        path_b, srv_b = fresh(tmp, "loop")
        answers = {}
        ans_lock = threading.Lock()
        errors = []

        def client(sl, r, idxs):
            try:
                for i in idxs:
                    res = sl.query(q[i])
                    with ans_lock:
                        answers[(r, i)] = res
            except Exception as e:   # surfaced after join
                errors.append(e)

        with ServeLoop(srv_b, flush_ms=shape["flush_ms"],
                       max_batch=shape["max_batch"]) as sl:
            t_loop = 0.0
            for r in range(rounds):
                # same untimed mutation as the serial phase above
                mutation.append_upsert(path_b, *deltas[r])
                sl.apply_mutation(mutation.load_state(path_b).view())
                # untimed: compile every pow2 flush shape a round can
                # produce, straight through the server (bypassing the
                # loop keeps the warm stalls out of the latency stats).
                for nb in warm_sizes:
                    jax.block_until_ready(
                        srv_b.query_batch(
                            jnp.asarray(q_warm[:nb])).top_scores)
                t0 = time.perf_counter()
                n_cl = shape["n_clients"]
                threads = [threading.Thread(
                    target=client, args=(sl, r, stream[c::n_cl]))
                    for c in range(n_cl)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                t_loop += time.perf_counter() - t0
            if errors:
                raise errors[0]
            loop_qps = rounds * len(stream) / t_loop
            snap = sl.stats.snapshot()
            # Parity: repeat visits to a (round, query) may replay
            # from the cache or co-batch with the first visit — either
            # way every answer must be bitwise the serial answer.
            parity = all(
                np.array_equal(np.asarray(res.top_idx), oracle[key][0])
                and np.array_equal(np.asarray(res.top_scores),
                                   oracle[key][1])
                for key, res in answers.items())

            # -- invalidation: delta-log update then epoch swap -------
            base_misses = sl.stats.snapshot()["cache_misses"]
            a1 = sl.query(q[0])
            a2 = sl.query(q[0])                  # cache replay
            hit_ok = (sl.stats.snapshot()["cache_misses"] == base_misses
                      and np.array_equal(np.asarray(a1.top_idx),
                                         np.asarray(a2.top_idx)))
            top = int(np.asarray(a1.top_idx)[0])
            mutation.append_delete(path_b, [top])
            sl.apply_mutation(mutation.load_state(path_b).view())
            a3 = sl.query(q[0])                  # new key -> cold serve
            mut_ok = (sl.stats.snapshot()["cache_misses"]
                      == base_misses + 1
                      and top not in np.asarray(a3.top_idx))
            mutation.Compactor(path_b).run()
            sl.swap_index(index_io.load_index(path_b))
            a4 = sl.query(q[0])                  # new epoch -> cold serve
            swap_ok = (sl.stats.snapshot()["cache_misses"]
                       == base_misses + 2
                       and top not in np.asarray(a4.top_idx))
            invalidation = bool(hit_ok and mut_ok and swap_ok)

    return {
        "serial_q_per_s": serial_qps,
        "loop_q_per_s": loop_qps,
        "speedup_loop_over_serial": loop_qps / serial_qps,
        "parity_identical": bool(parity),
        "cache_invalidation_ok": invalidation,
        "p50_latency_s": snap["p50_latency_s"],
        "p99_latency_s": snap["p99_latency_s"],
        "cache_hits": snap["cache_hits"],
        "cache_misses": snap["cache_misses"],
        "flushes": snap["flushes"],
        "padded_rows": snap["padded_rows"],
        "shape": dict(shape),
    }


# Residual-codec bench shape: a planted-relevance corpus (each query's
# topic tokens appear verbatim in its k relevant docs) so the top-k
# margin is structural — much larger than quantization noise — and
# recall@k isolates CODEC loss from corpus ambiguity.  vocab/dim sized
# so Lloyd's codebooks have real cluster structure to find.
RESIDUAL = dict(n_q=8, n_docs=256, m=16, l=8, dim=64, k=10,
                vocab=64, noise=0.05, keep_p=0.8, n_centroids=64)


def _planted_corpus(shape, seed=0):
    """(embs, masks, keep, queries, oracle_relevant) with each query's
    topic tokens PINNED into the keep mask of its relevant docs —
    random pruning must not delete the evidence the recall metric
    scores."""
    rng = np.random.default_rng(seed)
    n_q, n_docs, m, l, dim, k = (shape[x] for x in
                                 ("n_q", "n_docs", "m", "l", "dim", "k"))
    vocab = rng.standard_normal((shape["vocab"], dim)).astype(np.float32)
    vocab /= np.linalg.norm(vocab, axis=-1, keepdims=True)
    topics = np.stack([rng.choice(shape["vocab"], l, replace=False)
                       for _ in range(n_q)])
    embs = vocab[rng.integers(0, shape["vocab"], (n_docs, m))]
    pin = np.zeros((n_docs, m), bool)
    doc = 0
    for qi in range(n_q):
        for _ in range(k):           # k docs carry query qi's full topic
            pos = rng.permutation(m)[:l]
            embs[doc, pos] = vocab[topics[qi]]
            pin[doc, pos] = True
            doc += 1
    embs = embs + shape["noise"] * rng.standard_normal(
        embs.shape).astype(np.float32)
    embs /= np.linalg.norm(embs, axis=-1, keepdims=True)
    keep = (rng.random((n_docs, m)) < shape["keep_p"]) | pin
    keep[:, 0] = True
    q = vocab[topics] + shape["noise"] * rng.standard_normal(
        (n_q, l, dim)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    return (jnp.asarray(embs), jnp.ones((n_docs, m), bool),
            jnp.asarray(keep), jnp.asarray(q))


def run_residual_compression(**shape):
    """Compression-codec comparison (DESIGN_BACKENDS.md §Compression
    codecs): the SAME pruned planted-relevance corpus packed fp32 /
    int8 / residual-4 / residual-2, served through the fused streaming
    path.  Records bytes_stored (and the ratio vs the dense fp32
    corpus), top-k recall@k against the fp32 packed oracle, and q/s per
    codec — plus the HLO twin bits ``--check`` gates: the fused
    residual program must hold NO bucket-sized fp32 tensor (decode
    stays tile-local in VMEM), while the eagerly-decoding reference
    program — the materializing twin — MUST hold one."""
    from repro.core import metrics
    from repro.serve.retrieval import TokenIndex

    shape = RESIDUAL | shape
    n_q, k, dim = shape["n_q"], shape["k"], shape["dim"]
    embs, masks, keep, q = _planted_corpus(shape)
    masked = TokenIndex.build(embs, masks).with_keep(keep)
    dense_bytes = masked.pack().storage()["bytes_dense_fp32"]

    modes = {
        "fp32": dict(compression="none"),
        "int8": dict(compression="int8"),
        "residual4": dict(compression="residual", residual_bits=4,
                          n_centroids=shape["n_centroids"]),
        "residual2": dict(compression="residual", residual_bits=2,
                          n_centroids=shape["n_centroids"]),
    }
    # min_width=m folds everything into one full-width bucket: tiny
    # stragglers otherwise pack into one-doc buckets whose kernel TILE
    # degenerately equals the whole bucket, making the bucket-shaped
    # HLO pattern below vacuous.
    packed = {name: masked.pack(min_width=shape["m"], **kw)
              for name, kw in modes.items()}
    fns = {name: jax.jit(lambda qq, ix=ix: topk_search(
        ix, qq, k=k, backend="fused")) for name, ix in packed.items()}
    ids = {name: np.asarray(f(q)[0]) for name, f in fns.items()}
    out = {"shape": dict(shape), "bytes_dense_fp32": dense_bytes,
           "bytes_stored": {}, "bytes_ratio_vs_dense": {},
           "recall_vs_fp32": {}, "q_per_s": {}}
    for name, ix in packed.items():
        t, _ = common.timeit(lambda f=fns[name]: f(q), repeat=2)
        out["q_per_s"][name] = n_q / t
        b = ix.storage()["bytes_stored"]
        out["bytes_stored"][name] = b
        out["bytes_ratio_vs_dense"][name] = b / dense_bytes
        out["recall_vs_fp32"][name] = metrics.recall_at_k(
            ids[name], ids["fp32"])

    # HLO twins on the residual-4 index: the fused program must never
    # materialize a decoded bucket; the reference program (eager
    # decode -> fp32 kernel) is the twin that proves the pattern WOULD
    # show up if decode left the kernel.
    pr = packed["residual4"]
    pats = [re.compile(rf"{b.n_docs}x{b.cap}x{dim}xf32|"
                       rf"f32\[{b.n_docs},{b.cap},{dim}\]")
            for b in pr.buckets]
    def _bucket_fp32(fn):
        low = fn.lower(q)
        texts = (low.as_text(), low.compile().as_text())
        return any(p.search(t) for p in pats for t in texts)
    f_fused = jax.jit(lambda qq: maxsim_scores(pr, qq, backend="fused"))
    f_ref = jax.jit(lambda qq: maxsim_scores(pr, qq, backend="reference"))
    out["hlo_fused_no_fp32_bucket"] = not _bucket_fp32(f_fused)
    out["hlo_reference_materializes"] = bool(_bucket_fp32(f_ref))
    return out


def load_trajectory(path: str = OUT_PATH) -> list[dict]:
    """Read the trajectory entries; a legacy single-record dict (PR 1
    wrote one overwritten object) is adopted as the first entry."""
    if not os.path.exists(path):
        return []
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and "entries" in data:
        return data["entries"]
    if isinstance(data, dict):                # legacy single record
        data.setdefault("timestamp", "pre-trajectory (PR 1)")
        return [data]
    return list(data)


def append_entry(entry: dict, path: str = OUT_PATH) -> None:
    entries = load_trajectory(path)
    entries.append(entry)
    with open(path, "w") as f:
        json.dump({"entries": entries}, f, indent=2, sort_keys=True)
        f.write("\n")


def check_last(path: str = OUT_PATH) -> None:
    """Throughput smoke: batched corpus pruning (the bucketed pipeline
    on the platform's path) must not regress below the same entry's
    reference-path docs/sec."""
    entries = load_trajectory(path)
    if not entries:
        raise SystemExit(f"{path}: no trajectory entries; run the bench")
    last = entries[-1]
    docs = last.get("pruning_docs_per_s", {})
    bucketed = docs.get("bucketed")
    ref = docs.get("reference")
    if bucketed is None or ref is None:
        raise SystemExit(f"{path}: last entry predates the bucketed "
                         "pipeline row on the platform's path; re-run the "
                         "bench")
    if bucketed < ref:
        raise SystemExit(
            f"THROUGHPUT REGRESSION: bucketed pruning "
            f"{bucketed:.2f} docs/s fell below the reference path "
            f"{ref:.2f} docs/s at the bench shape "
            f"{last.get('pruning_shape')}")
    print(f"throughput smoke OK: bucketed {bucketed:.2f} docs/s vs "
          f"reference {ref:.2f} docs/s "
          f"({bucketed / ref:.2f}x at the bench shape)")
    layout = last.get("packed_serving_q_per_s", {})
    pk, mk = layout.get("packed"), layout.get("masked")
    if pk is None or mk is None:
        raise SystemExit(f"{path}: last entry predates the packed index "
                         "layout; re-run the bench")
    if pk < mk:
        raise SystemExit(
            f"THROUGHPUT REGRESSION: packed serving {pk:.2f} q/s fell "
            f"below the masked path {mk:.2f} q/s at the bench shape "
            f"{last.get('packed_serving_shape')}")
    print(f"throughput smoke OK: packed serving {pk:.2f} q/s vs masked "
          f"{mk:.2f} q/s ({pk / mk:.2f}x at the bench shape)")
    stream = last.get("streaming_serving_q_per_s", {})
    st, mt = stream.get("streaming"), stream.get("materializing")
    if st is None or mt is None:
        raise SystemExit(f"{path}: last entry predates streaming top-k "
                         "serving; re-run the bench")
    if st < mt:
        raise SystemExit(
            f"THROUGHPUT REGRESSION: streaming serving {st:.2f} q/s fell "
            f"below the materializing path {mt:.2f} q/s at the bench "
            f"shape {last.get('streaming_serving_shape')}")
    if not last.get("streaming_hlo_no_corpus_matrix", False):
        raise SystemExit(
            "HLO REGRESSION: a corpus-sized (n_q, n_docs) score tensor "
            "reappeared in the compiled streaming serving path "
            f"(shape {last.get('streaming_serving_shape')})")
    if not last.get("streaming_results_identical", False):
        raise SystemExit(
            "PARITY REGRESSION: streaming serving top-k diverged from "
            "the materializing path at the bench shape")
    print(f"throughput smoke OK: streaming serving {st:.2f} q/s vs "
          f"materializing {mt:.2f} q/s ({st / mt:.2f}x, HLO clean, "
          f"results identical)")
    mut = last.get("mutation_serving")
    if mut is None:
        raise SystemExit(f"{path}: last entry predates live-mutation "
                         "serving; re-run the bench")
    if not mut.get("recovery_parity_identical", False):
        raise SystemExit(
            "RECOVERY REGRESSION: the live view re-served after crash "
            "recovery diverged from the pre-crash view at shape "
            f"{mut.get('shape')}")
    if not mut.get("post_compact_parity_identical", False):
        raise SystemExit(
            "COMPACTION REGRESSION: the compacted epoch diverged from "
            "the delta-log view it folds at shape "
            f"{mut.get('shape')}")
    if mut.get("orphans_after_recovery", 1) != 0:
        raise SystemExit(
            "DURABILITY REGRESSION: crash recovery left "
            f"{mut['orphans_after_recovery']} orphaned file(s) in the "
            f"artifact at shape {mut.get('shape')}")
    print(f"mutation serving smoke OK: mixed {mut['mixed_q_per_s']:.2f} "
          f"q/s ({mut['upserts_per_s']:.2f} upserts/s interleaved), "
          f"view {mut['view_q_per_s']:.2f} q/s, recovery "
          f"{mut['recovery_s']*1e3:.0f} ms (bit-identical, 0 orphans)")
    loop = last.get("serve_loop")
    if loop is None:
        raise SystemExit(f"{path}: last entry predates the concurrent "
                         "serving loop; re-run the bench")
    if loop["loop_q_per_s"] < loop["serial_q_per_s"]:
        raise SystemExit(
            "THROUGHPUT REGRESSION: the micro-batched serving loop "
            f"{loop['loop_q_per_s']:.2f} q/s fell below one-query-per-"
            f"call serial serving {loop['serial_q_per_s']:.2f} q/s "
            f"under the mixed query+upsert workload at shape "
            f"{loop.get('shape')}")
    if not loop.get("parity_identical", False):
        raise SystemExit(
            "PARITY REGRESSION: a loop-served answer diverged from the "
            "serial oracle for its (round, query) — micro-batching or "
            f"the result cache drifted at shape {loop.get('shape')}")
    if not loop.get("cache_invalidation_ok", False):
        raise SystemExit(
            "INVALIDATION REGRESSION: the serving loop's result cache "
            "replayed a stale answer across a delta-log update or "
            f"epoch swap at shape {loop.get('shape')}")
    print(f"serving loop smoke OK: loop {loop['loop_q_per_s']:.2f} q/s "
          f"vs serial {loop['serial_q_per_s']:.2f} q/s "
          f"({loop['speedup_loop_over_serial']:.2f}x), p50 "
          f"{loop['p50_latency_s']*1e3:.1f} ms / p99 "
          f"{loop['p99_latency_s']*1e3:.1f} ms (bitwise parity, "
          f"cache invalidates on swap)")
    res = last.get("residual_compression")
    if res is None:
        raise SystemExit(f"{path}: last entry predates the residual "
                         "codec; re-run the bench")
    r4 = res["bytes_ratio_vs_dense"].get("residual4")
    if r4 is None or r4 > 0.25:
        raise SystemExit(
            f"COMPRESSION REGRESSION: residual-4 bytes_stored is {r4} "
            f"of the dense fp32 corpus (gate: <= 0.25) at shape "
            f"{res.get('shape')}")
    rec4 = res["recall_vs_fp32"].get("residual4", 0.0)
    if rec4 < 0.99:
        raise SystemExit(
            f"RECALL REGRESSION: residual-4 top-k recall@k {rec4} fell "
            f"below 0.99 against the fp32 packed oracle at shape "
            f"{res.get('shape')}")
    if not res.get("hlo_fused_no_fp32_bucket", False):
        raise SystemExit(
            "HLO REGRESSION: a bucket-sized fp32 tensor appeared in the "
            "fused residual serving program — decode is no longer "
            f"staying tile-local in VMEM (shape {res.get('shape')})")
    if not res.get("hlo_reference_materializes", False):
        raise SystemExit(
            "HLO TWIN BROKEN: the eagerly-decoding reference program no "
            "longer holds a bucket-sized fp32 tensor — the pattern the "
            "fused gate greps for has gone stale and proves nothing "
            f"(shape {res.get('shape')})")
    qr, qi = res["q_per_s"]["residual4"], res["q_per_s"]["int8"]
    if last.get("interpret_mode_kernels", True):
        # Off-TPU the Pallas interpreter prices the in-kernel decode
        # per tile while the int8 path decodes in plain XLA; hold a
        # sanity floor here and enforce the real >= contract where the
        # Mosaic kernels actually run.
        if qr < 0.5 * qi:
            raise SystemExit(
                f"THROUGHPUT REGRESSION: fused residual serving "
                f"{qr:.2f} q/s fell below half the int8 path "
                f"{qi:.2f} q/s even under the interpreter at shape "
                f"{res.get('shape')}")
        note = f"interpreted: floor 0.5x int8, measured {qr / qi:.2f}x"
    elif qr < qi:
        raise SystemExit(
            f"THROUGHPUT REGRESSION: fused residual serving {qr:.2f} "
            f"q/s fell below the int8 path {qi:.2f} q/s at shape "
            f"{res.get('shape')}")
    else:
        note = f"{qr / qi:.2f}x int8"
    print(f"residual codec smoke OK: {r4:.4f}x dense bytes at recall "
          f"{rec4:.3f}; residual-4 {qr:.2f} q/s vs int8 {qi:.2f} q/s "
          f"({note}); fused HLO bucket-fp32-free, reference twin "
          f"materializes")
    # Routed gate sits BEFORE the grid/fault gates: those may return
    # early on platforms that cannot form a grid, and the routed
    # contract must be enforced everywhere.
    routed = last.get("routed_serving")
    if routed is None:
        raise SystemExit(f"{path}: last entry predates candidate "
                         "routing; re-run the bench")
    if routed.get("recall_nprobe", 0.0) < 0.99:
        raise SystemExit(
            f"RECALL REGRESSION: nprobe routing recall@k "
            f"{routed.get('recall_nprobe')} fell below 0.99 against the "
            f"exhaustive oracle at shape {routed.get('shape')}")
    if routed.get("fraction_buckets_nprobe", 1.0) >= 1.0:
        raise SystemExit(
            "ROUTING REGRESSION: the nprobe route scored every bucket "
            f"(fraction {routed.get('fraction_buckets_nprobe')}) — "
            f"candidate pruning is not engaging at shape "
            f"{routed.get('shape')}")
    if not routed.get("bounded_exact", False):
        raise SystemExit(
            "PARITY REGRESSION: the bounded route diverged from the "
            "exhaustive sweep — the score upper bound is no longer "
            f"admissible at shape {routed.get('shape')}")
    if routed.get("nprobe", 0.0) < routed.get("exhaustive", 0.0):
        raise SystemExit(
            f"THROUGHPUT REGRESSION: routed serving "
            f"{routed.get('nprobe'):.2f} q/s fell below the exhaustive "
            f"sweep {routed.get('exhaustive'):.2f} q/s at shape "
            f"{routed.get('shape')}")
    print(f"routed serving smoke OK: nprobe {routed['nprobe']:.2f} q/s "
          f"vs exhaustive {routed['exhaustive']:.2f} q/s "
          f"({routed['speedup_nprobe_over_exhaustive']:.2f}x at "
          f"{routed['fraction_buckets_nprobe']:.2f} of buckets, recall "
          f"{routed['recall_nprobe']:.3f}); bounded "
          f"{routed['bounded']:.2f} q/s (exact, "
          f"{routed['fraction_buckets_bounded']:.2f} of buckets)")
    grid = last.get("grid_serving")
    if grid is None:
        raise SystemExit(f"{path}: last entry predates grid placement "
                         "serving; re-run the bench")
    if not grid.get("results_identical", False):
        raise SystemExit(
            "PARITY REGRESSION: grid-placed serving diverged from the "
            f"single-device oracle at shape {grid.get('shape')}")
    if not grid.get("hlo_no_corpus_matrix", False):
        raise SystemExit(
            "HLO REGRESSION: a corpus-sized tensor appeared in a "
            f"compiled per-group grid program (shape {grid.get('shape')})")
    xb = grid["exchange_bytes"]
    print(f"grid placement smoke OK: grid {grid['grid']:.2f} q/s vs flat "
          f"{grid['flat']:.2f} q/s; cross-host exchange "
          f"{xb['grid_cross_host']} B vs {xb['flat_cross_host']} B "
          f"({grid['cross_host_bytes_ratio_flat_over_grid']:.1f}x less, "
          f"parity + HLO clean)")
    ft = last.get("fault_tolerance")
    if ft is None:
        raise SystemExit(f"{path}: last entry predates fault-tolerant "
                         "serving; re-run the bench")
    if not ft.get("parity_failover_identical", False):
        raise SystemExit(
            "FAILOVER REGRESSION: replicated serving after one lost host "
            "group diverged from the no-failure oracle at shape "
            f"{ft.get('shape')}")
    if not (0.0 < ft.get("degraded_coverage", 1.0) < 1.0
            and ft.get("degraded_scores_finite", False)):
        raise SystemExit(
            "COVERAGE REGRESSION: unreplicated serving under a lost "
            "group must report 0 < coverage < 1 with finite scores, got "
            f"coverage={ft.get('degraded_coverage')} at shape "
            f"{ft.get('shape')}")
    print(f"fault tolerance smoke OK: replicated {ft['replicated']:.2f} "
          f"q/s, failover recovery {ft['failover_recovery_s']*1e3:.0f} ms, "
          f"post-failover {ft['post_failover']:.2f} q/s "
          f"(bit-identical); degraded coverage "
          f"{ft['degraded_coverage']:.3f}")


def main():
    pruning = run_pruning_backends()
    ragged = run_ragged_pruning()
    rerank = run_rerank_backends(**RERANK)
    layout = run_packed_serving()
    stream = run_streaming_serving()
    mut = run_mutation_serving()
    loop = run_serve_loop()
    res = run_residual_compression()
    routed = run_routed_serving()
    grid = run_grid_serving()
    fault = run_fault_tolerance()

    for name in PRUNING_BACKENDS:
        common.csv_line(f"kernel_backends/pruning_{name}",
                        1e6 / pruning[name],
                        f"docs_per_s={pruning[name]:.2f}")
    common.csv_line("kernel_backends/pruning_bucketed_ragged",
                    1e6 / ragged["bucketed"],
                    f"docs_per_s={ragged['bucketed']:.2f};"
                    f"{ragged['speedup_bucketed_over_flat']:.2f}x over "
                    f"flat padding on the ragged corpus")
    for name in ("reference", "fused", "fused_autotuned"):
        common.csv_line(f"kernel_backends/rerank_{name}",
                        1e6 / rerank[name],
                        f"q_per_s={rerank[name]:.2f}")
    wins = rerank["speedup_fused_over_reference"] > 1.0
    common.csv_line(
        "kernel_backends/CLAIM_chunked_serving_beats_reference", 0.0,
        f"holds={wins};"
        f"speedup={rerank['speedup_fused_over_reference']:.2f}x at "
        f"{rerank['shape']['n_q']}q x {rerank['shape']['n_docs']}docs")
    prune_speedup = pruning["bucketed"] / pruning["reference"]
    common.csv_line(
        "kernel_backends/CLAIM_bucketed_pruning_2x_reference", 0.0,
        f"holds={prune_speedup >= 2.0};speedup={prune_speedup:.2f}x at "
        f"{pruning['shape']['n_docs']}docs x {pruning['shape']['m']}tok")
    for name in ("masked", "packed"):
        common.csv_line(f"kernel_backends/serving_layout_{name}",
                        1e6 / layout[name],
                        f"q_per_s={layout[name]:.2f}")
    common.csv_line(
        "kernel_backends/CLAIM_packed_index_shrinks_and_keeps_throughput",
        0.0,
        f"holds={layout['speedup_packed_over_masked'] >= 1.0};"
        f"speedup={layout['speedup_packed_over_masked']:.2f}x;"
        f"bytes_ratio={layout['bytes_ratio_packed_over_dense']:.3f} of "
        f"dense at keep={layout['shape']['keep_fraction']}")
    for name in ("materializing", "streaming"):
        common.csv_line(f"kernel_backends/serving_dataflow_{name}",
                        1e6 / stream[name],
                        f"q_per_s={stream[name]:.2f}")
    pb_m = stream["peak_temp_bytes_materializing"]
    pb_s = stream["peak_temp_bytes_streaming"]
    stream_ok = (stream["speedup_streaming_over_materializing"] >= 1.0
                 and stream["hlo_no_corpus_matrix"]
                 and stream["results_identical"])
    common.csv_line(
        "kernel_backends/CLAIM_streaming_topk_no_score_matrix", 0.0,
        f"holds={stream_ok};"
        f"speedup={stream['speedup_streaming_over_materializing']:.2f}x;"
        f"peak_temp_bytes={pb_s}/{pb_m};"
        f"hlo_clean={stream['hlo_no_corpus_matrix']}")
    for name in ("mixed_q_per_s", "view_q_per_s"):
        common.csv_line(f"kernel_backends/serving_mutation_{name}",
                        1e6 / mut[name], f"q_per_s={mut[name]:.2f}")
    common.csv_line("kernel_backends/serving_mutation_recovery",
                    mut["recovery_s"] * 1e6,
                    f"recover_to_first_query_s={mut['recovery_s']:.3f}")
    mut_ok = (mut["recovery_parity_identical"]
              and mut["post_compact_parity_identical"]
              and mut["orphans_after_recovery"] == 0)
    common.csv_line(
        "kernel_backends/CLAIM_mutation_recovery_bit_identical", 0.0,
        f"holds={mut_ok};"
        f"recovery_parity={mut['recovery_parity_identical']};"
        f"compact_parity={mut['post_compact_parity_identical']};"
        f"orphans={mut['orphans_after_recovery']}")
    for name in ("serial_q_per_s", "loop_q_per_s"):
        common.csv_line(f"kernel_backends/serving_loop_{name}",
                        1e6 / loop[name], f"q_per_s={loop[name]:.2f}")
    common.csv_line("kernel_backends/serving_loop_p99",
                    loop["p99_latency_s"] * 1e6,
                    f"p50_s={loop['p50_latency_s']:.4f};"
                    f"p99_s={loop['p99_latency_s']:.4f}")
    loop_ok = (loop["speedup_loop_over_serial"] >= 1.0
               and loop["parity_identical"]
               and loop["cache_invalidation_ok"])
    common.csv_line(
        "kernel_backends/CLAIM_serve_loop_batches_without_drift", 0.0,
        f"holds={loop_ok};"
        f"speedup={loop['speedup_loop_over_serial']:.2f}x;"
        f"parity={loop['parity_identical']};"
        f"invalidation={loop['cache_invalidation_ok']};"
        f"cache_hits={loop['cache_hits']}")
    for name in ("fp32", "int8", "residual4", "residual2"):
        common.csv_line(f"kernel_backends/serving_codec_{name}",
                        1e6 / res["q_per_s"][name],
                        f"q_per_s={res['q_per_s'][name]:.2f};"
                        f"bytes_ratio="
                        f"{res['bytes_ratio_vs_dense'][name]:.4f};"
                        f"recall={res['recall_vs_fp32'][name]:.3f}")
    res_ok = (res["bytes_ratio_vs_dense"]["residual4"] <= 0.25
              and res["recall_vs_fp32"]["residual4"] >= 0.99
              and res["hlo_fused_no_fp32_bucket"]
              and res["hlo_reference_materializes"])
    common.csv_line(
        "kernel_backends/CLAIM_residual_compression", 0.0,
        f"holds={res_ok};"
        f"bytes_ratio={res['bytes_ratio_vs_dense']['residual4']:.4f};"
        f"recall={res['recall_vs_fp32']['residual4']:.3f};"
        f"q_per_s_vs_int8="
        f"{res['q_per_s']['residual4'] / res['q_per_s']['int8']:.2f}x;"
        f"hlo_fused_clean={res['hlo_fused_no_fp32_bucket']}")
    for name in ("exhaustive", "nprobe", "bounded"):
        common.csv_line(f"kernel_backends/serving_routed_{name}",
                        1e6 / routed[name], f"q_per_s={routed[name]:.2f}")
    routed_ok = (routed["recall_nprobe"] >= 0.99
                 and routed["bounded_exact"]
                 and routed["fraction_buckets_nprobe"] < 1.0
                 and routed["nprobe"] >= routed["exhaustive"])
    common.csv_line(
        "kernel_backends/CLAIM_routed_serving_sublinear_high_recall", 0.0,
        f"holds={routed_ok};"
        f"speedup={routed['speedup_nprobe_over_exhaustive']:.2f}x;"
        f"fraction={routed['fraction_buckets_nprobe']:.2f};"
        f"recall={routed['recall_nprobe']:.3f};"
        f"bounded_exact={routed['bounded_exact']}")
    for name in ("flat", "grid"):
        common.csv_line(f"kernel_backends/serving_placement_{name}",
                        1e6 / grid[name],
                        f"q_per_s={grid[name]:.2f};{CPU_REHEARSAL}")
    grid_ok = (grid["results_identical"]
               and grid["hlo_no_corpus_matrix"])
    common.csv_line(
        "kernel_backends/CLAIM_grid_placement_shrinks_cross_host_bytes",
        0.0,
        f"holds={grid_ok};cross_host_bytes_ratio="
        f"{grid['cross_host_bytes_ratio_flat_over_grid']:.1f}x;"
        f"parity={grid['results_identical']};"
        f"hlo_clean={grid['hlo_no_corpus_matrix']}")
    common.csv_line("kernel_backends/serving_replicated",
                    1e6 / fault["replicated"],
                    f"q_per_s={fault['replicated']:.2f};{CPU_REHEARSAL}")
    common.csv_line("kernel_backends/serving_failover_recovery",
                    fault["failover_recovery_s"] * 1e6,
                    f"first_query_after_loss_s="
                    f"{fault['failover_recovery_s']:.3f};{CPU_REHEARSAL}")
    fault_ok = (fault["parity_failover_identical"]
                and 0.0 < fault["degraded_coverage"] < 1.0)
    common.csv_line(
        "kernel_backends/CLAIM_replicated_failover_bit_identical",
        0.0,
        f"holds={fault_ok};"
        f"parity={fault['parity_failover_identical']};"
        f"degraded_coverage={fault['degraded_coverage']:.3f}")

    entry = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "jax_backend": jax.default_backend(),
        "interpret_mode_kernels": jax.default_backend() != "tpu",
        "pruning_docs_per_s": {k: v for k, v in pruning.items()
                               if k != "shape"},
        "pruning_shape": pruning["shape"],
        "pruning_speedup_bucketed_over_reference": prune_speedup,
        "ragged_pruning_docs_per_s": {k: ragged[k]
                                      for k in ("flat", "bucketed")},
        "ragged_pruning_shape": ragged["shape"],
        "ragged_speedup_bucketed_over_flat":
            ragged["speedup_bucketed_over_flat"],
        "rerank_q_per_s": {k: rerank[k] for k in
                           ("reference", "fused", "fused_autotuned")},
        "rerank_speedup_fused_over_reference":
            rerank["speedup_fused_over_reference"],
        "rerank_shape": rerank["shape"],
        "packed_serving_q_per_s": {k: layout[k]
                                   for k in ("masked", "packed")},
        "packed_serving_shape": layout["shape"],
        "packed_speedup_over_masked": layout["speedup_packed_over_masked"],
        "packed_bytes": {k: layout[k] for k in
                         ("bytes_masked_resident", "bytes_packed_stored",
                          "bytes_ratio_packed_over_dense")},
        "streaming_serving_q_per_s": {k: stream[k] for k in
                                      ("materializing", "streaming")},
        "streaming_serving_shape": stream["shape"],
        "streaming_speedup_over_materializing":
            stream["speedup_streaming_over_materializing"],
        "streaming_peak_temp_bytes": {
            "materializing": stream["peak_temp_bytes_materializing"],
            "streaming": stream["peak_temp_bytes_streaming"]},
        "streaming_hlo_no_corpus_matrix": stream["hlo_no_corpus_matrix"],
        "streaming_results_identical": stream["results_identical"],
        "claim_chunked_serving_beats_reference": bool(wins),
        "claim_bucketed_pruning_2x_reference": bool(prune_speedup >= 2.0),
        "claim_packed_index_shrinks_and_keeps_throughput":
            bool(layout["speedup_packed_over_masked"] >= 1.0),
        "claim_streaming_topk_no_score_matrix": bool(
            stream["speedup_streaming_over_materializing"] >= 1.0
            and stream["hlo_no_corpus_matrix"]
            and stream["results_identical"]),
        "mutation_serving": mut,
        "claim_mutation_recovery_bit_identical": bool(
            mut["recovery_parity_identical"]
            and mut["post_compact_parity_identical"]
            and mut["orphans_after_recovery"] == 0),
        "serve_loop": loop,
        "claim_serve_loop_batches_without_drift": bool(loop_ok),
        "residual_compression": res,
        "claim_residual_compression": bool(res_ok),
        "routed_serving": routed,
        "claim_routed_serving_sublinear_high_recall": bool(routed_ok),
        "grid_serving": grid,
        "claim_grid_placement_parity_and_clean_hlo": bool(grid_ok),
        "fault_tolerance": fault,
        "claim_replicated_failover_bit_identical": bool(fault_ok),
    }
    append_entry(entry)


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--grid-worker" in argv:
        shape = json.loads(argv[argv.index("--grid-worker") + 1])
        print("RESULT " + json.dumps(_grid_worker(shape)))
    elif "--fault-worker" in argv:
        shape = json.loads(argv[argv.index("--fault-worker") + 1])
        print("RESULT " + json.dumps(_fault_worker(shape)))
    elif "--check" in argv:
        check_last()
    else:
        main()
