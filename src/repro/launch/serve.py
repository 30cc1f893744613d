"""Serving driver.

  --arch colbert : end-to-end late-interaction retrieval service
                   (encode corpus -> Voronoi-prune -> pack -> batched
                   queries), for every arch of the "retrieval" family
                   (colbert, gte-moderncolbert).  With --index-dir the packed artifact is
                   persisted there on first run (prune -> pack -> save ->
                   load -> serve) and loaded directly on later runs —
                   the offline-prune / online-serve split.  --upsert /
                   --delete / --compact then drive the live-mutation
                   lifecycle against that artifact: durable WAL-logged
                   delta buckets and tombstones served beside the base
                   epoch, folded into the next epoch by compaction
                   (repro.serve.mutation).  --route bounded|nprobe turns
                   on Voronoi-as-IVF candidate routing: a per-bucket
                   centroid table (repro.serve.routing, persisted as an
                   artifact sidecar) prunes whole capacity buckets per
                   query before any document is scored, and the run
                   reports recall@k against the exhaustive sweep.
                   --preset full runs the arch's published widths
                   (colbert: 12L/768, out_dim 128, doc_len 180) with
                   seeded random weights over --n-docs synthetic
                   documents.
  --arch <lm>    : KV-cache decode loop on the smoke config
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro import sharding as shlib
from repro.core import backend as backend_lib
from repro.core import metrics
from repro.core import pruning_pipeline, voronoi
from repro.core.sampling import sample_sphere
from repro.data import synthetic
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.models import colbert as colbert_lib
from repro.models import transformer as tfm
from repro.serve import health, index_io
from repro.serve import mutation as mutation_lib
from repro.serve.retrieval import RetrievalServer, TokenIndex, topk_search
from repro.train import checkpoint


PRESETS = ("smoke", "full")
# Corpus size when --n-docs is not given.
DEFAULT_N_DOCS = {"smoke": 256, "full": 4096}
# Documents per jitted encoder call: one compiled shape for the corpus.
ENCODE_BATCH = 256


def is_retrieval(arch: str) -> bool:
    """Whether ``arch`` is registered in the late-interaction retrieval
    family (served by :func:`serve_retrieval`)."""
    return (arch in configs.all_archs()
            and configs.get(arch).family == "retrieval")


def load_model(preset: str = "smoke", seed: int = 0,
               ckpt_dir: str | None = None, arch: str = "colbert"):
    """``(cfg, params)`` of the retrieval encoder ``arch`` at ``preset``
    width, weights drawn from ``seed`` (or restored from ``ckpt_dir``)."""
    entry = configs.get(arch)
    cfg = entry.config if preset == "full" else entry.smoke
    params = colbert_lib.init_params(jax.random.PRNGKey(seed), cfg)
    if ckpt_dir:
        _, restored = checkpoint.restore_latest(
            ckpt_dir, {"params": params, "opt": None, "step": None})
        if restored is not None:
            params = restored["params"]
    return cfg, params


def n_samples_for(preset: str, arch: str = "colbert") -> int:
    """Voronoi sample count: the ``prune_index`` shape at full width."""
    if preset == "full":
        return configs.get(arch).shapes["prune_index"].dims["n_samples"]
    return 2048


@dataclasses.dataclass
class EncodeStats:
    """What :func:`encode_corpus` computed, summed over its calls: real
    (non-pad) tokens, and token slots (real and padded, padding rows of
    the last batch included)."""

    real_tokens: int = 0
    slots: int = 0


@functools.partial(jax.jit, static_argnums=1)
def encode_docs(params, cfg, ids):
    return colbert_lib.encode_docs(params, cfg, ids)


@functools.partial(jax.jit, static_argnums=1)
def encode_query_batch(params, cfg, ids):
    return colbert_lib.encode_queries(params, cfg, ids)[0]


def encode_corpus(params, cfg, doc_ids, batch: int = ENCODE_BATCH,
                  stats: EncodeStats | None = None):
    """Encode token-id documents in fixed-shape jitted batches (the last
    one zero-padded, i.e. all-masked) -> ``(d_emb (n, m, out_dim) f32,
    d_mask (n, m) bool)``; the index stores fp32 whatever the encoder
    computes in.  Each dispatch is the span ``repro.encode`` (args
    ``backbone``, ``docs``, ``real_tokens``, ``slots``) and is added to
    ``stats`` when given."""
    ids = np.asarray(doc_ids)
    n = ids.shape[0]
    b = max(1, min(batch, n))
    embs, masks = [], []
    for lo in range(0, n, b):
        chunk = ids[lo:lo + b]
        docs, real = len(chunk), int(np.count_nonzero(chunk))
        if len(chunk) < b:
            chunk = np.pad(chunk, ((0, b - len(chunk)), (0, 0)))
        with obs.span("repro.encode", backbone=cfg.backbone, docs=docs,
                      real_tokens=real, slots=chunk.size):
            e, mk = encode_docs(params, cfg, jnp.asarray(chunk))
        if stats is not None:
            stats.real_tokens += real
            stats.slots += chunk.size
        embs.append(e.astype(jnp.float32))
        masks.append(mk)
    return jnp.concatenate(embs)[:n], jnp.concatenate(masks)[:n]


def encode_queries(params, cfg, q_ids):
    """Encode a query batch (ColBERT [MASK] augmentation) in one jitted
    call -> ``(n_q, query_len, out_dim)`` f32."""
    return encode_query_batch(params, cfg,
                              jnp.asarray(q_ids)).astype(jnp.float32)


def prune_index(d_emb, d_mask, keep_fraction: float, *, n_samples: int,
                backend: str | None = None) -> TokenIndex:
    """Voronoi-prune a corpus to ``keep_fraction`` of its tokens under a
    corpus-wide budget: length-bucketed per-document orders, then the
    global merge (``pruning_pipeline.prune_corpus``).  Under the active
    sharding rules' data mesh the job distributes, bit-identically."""
    samples = sample_sphere(jax.random.PRNGKey(1), n_samples,
                            d_emb.shape[-1])
    keep, _, _ = pruning_pipeline.prune_corpus(
        d_emb, d_mask, samples, keep_fraction, backend=backend)
    return TokenIndex.build(d_emb, d_mask).with_keep(keep)


def grid_rules(packed, hosts: int, *, replicas: int = 1, placement=None):
    """The ``--mesh grid`` layout of ``packed``: capacity buckets pinned
    to ``hosts`` host groups (``placement``, else a fresh
    ``PlacementPlan``) on a hosts x candidates device grid.  Returns
    ``(rules, monitor)``: the serving axis rules to enter and the
    ``FleetMonitor`` the cross-group exchange reports to."""
    placement = placement or shlib.PlacementPlan.for_index(
        packed, hosts, replicas=min(replicas, hosts))
    serve_mesh = mesh_lib.make_serve_mesh(hosts=hosts)
    print(f"[serve] grid serving mesh: {dict(serve_mesh.shape)} "
          f"(placement groups={list(placement.groups)}, "
          f"replicas={placement.replicas})")
    return (shlib.serve_rules(serve_mesh, placement=placement),
            health.FleetMonitor(hosts))


def _report_bytes(packed) -> None:
    """One stable, grep-able storage line per serve run (smoke.sh and
    operators key off it)."""
    st = packed.storage()
    ratio = st["bytes_stored"] / max(st["bytes_dense_fp32"], 1)
    codec = packed.codec_tag() or "fp32"
    print(f"[serve] storage: codec={codec} "
          f"bytes_stored={st['bytes_stored']} "
          f"ratio={ratio:.4f} of dense fp32")


def serve_retrieval(keep_fraction: float = 0.5, n_queries: int = 32,
                    preset: str = "smoke", n_docs: int | None = None,
                    arch: str = "colbert",
                    ckpt_dir: str | None = None, seed: int = 0,
                    backend: str | None = None,
                    index_dir: str | None = None,
                    compress: str = "none",
                    residual_bits: int = 4,
                    pool_threshold: float = 0.0,
                    mesh: str = "none",
                    n_first: int = 64,
                    hosts: int = 0,
                    replicas: int = 1,
                    on_group_loss: str = "degrade",
                    kill_group: int | None = None,
                    upsert: int = 0,
                    delete: tuple = (),
                    compact: bool = False,
                    route: str = "exhaustive",
                    n_probe: int = 1,
                    centroids: int = 4,
                    serve_loop: bool = False,
                    flush_ms: float = 2.0,
                    max_batch: int = 8):
    if replicas < 1:
        raise ValueError(f"--replicas {replicas} < 1")
    # --backend names one path; pruning and serving each take it only
    # when it is one of theirs, else their platform default.
    prune_backend = backend if backend in backend_lib.PRUNING else None
    serve_backend = backend if backend in backend_lib.SERVING else None
    if route != "exhaustive" and not index_dir:
        raise ValueError(f"--route {route} needs --index-dir: the routing "
                         "table is an artifact sidecar")
    cfg, params = load_model(preset, seed, ckpt_dir, arch=arch)
    corpus = synthetic.token_corpus(
        seed, n_docs=n_docs or DEFAULT_N_DOCS[preset], n_q=n_queries,
        vocab=cfg.vocab, m=cfg.doc_len, l=cfg.query_len)
    if mesh == "grid" and hosts <= 0:
        hosts = mesh_lib.default_serve_hosts()
    if mesh == "grid" and hosts <= 1:
        raise ValueError(
            f"--mesh grid needs >= 2 host groups; {len(jax.devices())} "
            "device(s) form none (set --hosts, or serve without --mesh)")
    if index_dir and (upsert or delete or compact):
        # Mutation runs start by resolving any interrupted mutation a
        # previous process left behind: roll landed intents forward,
        # torn ones back, sweep orphans — then the artifact is a clean
        # pre- or post-mutation epoch and serving proceeds normally.
        report = index_io.recover(index_dir)
        if any(report.values()):
            print(f"[serve] recovered artifact: {report}")
    if index_dir and index_io.has_index(index_dir):
        # Online half of the lifecycle: the pruning job already ran and
        # the artifact is authoritative — this run's pruning/packing
        # flags do not apply to it.  Warn when they visibly disagree so
        # a ratio sweep pointed at a stale directory cannot silently
        # report results from the wrong index.
        packed = index_io.load_index(index_dir)
        st = packed.storage()
        print(f"[serve] loaded packed index from {index_dir}: {st}")
        _report_bytes(packed)
        if compress != packed.compression:
            print(f"[serve] WARNING: --compress {compress} ignored; the "
                  f"loaded artifact is {packed.compression!r} (delete "
                  f"{index_dir} to re-pack)")
        if abs(st["remain_pct"] - 100.0 * keep_fraction) > 1.0:
            print(f"[serve] WARNING: --keep {keep_fraction} ignored; the "
                  f"loaded artifact retains {st['remain_pct']:.1f}% of "
                  f"tokens (delete {index_dir} to re-prune)")
        if ckpt_dir:
            print(f"[serve] WARNING: --ckpt-dir ignored; the loaded "
                  f"artifact was encoded by the job that built it")
    else:
        d_emb, d_mask = encode_corpus(params, cfg, corpus.doc_ids)
        # Length-bucketed corpus pruning: short documents run in narrow
        # shape buckets instead of paying full-doc_len padding per step.
        # Under a multi-device mesh the whole job distributes: each
        # bucket's doc axis shards over `data` (shard_map) and the §4.2
        # global merge runs its bitwise-selection cut — bit-identical to
        # the single-device path either way.
        prune_ctx = contextlib.nullcontext()
        if mesh in ("host", "grid") and len(jax.devices()) > 1:
            data_mesh = mesh_lib.make_host_mesh()
            print(f"[serve] sharded pruning over data={data_mesh.shape['data']}")
            prune_ctx = shlib.axis_rules({"__mesh__": data_mesh})
        print(f"[serve] pruning backend: "
              f"{voronoi.resolve_pruning_backend(prune_backend)}")
        with prune_ctx:
            pruned = prune_index(d_emb, d_mask, keep_fraction,
                                 n_samples=n_samples_for(preset, arch),
                                 backend=prune_backend)
        keep = pruned.keep
        if pool_threshold:
            # Token pooling (Clavié et al.): merge near-duplicate kept
            # tokens per doc before packing — the pooled corpus is what
            # the residual codec quantizes, so the two reductions stack.
            before = int(np.asarray(keep & d_mask).sum())
            pooled, keep = pruning_pipeline.pool_tokens(
                np.asarray(d_emb), np.asarray(keep & d_mask),
                pool_threshold)
            pruned = TokenIndex.build(jnp.asarray(pooled),
                                      d_mask).with_keep(jnp.asarray(keep))
            after = int(np.asarray(keep).sum())
            print(f"[serve] pooled tokens at cos>={pool_threshold}: "
                  f"{before} -> {after} kept")
        print(f"[serve] masked (reported): {pruned.storage()}")
        packed = pruned.pack(compression=compress,
                             residual_bits=residual_bits)
        print(f"[serve] packed (measured): {packed.storage()}")
        _report_bytes(packed)
        if index_dir:
            placement = None
            if mesh == "grid" and hosts > 1:
                r = min(replicas, hosts)
                if r != replicas:
                    print(f"[serve] WARNING: --replicas {replicas} clamped "
                          f"to {r} (chains must land on distinct groups, "
                          f"only {hosts} host groups)")
                placement = shlib.PlacementPlan.for_index(packed, hosts,
                                                          replicas=r)
            index_io.save_index(index_dir, packed, placement=placement)
            # Serve what is on disk, not what is in memory: the reload
            # exercises the exact artifact a later job would start from.
            packed = index_io.load_index(index_dir)
            print(f"[serve] saved + reloaded packed index at {index_dir}"
                  + (f" ({placement.n_groups} host-group bodies)"
                     if placement else ""))
    routing = None
    if route != "exhaustive":
        # The routing table is an artifact sidecar: load the persisted
        # one when the live epoch carries it, else build it once (k-means
        # over each bucket's kept tokens) and persist it beside the
        # epoch it was built from, where the Compactor will keep it
        # fresh across future epochs.
        if index_io.has_routing(index_dir):
            routing = index_io.load_routing(index_dir)
            print(f"[serve] loaded routing table: {routing.n_buckets} "
                  f"buckets x {routing.n_centroids} centroids "
                  f"(epoch {routing.epoch})")
            if routing.n_centroids != centroids:
                print(f"[serve] WARNING: --centroids-per-bucket "
                      f"{centroids} ignored; the loaded table has "
                      f"{routing.n_centroids} (delete the artifact's "
                      f"routing sidecar to rebuild)")
        else:
            from repro.serve.routing import RoutingIndex
            routing = RoutingIndex.build(packed, n_centroids=centroids)
            index_io.save_routing(index_io.live_epoch_dir(index_dir),
                                  routing)
            print(f"[serve] built + saved routing table: "
                  f"{routing.n_buckets} buckets x "
                  f"{routing.n_centroids} centroids "
                  f"(epoch {routing.epoch})")
    # --mesh host: every local device on the candidates axis; the server
    # closures trace under serve_rules, so the streaming top-k merge
    # shards each capacity bucket and all-gathers only (n_q, k)
    # candidates per shard (DESIGN_BACKENDS.md §Sharded serving).  The
    # sharded merge runs on the e2e exact-sweep route — pass
    # --n-first >= the corpus size (or 0) to take it; a smaller n_first
    # serves the two-stage rerank, whose first stage streams but stays
    # shard-local.
    ctx = contextlib.nullcontext()
    monitor = None
    if mesh == "host":
        serve_mesh = mesh_lib.make_serve_mesh()
        n_shards = serve_mesh.shape["model"]
        print(f"[serve] sharded serving mesh: {serve_mesh} "
              f"({n_shards} candidate shard{'s' if n_shards != 1 else ''})")
        ctx = shlib.axis_rules(shlib.serve_rules(serve_mesh))
    elif mesh == "grid":
        # --mesh grid: the multi-host placement layout.  Buckets pin to
        # host groups (PlacementPlan), each group's row of the
        # hosts x candidates mesh serves its own buckets, and only
        # (n_q, k) candidate blocks cross groups (DESIGN_BACKENDS.md
        # §Placement).  A saved artifact's plan is authoritative: the
        # mesh follows ITS group count when the device count can form
        # that grid; otherwise the plan is rebalanced for this machine
        # (with a warning — the artifact on disk keeps its layout).
        placement = index_dir and index_io.load_placement(index_dir)
        if placement and placement.n_groups != hosts:
            if len(jax.devices()) % placement.n_groups == 0:
                print(f"[serve] --hosts {hosts} overridden by the "
                      f"artifact's placement ({placement.n_groups} "
                      "host groups)")
                hosts = placement.n_groups
            else:
                print(f"[serve] WARNING: artifact placement has "
                      f"{placement.n_groups} host groups but "
                      f"{len(jax.devices())} devices cannot form that "
                      f"grid; rebalancing for {hosts} groups")
                placement = None
        if placement and replicas > 1 and placement.replicas != replicas:
            print(f"[serve] WARNING: --replicas {replicas} ignored; the "
                  f"artifact's plan stores replicas={placement.replicas} "
                  f"(delete {index_dir} to re-place)")
        rules, monitor = grid_rules(packed, hosts, replicas=replicas,
                                    placement=placement or None)
        ctx = shlib.axis_rules(rules)
    if n_first <= 0:
        n_first = packed.n_docs                  # e2e exact-sweep route
    # Routed modes always take the streaming e2e sweep over the surviving
    # buckets (candidate routing replaces the two-stage shortlist).
    sweep = ("e2e" if n_first >= packed.n_docs or route != "exhaustive"
             else "two-stage")
    with ctx:
        server = RetrievalServer(packed, k=10, n_first=n_first,
                                 backend=serve_backend, monitor=monitor,
                                 on_group_loss=on_group_loss,
                                 route=route, routing=routing,
                                 n_probe=n_probe)
        print(f"[serve] route: {sweep} (n_first={n_first}, "
              f"n_docs={packed.n_docs})"
              + (f" + candidate routing ({route})"
                 if route != "exhaustive" else ""))
        print(f"[serve] scoring backend: {server.backend}")
        if kill_group is not None:
            if monitor is None:
                print("[serve] WARNING: --kill-group needs an active "
                      "--mesh grid; ignored")
            else:
                monitor.demote(kill_group)
                print(f"[serve] injected loss of host group {kill_group} "
                      f"(--on-group-loss {on_group_loss})")
        q_emb = encode_queries(params, cfg, corpus.q_ids)
        t0 = time.time()
        out = server.query_batch(q_emb)
        dt = time.time() - t0
        idx, scores = out
        coverage = getattr(out, "coverage", 1.0)
        print(f"[serve] {n_queries} queries in {dt*1e3:.1f} ms "
              f"({dt/n_queries*1e3:.2f} ms/q)")
        if monitor is not None:
            print(f"[serve] coverage: {coverage:.3f} "
                  f"(live groups: {sorted(monitor.live())})")
        if route != "exhaustive":
            # Routed report: rerun eagerly to collect route_stats (the
            # server's closure serves the same host-side selection), and
            # score the served ids against the exhaustive oracle.
            stats = {}
            topk_search(packed, q_emb, k=server.k, backend=server.backend,
                        route=route, routing=routing, n_probe=n_probe,
                        route_stats=stats)
            oi, _ = topk_search(packed, q_emb, k=server.k,
                                backend=server.backend)
            rec = metrics.recall_at_k(np.asarray(idx), np.asarray(oi))
            line = (f"[serve] routed ({route}): "
                    f"{stats['buckets_scored']}/{stats['n_buckets']} "
                    f"buckets scored "
                    f"(fraction {stats['fraction']:.2f})")
            if "groups_consulted" in stats:
                line += (f"; {stats['groups_consulted']}/"
                         f"{stats['n_groups']} host groups consulted")
            print(line)
            print(f"[serve] routed recall@{server.k} vs exhaustive: "
                  f"{rec:.3f}")
        if upsert or delete or compact:
            idx, scores = _mutation_lifecycle(
                index_dir, server, q_emb, params, cfg, seed,
                upsert=upsert, delete=delete, compact=compact)
        if serve_loop:
            # Runs LAST so the loop fronts the server's final state
            # (mutated view or compacted epoch included).
            _serve_loop_leg(server, q_emb,
                            flush_ms=flush_ms, max_batch=max_batch)
    return idx, scores


def _serve_loop_leg(server, q_emb, *, flush_ms, max_batch):
    """The --serve-loop demo: concurrent client threads stream single
    queries through a :class:`repro.serve.loop.ServeLoop` while its
    dispatcher micro-batches them into the autotuner's pow2 shapes —
    and one epoch swap lands mid-run.  The swap re-serves the same
    corpus state under a new generation, so it is observable ONLY in
    ``epoch_key`` (closures and cached results drop, answers stay
    bitwise identical); the leg then asserts every streamed answer is
    bit-equal to the serial oracle and prints the grep-able parity
    line smoke.sh gates on."""
    from repro.serve.loop import ServeLoop
    q = np.asarray(q_emb)
    n = q.shape[0]
    oracle = server.query_batch(jnp.asarray(q))
    key0 = server.epoch_key
    results = [None] * n
    errors = []

    def client(lo, hi):
        try:
            for i in range(lo, hi):
                results[i] = sl.query(q[i])
        except Exception as e:
            errors.append(e)

    t0 = time.time()
    with ServeLoop(server, flush_ms=flush_ms, max_batch=max_batch) as sl:
        n_clients = max(1, min(4, n))
        bounds = [round(c * n / n_clients) for c in range(n_clients + 1)]
        threads = [threading.Thread(target=client, name=f"loop-client-{c}",
                                    args=(bounds[c], bounds[c + 1]))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        # Mid-run epoch swap: the server's write gate drains in-flight
        # flushes first, so the swap lands strictly between batches and
        # no closure compiled under the old generation ever answers a
        # post-swap query.
        sl.swap_index(server.index, mutation=server._mutation,
                      routing=server.routing)
        for t in threads:
            t.join()
    dt = time.time() - t0
    if errors:
        raise errors[0]
    snap = sl.stats.snapshot()
    wait_ms = 1e3 * snap["queue_wait_s"] / max(snap["queries"], 1)
    pre = sum(1 for r in results if r.epoch_key == key0)
    keys = sorted({r.epoch_key for r in results})
    parity = all(
        np.array_equal(np.asarray(r.top_idx), np.asarray(oracle.top_idx[i]))
        and np.array_equal(np.asarray(r.top_scores),
                           np.asarray(oracle.top_scores[i]))
        for i, r in enumerate(results))
    print(f"[serve] loop: {n} queries / {n_clients} clients in "
          f"{dt*1e3:.1f} ms (flush_ms={flush_ms}, max_batch={max_batch})")
    print(f"[serve] loop stats: flushes={snap['flushes']} "
          f"batches={snap['batches']} cache_hits={snap['cache_hits']} "
          f"padded_rows={snap['padded_rows']} "
          f"p50={snap['p50_latency_s']*1e3:.2f} ms "
          f"p99={snap['p99_latency_s']*1e3:.2f} ms "
          f"queue_wait={wait_ms:.2f} ms/query "
          f"closure_builds={server.closure_builds} "
          f"closure_hits={server.closure_hits}")
    print(f"[serve] loop epoch swap mid-run: {pre} answers pre-swap, "
          f"{n - pre} post-swap (epoch keys {keys})")
    print(f"[serve] loop parity vs serial: {parity}")
    if not parity:
        raise RuntimeError("serve-loop answers diverged from the serial "
                           "oracle (bitwise parity contract)")


def _mutation_lifecycle(index_dir, server, q_emb, params, cfg, seed, *,
                        upsert, delete, compact):
    """The live-mutation demo leg: durable upsert/delete against the
    artifact, serve the delta-log view beside the base epoch, then
    (optionally) compact to the next epoch and verify the swap served
    bit-identical results.  Single-process by design — compaction IS
    the redeploy path for sharded/grid serving."""
    if upsert:
        base_n = index_io.load_index(index_dir).n_docs
        new_ids = list(range(base_n, base_n + upsert))
        docs = synthetic.token_corpus(seed + 1, n_docs=upsert, n_q=1,
                                      vocab=cfg.vocab, m=cfg.doc_len,
                                      l=cfg.query_len)
        n_emb, n_mask = encode_corpus(params, cfg, docs.doc_ids)
        delta_id = mutation_lib.append_upsert(
            index_dir, np.asarray(n_emb), np.asarray(n_mask), new_ids)
        print(f"[serve] upserted {upsert} docs "
              f"(delta {delta_id}, ids {new_ids[0]}..{new_ids[-1]})")
    if delete:
        mutation_lib.append_delete(index_dir, delete)
        print(f"[serve] tombstoned doc ids {sorted(delete)}")
    log = mutation_lib.load_state(index_dir)
    server.swap_index(log.base, mutation=log.view())
    idx, scores = server.query_batch(q_emb)
    print(f"[serve] serving live mutation view: {len(log.deltas)} "
          f"delta(s), {len(log.tombstones)} tombstone(s), "
          f"n_live={log.n_live}")
    if compact:
        # Eager exact-route reference BEFORE the swap: the bitwise
        # parity law compares eager against eager (the server's
        # whole-program jit may fuse the delta scorer with 1-ulp
        # different rounding than the eager composition).
        ri, rv = topk_search(log.base, q_emb, k=server.k,
                             backend=server.backend,
                             mutation=log.view())
        t0 = time.time()
        new_index = mutation_lib.Compactor(index_dir).run()
        dt = time.time() - t0
        if new_index is None:
            print("[serve] nothing to compact")
            return idx, scores
        reloaded = index_io.load_index(index_dir)
        server.swap_index(reloaded)
        idx2, scores2 = server.query_batch(q_emb)
        # Parity is checked on the SAME route the mutated view served —
        # the e2e exact sweep (the server may route two-stage after the
        # swap once n_first < n_docs again, a different, approximate
        # dataflow).  Exact for compression="none"; int8 requantizes on
        # compaction, so there parity is approximate by construction.
        pi, pv = topk_search(reloaded, q_emb, k=server.k,
                             backend=server.backend)
        parity = bool(jnp.array_equal(ri, pi)
                      and jnp.array_equal(rv, pv))
        orphans = index_io.list_orphans(index_dir)
        print(f"[serve] compacted to epoch {reloaded.epoch} in "
              f"{dt*1e3:.1f} ms; post-compact parity: {parity}; "
              f"orphans: {len(orphans)}")
        idx, scores = idx2, scores2
    return idx, scores


def serve_lm(arch: str, n_tokens: int = 32, batch: int = 2):
    cfg = configs.get(arch).smoke
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    cache = tfm.init_cache(cfg, batch, n_tokens)
    step = jax.jit(lambda p, c, t, s: tfm.decode_step(p, c, t, s, cfg))
    tok = jnp.zeros((batch, 1), jnp.int32)
    t0 = time.time()
    outs = []
    for s in range(n_tokens):
        logits, cache = step(params, cache, tok, jnp.int32(s))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs.append(tok[:, 0])
    dt = time.time() - t0
    print(f"[serve] decoded {n_tokens} tokens x {batch} seqs "
          f"in {dt:.2f}s ({dt/n_tokens*1e3:.1f} ms/token)")
    return jnp.stack(outs, axis=1)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="repro.launch.serve")
    ap.add_argument("--arch", default="colbert",
                    help="a retrieval-family arch (colbert, "
                         "gte-moderncolbert) serves the late-interaction "
                         "stack; any other registered arch decodes an LM")
    ap.add_argument("--preset", default="smoke", choices=list(PRESETS),
                    help="encoder width: 'smoke' (the arch's CPU-sized "
                         "config) or 'full' (its published config, e.g. "
                         "colbert 12L/768, out_dim 128, doc_len 180; "
                         "random weights from the seed)")
    ap.add_argument("--n-docs", type=int, default=None,
                    help="synthetic corpus size (default: 256 for "
                         "--preset smoke, 4096 for full)")
    ap.add_argument("--keep", type=float, default=0.5)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--backend", default=None,
                    choices=list(backend_lib.BACKENDS),
                    help="pruning/scoring path: 'reference' for both, "
                         "'shortlist_topk' for pruning, 'fused' for "
                         "serving; the other path keeps its default "
                         "(shortlist_topk pruning + fused serving on "
                         "TPU, reference elsewhere; see repro.core.backend)")
    ap.add_argument("--index-dir", default=None,
                    help="packed-index artifact directory: load and serve "
                         "if one exists there, else prune -> pack -> save "
                         "it first (repro.serve.index_io)")
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "residual"],
                    help="token compression when packing a new index; "
                         "'residual' stores each kept token as a centroid "
                         "id + --residual-bits quantized residual, decoded "
                         "inside the scoring kernels (never materialized "
                         "fp32 in HBM)")
    ap.add_argument("--residual-bits", type=int, default=4, choices=[2, 4],
                    help="bits per residual dimension for --compress "
                         "residual (ignored otherwise)")
    ap.add_argument("--pool-threshold", type=float, default=0.0,
                    help="merge kept tokens within a doc whose cosine "
                         "similarity meets this threshold before packing "
                         "(token pooling; 0 disables)")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "host", "grid"],
                    help="'host': shard serving over every local device "
                         "(candidates axis; streaming top-k merge under "
                         "sharding.serve_rules).  'grid': the multi-host "
                         "placement layout — a hosts x candidates device "
                         "grid, capacity buckets pinned to host groups "
                         "(PlacementPlan), per-group merge + cross-group "
                         "candidate exchange; pruning shards over data")
    ap.add_argument("--hosts", type=int, default=0,
                    help="host-group count for --mesh grid (0 = auto: "
                         "largest pow2 grid the device count supports)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica count for --mesh grid placement: each "
                         "capacity bucket is stored on this many distinct "
                         "host groups (a replica chain, primary first), so "
                         "losing any replicas-1 groups still serves exact, "
                         "full-coverage results; clamped to --hosts")
    ap.add_argument("--on-group-loss", default="degrade",
                    choices=["degrade", "rebalance", "fail"],
                    help="policy when every replica of some bucket is "
                         "unreachable: 'degrade' answers from surviving "
                         "buckets and reports coverage < 1, 'rebalance' "
                         "re-places lost buckets over surviving groups "
                         "(PlacementPlan.rebalance) and re-answers at full "
                         "coverage, 'fail' raises DegradedCoverage")
    ap.add_argument("--kill-group", type=int, default=None,
                    help="fault injection: demote this host group before "
                         "the query batch (demo of the failover / "
                         "degraded-coverage path; needs --mesh grid)")
    ap.add_argument("--n-first", type=int, default=64,
                    help="first-stage candidate count; >= corpus size "
                         "(or 0) serves the e2e exact sweep — the route "
                         "the sharded streaming merge runs on")
    ap.add_argument("--upsert", type=int, default=0,
                    help="durably upsert this many freshly encoded docs "
                         "into the artifact as a WAL-logged delta bucket "
                         "set, then serve the mutated view "
                         "(repro.serve.mutation; needs --index-dir)")
    ap.add_argument("--delete", default=None,
                    help="comma-separated doc ids to durably tombstone "
                         "(WAL intent -> atomic tombstone set -> commit; "
                         "needs --index-dir)")
    ap.add_argument("--route", default="exhaustive",
                    choices=["exhaustive", "bounded", "nprobe"],
                    help="candidate routing mode (repro.serve.routing): "
                         "'exhaustive' scores every capacity bucket; "
                         "'nprobe' scores only the --nprobe best buckets "
                         "per query by centroid MaxSim; 'bounded' keeps "
                         "every bucket whose provable score upper bound "
                         "clears the shortlist threshold — exact results, "
                         "fewer buckets.  Routed modes need --index-dir "
                         "(the routing table is an artifact sidecar)")
    ap.add_argument("--nprobe", type=int, default=1,
                    help="buckets to score per query under --route "
                         "nprobe (and the seed width for --route "
                         "bounded); must be >= 1")
    ap.add_argument("--centroids-per-bucket", type=int, default=4,
                    dest="centroids",
                    help="k-means centroids per capacity bucket when "
                         "building a new routing table (ignored with a "
                         "WARNING when the artifact already carries one)")
    ap.add_argument("--serve-loop", action="store_true",
                    help="after the batch serve, run the concurrent "
                         "micro-batched serving loop (repro.serve.loop."
                         "ServeLoop): client threads stream single "
                         "queries, the dispatcher flushes pow2 "
                         "micro-batches, one epoch swap lands mid-run, "
                         "and every answer is checked bitwise against "
                         "the serial oracle")
    ap.add_argument("--flush-ms", type=float, default=2.0,
                    help="serve-loop flush deadline in milliseconds: a "
                         "micro-batch dispatches when this much time "
                         "passed since its oldest query (or --max-batch "
                         "filled, whichever first)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="serve-loop micro-batch cap: flush immediately "
                         "once this many queries are waiting")
    ap.add_argument("--compact", action="store_true",
                    help="fold the artifact's delta log into the next "
                         "epoch (background-compaction path: new epoch "
                         "written beside the live one, committed by one "
                         "atomic manifest swap) and re-serve from it")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    """Parse + validate.  Config contradictions die HERE, at parse
    time, with an argparse usage error — not minutes later as a warning
    buried in serve-time logs after devices spun up (tested directly in
    tests/test_serve_cli.py)."""
    ap = build_parser()
    args = ap.parse_args(argv)
    retrieval = is_retrieval(args.arch)
    if args.kill_group is not None and args.mesh != "grid":
        ap.error(f"--kill-group {args.kill_group} requires --mesh grid: "
                 "fault injection demotes a host group of the grid "
                 "placement, and no other mesh has host groups")
    if args.replicas > 1 and args.mesh == "none":
        ap.error(f"--replicas {args.replicas} requires a serving mesh: "
                 "replica chains place buckets across host groups "
                 "(--mesh grid); unsharded serving has nowhere to "
                 "replicate to")
    if args.upsert < 0:
        ap.error(f"--upsert {args.upsert} must be >= 0")
    if args.n_docs is not None and args.n_docs < 1:
        ap.error(f"--n-docs {args.n_docs} must be >= 1")
    if not retrieval and (args.preset != "smoke"
                          or args.n_docs is not None):
        ap.error(f"--preset/--n-docs size the retrieval corpus; --arch "
                 f"{args.arch} decodes an LM at its smoke config")
    if args.delete is not None:
        try:
            args.delete = tuple(int(x) for x in args.delete.split(",")
                                if x.strip())
        except ValueError:
            ap.error(f"--delete expects comma-separated integer doc "
                     f"ids, got {args.delete!r}")
    else:
        args.delete = ()
    mutating = bool(args.upsert or args.delete or args.compact)
    if mutating and not args.index_dir:
        ap.error("--upsert/--delete/--compact mutate a persisted "
                 "artifact; set --index-dir")
    if mutating and args.mesh == "grid":
        ap.error("mutation serving is single-process; run --compact to "
                 "fold the delta log into a fresh epoch before serving "
                 "it under --mesh grid")
    if args.pool_threshold and not 0.0 < args.pool_threshold <= 1.0:
        ap.error(f"--pool-threshold {args.pool_threshold} must be in "
                 "(0, 1]: it is a cosine-similarity merge cutoff "
                 "(0 disables pooling)")
    if args.nprobe < 1:
        ap.error(f"--nprobe {args.nprobe} must be >= 1: the router "
                 "always scores at least the best bucket per query")
    if args.centroids < 1:
        ap.error(f"--centroids-per-bucket {args.centroids} must be >= 1")
    if args.route != "exhaustive" and not args.index_dir:
        ap.error(f"--route {args.route} needs --index-dir: the routing "
                 "table is a sidecar of a persisted artifact "
                 "(repro.serve.index_io.save_routing)")
    if not args.serve_loop:
        if args.flush_ms != 2.0:
            ap.error(f"--flush-ms {args.flush_ms} only applies to the "
                     "micro-batched serving loop; set --serve-loop")
        if args.max_batch != 8:
            ap.error(f"--max-batch {args.max_batch} only applies to the "
                     "micro-batched serving loop; set --serve-loop")
    else:
        if args.flush_ms < 0:
            ap.error(f"--flush-ms {args.flush_ms} must be >= 0 (0 means "
                     "flush whatever arrived with the first query)")
        if args.max_batch < 1:
            ap.error(f"--max-batch {args.max_batch} must be >= 1: a "
                     "flush serves at least one query")
        if not retrieval:
            ap.error(f"--serve-loop serves the late-interaction retrieval "
                     f"stack; --arch {args.arch} decodes an LM")
    if args.route != "exhaustive" and mutating:
        ap.error(f"--route {args.route} with --upsert/--delete/--compact "
                 "is not supported by this driver: the mutation demo "
                 "swaps served views mid-run, and routed swaps require "
                 "the matching epoch's routing table (the library "
                 "handles this — serve the mutated view exhaustively, "
                 "or compact first and serve the new epoch routed)")
    return args


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    if is_retrieval(args.arch):
        serve_retrieval(keep_fraction=args.keep, preset=args.preset,
                        n_docs=args.n_docs, arch=args.arch,
                        ckpt_dir=args.ckpt_dir,
                        backend=args.backend, index_dir=args.index_dir,
                        compress=args.compress,
                        residual_bits=args.residual_bits,
                        pool_threshold=args.pool_threshold,
                        mesh=args.mesh,
                        n_first=args.n_first, hosts=args.hosts,
                        replicas=args.replicas,
                        on_group_loss=args.on_group_loss,
                        kill_group=args.kill_group,
                        upsert=args.upsert, delete=args.delete,
                        compact=args.compact, route=args.route,
                        n_probe=args.nprobe, centroids=args.centroids,
                        serve_loop=args.serve_loop,
                        flush_ms=args.flush_ms, max_batch=args.max_batch)
    else:
        serve_lm(args.arch, n_tokens=args.tokens)


if __name__ == "__main__":
    main()
