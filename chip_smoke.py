"""Chip smoke test: the full-width prune-and-serve path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the --mesh grid path on a 2x2 grid

One chip: the published ColBERT encoder (12L/768, out_dim 128, doc_len
180; random weights from ``--seed``) encodes a synthetic corpus, the
platform-default backend Voronoi-prunes it to half its tokens, the
kept tokens are packed twice (fp32 and the 4-bit residual codec), and
a ``RetrievalServer`` on the ``fused`` backend answers query batches,
then ``ServeLoop`` answers single queries.  Every answer is checked
against a plain exhaustive MaxSim top-k over the same stored tokens,
computed in fp32 at the highest matmul precision.

Four chips: a packed fp32 index of seeded unit vectors at the same
widths is pinned to 2 host groups of a 2x2 device grid and served
through the grid placement path; its answers are checked against the
single-device answer and the same exhaustive reference.

Informational lines come first (timings among them are not benchmark
numbers).  The last line is ``{"ok": true, "device": {...}}``; the
script exits non-zero without it when no TPU is found, ``REPRO_BACKEND``
is set, a backend resolves to ``reference``, a kernel would run in the
Pallas interpreter, or any check fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import backend as backend_lib  # noqa: E402
from repro.core import voronoi  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.launch import compile_cache, serve  # noqa: E402
from repro.launch.mesh import default_serve_hosts  # noqa: E402
from repro.serve.index import PackedIndex  # noqa: E402
from repro.serve.loop import ServeLoop  # noqa: E402
from repro.serve.retrieval import (RetrievalServer, topk_search,  # noqa: E402
                                   topk_search_group)
from repro.sharding import axis_rules  # noqa: E402

K = 10
RECALL_MIN = 0.99
EPS_F32 = 2.0 ** -24


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def info(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    if os.environ.get("REPRO_BACKEND"):
        fail(f"REPRO_BACKEND={os.environ['REPRO_BACKEND']} is set; the "
             "smoke test runs the platform defaults")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU chips, found {len(devices)}")
    if backend_lib.default_interpret(None):
        fail("Pallas kernels would run in interpret mode")
    info(f"device: {devices[0].device_kind} x {len(devices)}")
    return devices


def score_tol(l: int, dim: int, ref):
    """Worst-case fp32 error of a MaxSim score: each of the ``l`` token
    maxima is a ``dim``-term dot of vectors of norm ~1 (error <= dim*eps
    relative to its magnitude), and summing ``l`` of them adds
    ``l*eps*|score|``."""
    return l * (dim + l) * EPS_F32 * (1.0 + np.abs(ref))


def exhaustive_topk(q, docs, masks, k: int):
    """Plain exhaustive MaxSim top-k: q (n_q, l, dim), docs (n, m, dim)
    f32, masks (n, m) -> (ids, scores) of shape (n_q, k), fp32 at the
    highest matmul precision.  Docs are scored 256 at a time."""
    chunk = 256
    with jax.default_matmul_precision("highest"):
        score = jax.jit(lambda q, d, mk: jnp.where(
            mk[None, :, None, :], jnp.einsum("qld,nmd->qnlm", q, d),
            -jnp.inf).max(-1).sum(-1))
        parts = [score(q, docs[lo:lo + chunk], masks[lo:lo + chunk])
                 for lo in range(0, docs.shape[0], chunk)]
    s, i = jax.lax.top_k(jnp.concatenate(parts, axis=1), k)
    return np.asarray(i), np.asarray(s)


def stored_tokens(packed: PackedIndex):
    """The tokens a packed index stores, decoded and laid back out per
    document: (n_docs, cap_max, dim) f32 and (n_docs, cap_max) masks."""
    n, cap, dim = packed.n_docs, packed.cap_max, packed.dim
    docs = np.zeros((n, cap, dim), np.float32)
    masks = np.zeros((n, cap), bool)
    for b in packed.buckets:
        ids = np.asarray(b.doc_ids)
        docs[ids, :b.cap] = np.asarray(b.dense_embs(dim), np.float32)
        masks[ids, :b.cap] = np.asarray(b.masks)
    return jnp.asarray(docs), jnp.asarray(masks)


def check_answers(name, ids, scores, ref_ids, ref_scores, l, dim,
                  against="exhaustive fp32 reference"):
    """recall@k of ``ids`` against the reference and the score error of
    each reference doc it returned; fails below the bounds."""
    ids, scores = np.asarray(ids), np.asarray(scores)
    if ids.shape != ref_ids.shape or not np.isfinite(scores).all():
        fail(f"{name}: answer shape {ids.shape} (want {ref_ids.shape}) "
             f"or non-finite scores")
    hits, worst = 0, 0.0
    for i, s, ri, rs in zip(ids, scores, ref_ids, ref_scores):
        ref = dict(zip(ri.tolist(), rs.tolist()))
        for doc, sc in zip(i.tolist(), s.tolist()):
            if doc in ref:
                hits += 1
                err = abs(sc - ref[doc]) / score_tol(l, dim, ref[doc])
                worst = max(worst, float(err))
    recall = hits / ref_ids.size
    info(f"{name}: recall@{ref_ids.shape[1]} {recall:.4f} vs {against}; "
         f"worst score error {worst:.3g} of the bound")
    if recall < RECALL_MIN:
        fail(f"{name}: recall {recall:.4f} < {RECALL_MIN}")
    if worst > 1.0:
        fail(f"{name}: score error {worst:.3g}x the fp32 bound")


def serve_and_check(name, packed, q_batches, ref, l, dim, *, loop_q=None):
    """Serve ``q_batches`` through a ``RetrievalServer`` (and ``loop_q``
    single queries through a ``ServeLoop`` on it); check each answer."""
    server = RetrievalServer(packed, k=K, n_first=packed.n_docs)
    if server.backend != backend_lib.FUSED:
        fail(f"{name}: serving backend resolved to {server.backend}")
    if "tpu_custom_call" not in server.lowered_text(q_batches[0]):
        fail(f"{name}: the serving program holds no compiled kernel "
             "(tpu_custom_call)")
    info(f"{name}: serving backend {server.backend}; serving program "
         "contains tpu_custom_call")
    n_q = q_batches[0].shape[0]
    t0 = time.perf_counter()
    out = [server.query_batch(q_batches[0])]
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out += [server.query_batch(q) for q in q_batches[1:]]
    per_batch = (time.perf_counter() - t0) / max(len(q_batches) - 1, 1)
    info(f"{name}: first batch (compile + run) {compile_s:.2f} s; then "
         f"{per_batch * 1e3:.2f} ms per {n_q}-query batch "
         "(informational)")
    ids = np.concatenate([o.top_idx for o in out])
    scores = np.concatenate([o.top_scores for o in out])
    n = ids.shape[0]
    check_answers(f"{name} query_batch", ids, scores, ref[0][:n],
                  ref[1][:n], l, dim)
    if loop_q is None:
        return
    results = [None] * len(loop_q)
    errors = []

    def client(rows):
        try:
            for r in rows:
                results[r] = sl.query(loop_q[r])
        except Exception as e:   # re-raised below, after the join
            errors.append(e)

    t0 = time.perf_counter()
    with ServeLoop(server, flush_ms=5.0, max_batch=8) as sl:
        threads = [threading.Thread(target=client,
                                    args=(range(c, len(loop_q), 4),))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    if errors:
        raise errors[0]
    snap = sl.stats.snapshot()
    info(f"{name} ServeLoop: {len(loop_q)} single queries, 4 clients, "
         f"{snap['flushes']} flushes in {time.perf_counter() - t0:.2f} s "
         "(informational)")
    m = len(loop_q)
    check_answers(f"{name} ServeLoop",
                  np.stack([r.top_idx for r in results]),
                  np.stack([r.top_scores for r in results]),
                  ref[0][n:n + m], ref[1][n:n + m], l, dim)


def one_chip(args):
    cfg, params = serve.load_model("full", args.seed)
    info(f"config: {cfg.name} {cfg.n_layers}L/{cfg.d_model} d_ff "
         f"{cfg.d_ff} out_dim {cfg.out_dim} query_len {cfg.query_len} "
         f"doc_len {cfg.doc_len}; n_docs {args.n_docs}")
    prune_backend = voronoi.resolve_pruning_backend(None)
    serve_backend = backend_lib.resolve_backend(
        None, allow=backend_lib.SERVING)
    info(f"backends: pruning {prune_backend}, serving {serve_backend}")
    if backend_lib.REFERENCE in (prune_backend, serve_backend):
        fail("a backend resolved to reference")

    n_batches, batch, n_loop = 4, 16, 32
    corpus = synthetic.token_corpus(
        args.seed, n_docs=args.n_docs, n_q=n_batches * batch + n_loop,
        vocab=cfg.vocab, m=cfg.doc_len, l=cfg.query_len)
    t0 = time.perf_counter()
    d_emb, d_mask = serve.encode_corpus(params, cfg, corpus.doc_ids)
    d_emb.block_until_ready()
    info(f"encoded {args.n_docs} docs in {time.perf_counter() - t0:.2f} s "
         "(informational)")
    t0 = time.perf_counter()
    pruned = serve.prune_index(d_emb, d_mask, 0.5,
                               n_samples=serve.n_samples_for("full"))
    st = pruned.storage()
    info(f"pruned with {serve.n_samples_for('full')} samples in "
         f"{time.perf_counter() - t0:.2f} s: {st['tokens_kept']} of "
         f"{st['tokens_total']} tokens kept (informational)")
    q = serve.encode_queries(params, cfg, corpus.q_ids)
    q_batches = [q[i * batch:(i + 1) * batch] for i in range(n_batches)]
    loop_q = np.asarray(q[n_batches * batch:])
    l, dim = q.shape[1:]

    for name, kw in (("fp32", {}), ("residual", {"compression": "residual",
                                                  "residual_bits": 4})):
        packed = pruned.pack(**kw)
        info(f"{name} index: {packed.storage()['bytes_stored']} bytes "
             f"stored in {len(packed.buckets)} buckets")
        docs, masks = stored_tokens(packed)
        ref = exhaustive_topk(q, docs, masks, K)
        serve_and_check(name, packed, q_batches, ref, l, dim,
                        loop_q=loop_q if name == "fp32" else None)


def four_chips(args):
    hosts = default_serve_hosts()
    if hosts < 2:
        fail(f"{len(jax.devices())} devices form no grid of host groups")
    cfg = configs.get("colbert").config
    m, l, dim = cfg.doc_len, cfg.query_len, cfg.out_dim
    info(f"config: {cfg.name} widths out_dim {dim} query_len {l} doc_len "
         f"{m}; n_docs {args.n_docs}, seeded unit-vector tokens")
    key = jax.random.PRNGKey(args.seed)
    d = jax.random.normal(key, (args.n_docs, m, dim))
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    n_real = jax.random.randint(jax.random.fold_in(key, 1), (args.n_docs,),
                                m // 4, m + 1)
    masks = jnp.arange(m)[None] < n_real[:, None]
    keep = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5, masks.shape)
    packed = PackedIndex.pack(d, masks, keep)
    q = jax.random.normal(jax.random.fold_in(key, 3), (32, l, dim))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    info(f"fp32 index: {packed.storage()['bytes_stored']} bytes stored in "
         f"{len(packed.buckets)} buckets")

    single = topk_search(packed, q, k=K)
    info(f"single-device answer on {jax.devices()[0]}")
    rules, monitor = serve.grid_rules(packed, hosts)
    with axis_rules(rules):
        server = RetrievalServer(packed, k=K, n_first=packed.n_docs,
                                 monitor=monitor)
        if server.backend != backend_lib.FUSED:
            fail(f"grid serving backend resolved to {server.backend}")
        for g in range(hosts):
            text = jax.jit(lambda qq, g=g: topk_search_group(
                packed, qq, group=g, k=K)).lower(q).as_text()
            if "tpu_custom_call" not in text:
                fail(f"grid group {g}: no compiled kernel (tpu_custom_call)")
        info(f"grid: backend {server.backend}; every group program "
             "contains tpu_custom_call")
        t0 = time.perf_counter()
        out = server.query_batch(q)
        info(f"grid: first batch (compile + run) "
             f"{time.perf_counter() - t0:.2f} s (informational)")
        t0 = time.perf_counter()
        out = server.query_batch(q)
        info(f"grid: {(time.perf_counter() - t0) * 1e3:.2f} ms per "
             f"{q.shape[0]}-query batch (informational)")
    if out.coverage != 1.0 or monitor.demoted:
        fail(f"grid coverage {out.coverage}, demoted {monitor.demoted}")
    info(f"grid: coverage {out.coverage}, {hosts} host groups live")
    docs, dmasks = stored_tokens(packed)
    ref = exhaustive_topk(q, docs, dmasks, K)
    check_answers("single-device", single[0], single[1], *ref, l, dim)
    check_answers("grid", out.top_idx, out.top_scores, *ref, l, dim)
    check_answers("grid", out.top_idx, out.top_scores,
                  np.asarray(single[0]), np.asarray(single[1]), l, dim,
                  against="single-device answer")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the grid placement path on 4 chips and "
                         "the single-device answer it is compared with")
    ap.add_argument("--n-docs", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)
    info(f"compile cache: {compile_cache.enable()}")
    (four_chips if args.four_chips else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
