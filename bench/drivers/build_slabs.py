"""Index build: back-to-back slabs of documents drawn from the seed, each
taken through the encoder, Voronoi pruning (``prune_corpus``) and
``PackedIndex.pack``.

Every slab holds the same count of documents in each power-of-two length
range, so every slab dispatches the same shapes and the warm-up slab
compiles them all.  The rate is the documents of completed slabs over the
time to the end of the last one.  The check runs the plain Voronoi
reference over sampled slabs' encoded tokens and compares the keep masks,
and compares the packed tokens with the program's kept ones."""

from __future__ import annotations

import time

import numpy as np

from benchlib import common, inputs, reference, work


def run(r) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import pruning_pipeline
    from repro.models import colbert
    from repro.serve.index import PackedIndex
    tr, cfg, model = r.traffic, r.cfg, r.config["model"]
    prune = r.config["prune"]
    law = tr["doc_lengths"]
    edges = [tuple(e) for e in tr["length_ranges"]]
    counts = inputs.range_counts(law, edges, tr["slab_docs"])
    samples = jnp.asarray(inputs.sphere_samples(
        prune["samples_seed"], prune["n_samples"], model["out_dim"]))
    enc = jax.jit(lambda p, t: tuple(
        x.astype(jnp.float32) if x.dtype != jnp.bool_ else x
        for x in colbert.encode_docs(p, cfg, t)))

    def slab_ids(j):
        rng = inputs.rng_for(r.seed, 3, j)
        return inputs.token_ids(rng, inputs.slab_lengths(rng, law, edges,
                                                         counts),
                                model["doc_len"], model["vocab"],
                                inputs.D_MARK)

    def one_slab(ids):
        n_real = (ids != 0).sum(1)
        flops = sum(work.encoder_flops(int(x), model) for x in n_real)
        with r.spans.span("encode", docs=len(ids), flops=flops):
            e, mk = enc(r.params, jnp.asarray(ids))
            e.block_until_ready()
        least = sum(work.voronoi_least_flops(int(x), prune["n_samples"],
                                             model["out_dim"])
                    for x in n_real)
        with r.spans.span("prune", docs=len(ids), flops=least):
            keep, _, _ = pruning_pipeline.prune_corpus(
                e, mk, samples, prune["keep_fraction"])
            keep.block_until_ready()
        with r.spans.span("pack", docs=len(ids)):
            packed = PackedIndex.pack(e, mk, keep)
        return e, mk, keep, packed, flops + least

    one_slab(slab_ids(-1))                       # compiles every shape
    # pack() converts each bucket's doc ids to int32 on the device; bucket
    # sizes follow the kept counts, so warm every size a slab can give.
    for n in range(1, tr["slab_docs"] + 1):
        jnp.asarray(np.arange(n), jnp.int32).block_until_ready()
    setup_s = r.setup_done()
    common.info(f"slab: {tr['slab_docs']} docs, per length range {counts}")

    slabs, traced_work = [], 0
    r.counter.armed = True
    t0 = time.perf_counter()
    t_end = t0
    while t_end - t0 < r.seconds or (r.trace and r.traced_s is None):
        j = len(slabs)
        if r.trace and r.traced_s is None and not r.tracing \
                and j >= tr["trace_after_slabs"]:
            r.trace_start()
            trace_stop = j + tr["trace_slabs"]
        out = one_slab(slab_ids(j))
        t_end = time.perf_counter()
        slabs.append(out[:4])
        if r.tracing:
            traced_work += out[4]
            if j + 1 >= trace_stop:
                r.trace_stop()
    r.counter.armed = False
    peak = common.memory_peak(r.devices)
    n_docs = tr["slab_docs"] * len(slabs)
    rate = n_docs / (t_end - t0)
    common.info(f"build: {len(slabs)} slabs, {n_docs} docs in "
                f"{t_end - t0:.3f} s")

    traced = {}
    if r.traced_s is not None:
        a, b = r.traced_s
        traced = {"flops": traced_work, "seconds": b - a}

    # The check; in a control run the reference one precision lower
    # prunes in the program's place.
    precision = r.config["precision"]["prune"]
    pick = inputs.rng_for(r.seed, 2).choice(
        len(slabs), size=min(tr["check_slabs"], len(slabs)), replace=False)
    keep_bad, pack_bad = 0.0, 0
    for j in sorted(pick.tolist()):
        e, mk, keep, packed = (np.asarray(jax.device_get(x))
                               if i < 3 else x
                               for i, x in enumerate(slabs[j]))
        stored = reference.stored_arrays(packed)
        pack_bad += reference.pack_mismatch(stored, e, keep)
        ref = reference.keep_reference(e, mk, np.asarray(samples),
                                       prune["keep_fraction"],
                                       precision=precision)
        if r.control:
            keep = reference.keep_reference(
                e, mk, np.asarray(samples), prune["keep_fraction"],
                precision=reference.lower(precision))
        keep_bad = max(keep_bad, reference.keep_mismatch(keep, ref, mk))
    checks = [("pack_mismatch", pack_bad, 0),
              ("keep_mismatch", keep_bad, r.config["limits"]["keep_mismatch"])]
    return {"e2e": {"build_docs_per_s": rate, "setup_s": setup_s},
            "attempted": n_docs, "failed": 0, "checks": checks,
            "facts": {"n_samples": prune["n_samples"]},
            "counters": {"slabs": len(slabs)}, "traced": traced,
            "memory_peak": peak}
