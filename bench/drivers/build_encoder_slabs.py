"""Index build with a published encoder: back-to-back slabs of documents
drawn from the seed, each encoded through the program's own entry
(``launch.serve.encode_corpus``), Voronoi-pruned (``prune_corpus``) and
packed (``PackedIndex.pack``).

The slab loop is ``build_slabs``'s: the same count of documents in each
length range every slab, so the warm-up slab compiles every shape, and
the rate is the documents of completed slabs over the time to the end of
the last one.  The loop is a copy, so a change to ``build_slabs``' loop
(warm-up, window, trace, traced work, check) is made here too.  The configuration's ``model`` holds every key of the
program's ``ColBERTConfig``, which this driver builds, with the weights,
in place of the ones ``bench/run.py`` made for the default backbone.

The check is ``build_slabs``'s (keep masks against the plain Voronoi
reference, packed tokens against the kept ones) plus the encoder's:
``encode_gap``, the largest ``1 - cos`` over every real token of the
sampled slabs between the embedding the window produced and the plain
fp32 reference's (``benchlib.reference_modernbert``).  In a control run
the references one precision below the configuration's answer in the
program's place for both.  The program's counter of encoded slots
(``launch.serve.EncodeStats``) over the window is returned as
``encode_real_tokens`` and ``encode_slots``; the traced slabs' least
work (``work_modernbert.encoder_flops`` of the real tokens plus
``work.voronoi_least_flops``) is returned as ``traced``.
"""

from __future__ import annotations

import time

import numpy as np

from benchlib import common, inputs, reference, reference_modernbert
from benchlib import work, work_modernbert


def model_config(model: dict):
    """The program's encoder configuration, from every key of the
    configuration file's ``model``."""
    import jax.numpy as jnp
    from repro.models.colbert import ColBERTConfig
    dtype = getattr(jnp, model["dtype"])
    fields = {k: v for k, v in model.items() if k != "dtype"}
    return ColBERTConfig(**fields, param_dtype=dtype, compute_dtype=dtype)


def run(r) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import pruning_pipeline
    from repro.launch import serve
    from repro.serve.index import PackedIndex
    tr, model = r.traffic, r.config["model"]
    r.params = None
    r.cfg = cfg = model_config(model)
    r.params = common.make_weights(cfg, r.config["weights_seed"])
    prune = r.config["prune"]
    law = tr["doc_lengths"]
    edges = [tuple(e) for e in tr["length_ranges"]]
    counts = inputs.range_counts(law, edges, tr["slab_docs"])
    samples = jnp.asarray(inputs.sphere_samples(
        prune["samples_seed"], prune["n_samples"], model["out_dim"]))
    stats = serve.EncodeStats()

    def slab_ids(j):
        rng = inputs.rng_for(r.seed, 3, j)
        return inputs.token_ids(rng, inputs.slab_lengths(rng, law, edges,
                                                         counts),
                                model["doc_len"], model["vocab"],
                                inputs.D_MARK)

    def one_slab(ids):
        n_real = (ids != 0).sum(1)
        flops = sum(work_modernbert.encoder_flops(int(x), model)
                    for x in n_real)
        with r.spans.span("encode", docs=len(ids), flops=flops):
            e, mk = serve.encode_corpus(r.params, cfg, ids, batch=len(ids),
                                        stats=stats)
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
    before = (stats.real_tokens, stats.slots)
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
    counters = {"slabs": len(slabs),
                "encode_real_tokens": stats.real_tokens - before[0],
                "encode_slots": stats.slots - before[1]}

    traced = {}
    if r.traced_s is not None:
        a, b = r.traced_s
        traced = {"flops": traced_work, "seconds": b - a}

    # The check; in a control run the references one precision lower
    # prune and encode in the program's place.
    precision = r.config["precision"]
    pick = inputs.rng_for(r.seed, 2).choice(
        len(slabs), size=min(tr["check_slabs"], len(slabs)), replace=False)
    params = jax.device_get(r.params)
    keep_bad, pack_bad, enc_gap = 0.0, 0, 0.0
    for j in sorted(pick.tolist()):
        e, mk, keep, packed = (np.asarray(jax.device_get(x))
                               if i < 3 else x
                               for i, x in enumerate(slabs[j]))
        stored = reference.stored_arrays(packed)
        pack_bad += reference.pack_mismatch(stored, e, keep)
        ref = reference.keep_reference(e, mk, np.asarray(samples),
                                       prune["keep_fraction"],
                                       precision=precision["prune"])
        ids = slab_ids(j)
        want, _ = reference_modernbert.encode_docs(params, model, ids)
        if r.control:
            keep = reference.keep_reference(
                e, mk, np.asarray(samples), prune["keep_fraction"],
                precision=reference.lower(precision["prune"]))
            e, _ = reference_modernbert.encode_docs(
                params, model, ids,
                precision=reference.lower(precision["encode"]))
        keep_bad = max(keep_bad, reference.keep_mismatch(keep, ref, mk))
        enc_gap = max(enc_gap, reference_modernbert.encode_gap(e, want, mk))
    limits = r.config["limits"]
    checks = [("pack_mismatch", pack_bad, 0),
              ("keep_mismatch", keep_bad, limits["keep_mismatch"]),
              ("encode_gap", enc_gap, limits["encode_gap"])]
    return {"e2e": {"build_docs_per_s": rate, "setup_s": setup_s},
            "attempted": n_docs, "failed": 0, "checks": checks,
            "facts": {"n_samples": prune["n_samples"]},
            "counters": counters, "traced": traced, "memory_peak": peak}
