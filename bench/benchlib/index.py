"""The serving index of a configuration, built once per checkout.

The first run of a serving cell builds the index the way users build it,
with the system's own offline path (``launch.serve.encode_corpus`` ->
``pruning_pipeline.prune_corpus`` -> ``PackedIndex.pack`` ->
``index_io.save_index``), into ``bench/.cache/<config>/``.  Later runs
load it with ``index_io.load_index``.  A ``spec.json`` written last names
what was built; a directory whose spec differs from the configuration, or
that has none (an interrupted build), is built again.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

from benchlib import common, inputs

SPEC = "spec.json"
PRUNE_KEYS = ("model", "weights_seed", "corpus", "prune")


def spec_of(config: dict) -> dict:
    return {k: config[k] for k in ("model", "weights_seed", "corpus",
                                   "prune", "codec")}


def _cached(path: str, spec: dict) -> bool:
    try:
        return common.load_json(os.path.join(path, SPEC)) == spec
    except (OSError, ValueError):
        return False


def build(config: dict, params, cfg, path: str):
    import jax.numpy as jnp
    from repro.core import pruning_pipeline
    from repro.launch import serve
    from repro.serve import index_io
    from repro.serve.index import PackedIndex
    corpus, prune, codec = config["corpus"], config["prune"], config["codec"]
    ids = inputs.corpus_ids(corpus, config["model"])
    t = time.perf_counter()
    d_emb, d_mask = serve.encode_corpus(params, cfg, ids)
    d_emb.block_until_ready()
    common.info(f"index build: encoded {ids.shape[0]} docs in "
                f"{time.perf_counter() - t:.1f} s")
    key = json.dumps({k: config[k] for k in PRUNE_KEYS}, sort_keys=True)
    keep_path = os.path.join(os.path.dirname(path), "keep-" + hashlib.sha1(
        key.encode()).hexdigest()[:16] + ".npy")
    if os.path.exists(keep_path):
        keep = np.load(keep_path)
        common.info(f"index build: keep masks from {keep_path}")
    else:
        samples = inputs.sphere_samples(prune["samples_seed"],
                                        prune["n_samples"],
                                        config["model"]["out_dim"])
        t = time.perf_counter()
        keep, _, _ = pruning_pipeline.prune_corpus(
            d_emb, d_mask, jnp.asarray(samples), prune["keep_fraction"])
        keep = np.asarray(keep)
        common.info(f"index build: pruned in {time.perf_counter() - t:.1f} s")
        os.makedirs(os.path.dirname(keep_path), exist_ok=True)
        np.save(keep_path + ".tmp.npy", keep)
        os.replace(keep_path + ".tmp.npy", keep_path)
    t = time.perf_counter()
    kw = {}
    if codec["compression"] == "residual":
        kw = dict(residual_bits=codec["residual_bits"],
                  n_centroids=codec["n_centroids"], seed=codec["seed"])
    packed = PackedIndex.pack(d_emb, d_mask, keep,
                              compression=codec["compression"], **kw)
    common.info(f"index build: packed in {time.perf_counter() - t:.1f} s: "
                f"{packed.storage()}")
    shutil.rmtree(path, ignore_errors=True)
    index_io.save_index(path, packed)
    with open(os.path.join(path, SPEC), "w") as f:
        json.dump(spec_of(config), f)
    return packed


def load_or_build(config: dict, params, cfg, cache_root: str | None = None):
    """The configuration's packed index, loaded from its artifact."""
    from repro.serve import index_io
    path = os.path.join(cache_root or common.CACHE, config["name"])
    if not _cached(path, spec_of(config)):
        build(config, params, cfg, path)
    return index_io.load_index(path)
