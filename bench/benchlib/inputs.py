"""Inputs drawn from seeds: token ids of documents and queries, Voronoi
sphere samples, open-loop arrival schedules and slab length plans.

Documents follow a log-normal length law capped at the model's
``doc_len`` (a heavy tail around an MS MARCO-like median); token ids are
Zipf-distributed over the vocabulary above the four reserved ids
(0 pad, 1 [Q], 2 [D], 3 [MASK]).
"""

from __future__ import annotations

import math

import numpy as np

PAD, Q_MARK, D_MARK, RESERVED = 0, 1, 2, 4


def rng_for(*words) -> np.random.Generator:
    """A generator keyed by any whole numbers (seeds over 32 bits too)."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) % 2 ** 64 for w in words]))


def lognormal_lengths(rng, n: int, law: dict) -> np.ndarray:
    x = rng.lognormal(math.log(law["median"]), law["sigma"], size=n)
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def token_ids(rng, lengths, width: int, vocab: int, first: int) -> np.ndarray:
    """(n, width) int32 rows: ``first`` then Zipf ids, zero-padded."""
    n = len(lengths)
    body = RESERVED + (rng.zipf(1.25, size=(n, width)) - 1) % (vocab - RESERVED)
    body[:, 0] = first
    body[np.arange(width)[None, :] >= np.asarray(lengths)[:, None]] = PAD
    return body.astype(np.int32)


def corpus_ids(corpus: dict, model: dict) -> np.ndarray:
    """The serving corpus, from its own fixed seed."""
    rng = rng_for(corpus["seed"])
    lens = lognormal_lengths(rng, corpus["n_docs"], corpus["doc_lengths"])
    return token_ids(rng, lens, model["doc_len"], model["vocab"], D_MARK)


def query_ids(rng, n: int, law: dict, model: dict) -> np.ndarray:
    lens = lognormal_lengths(rng, n, law)
    return token_ids(rng, lens, model["query_len"], model["vocab"], Q_MARK)


def sphere_samples(seed: int, n: int, dim: int) -> np.ndarray:
    x = rng_for(seed).standard_normal((n, dim)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def poisson_schedule(rng, rate: float, seconds: float) -> np.ndarray:
    """Send times of an open loop at ``rate`` per second over ``seconds``:
    exactly round(rate * seconds) requests whose gaps are the quantiles
    of the exponential law, in an order drawn from ``rng``.  Every seed
    gets the same set of gaps, so the same work, in another order."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def range_counts(law: dict, edges, n: int) -> list[int]:
    """Documents per length range (lo, hi] for a slab of ``n``: the law's
    mass in each range, rounded so that the counts sum to ``n``."""
    from statistics import NormalDist
    law_cdf = NormalDist(math.log(law["median"]), law["sigma"]).cdf
    # Ranges are contiguous from below the law's minimum up to its
    # maximum; lengths are rounded, and clipped into the end ranges.
    cuts = [law_cdf(math.log(hi + 0.5)) for _, hi in edges]
    mass = np.diff([0.0] + cuts)
    mass[-1] += 1.0 - cuts[-1]
    raw = mass / mass.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def slab_lengths(rng, law: dict, edges, counts) -> np.ndarray:
    """One slab's document lengths: ``counts[i]`` documents drawn from the
    law inside range ``edges[i]`` (by rejection), in shuffled order."""
    out = []
    for (lo, hi), c in zip(edges, counts):
        got = []
        while len(got) < c:
            x = lognormal_lengths(rng, 4 * c + 8, law)
            got.extend(x[(x > lo) & (x <= hi)].tolist())
        out.extend(got[:c])
    out = np.array(out, np.int64)
    rng.shuffle(out)
    return out
