"""Shape-aware autotuner for the kernel-backed hot paths (ROADMAP item).

Every tunable knob of the pruning and serving paths — Pallas tile sizes
(``block_s``/``block_t`` for the ``maxsim_topk`` kernel,
``block_docs``/``block_q`` for the chunked serving sweep) and the
shortlist algorithm's (``shortlist``, ``rescan_every``) pair — used
to be hardcoded defaults at the call sites.  This module picks them
from (problem shape, platform, VMEM budget) instead:

* **heuristic mode** (default): a static table/formula, pure and
  deterministic — same shape bucket in, same :class:`KernelConfig` out.
  Tile sizes are MXU/VPU-aligned and shrunk to fit the VMEM budget;
  the shortlist size balances per-step O(N*K) work against the
  amortized O(N*m / rescan_every) rescan (K ~ sqrt(m), always
  satisfying the exactness bound ``shortlist >= rescan_every + 1``).
* **measured mode** (``measure=True`` or ``REPRO_AUTOTUNE=measure``):
  a one-shot wall-clock race of a small candidate grid on synthetic
  data of the given shape, cached in-process so each (kind, platform,
  shape bucket) pays the measurement exactly once.

The in-process cache also persists (ROADMAP item: offline jobs share
one measurement pass): :func:`dump_cache`/:func:`load_cache` write/read
it as JSON, and the ``REPRO_AUTOTUNE_CACHE`` env var automates both —
the file is loaded lazily before the first :func:`tune` call and
re-dumped (atomic tmp+rename, merging the file's current entries first
so concurrent writers keep each other's measurements) after every
measured race, so a fleet of jobs pointed at one path converges on one
measurement pass per shape bucket.  Entries are keyed on platform, so
one file can carry CPU and TPU tables side by side.

Shapes are bucketed (power-of-two on the sample/doc/query counts, exact
on the per-document axes m/l/dim that determine tile legality) so jit
caches and the measurement cache stay small under ragged workloads.

Consumers reach this module through the backend seam
(``repro.core.backend.tuned``) — ``pruning_order*`` resolves
``block_s``/``block_t``/``shortlist``/``rescan_every`` here when the
caller passes ``None``, and ``maxsim_scores``/``search``/
``RetrievalServer`` do the same for ``block_docs``/``block_q``.
Explicit arguments always win; the autotuner only fills blanks.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time

import jax

__all__ = [
    "KernelConfig",
    "cache_info",
    "clear_cache",
    "dump_cache",
    "heuristic_config",
    "load_cache",
    "pruning_docs_per_block",
    "shape_key",
    "tune",
]

_ENV_VAR = "REPRO_AUTOTUNE"
_CACHE_ENV_VAR = "REPRO_AUTOTUNE_CACHE"
# 2: KernelConfig grew ``chunk_docs`` (streaming top-k serving); format-1
# files load fine (the field defaults), format-2 files refuse old readers.
_CACHE_FORMAT = 2

# Per-core VMEM is ~16 MB on current TPUs; budget half of it so the
# pipelined double-buffering of grid blocks still fits.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024
# The serving kernels' VMEM model (`_tpu_serving_block_docs`) already
# counts double-buffering and in-kernel temporaries, so it is held to
# three quarters of Mosaic's 16 MiB default scoped-VMEM limit on v5e.
SERVING_VMEM_BUDGET = 12 * 1024 * 1024
# Off-TPU the kernels run through the Pallas interpreter: there is no
# VMEM to respect, block buffers live in host cache, and larger blocks
# amortize per-launch interpreter overhead — so the working-set bound is
# LLC-ish instead (measured: block_docs=64 at the 134 MB rerank bench
# shape beats budget-shrunk blocks ~1.5x on CPU).
INTERPRET_WORKING_SET_BUDGET = 64 * 1024 * 1024

KINDS = ("pruning", "serving")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Resolved knobs for one hot-path invocation.

    Pruning consumers read ``block_s``/``block_t`` (kernel tile sizes)
    and ``shortlist``/``rescan_every`` (shortlist schedule); serving
    consumers read ``block_docs``/``block_q``, and the streaming top-k
    path additionally reads ``chunk_docs`` (the doc-axis slab each
    shard scores-then-reduces per merge step).  A single config type
    keeps the backend seam one function wide.
    """

    block_s: int = 256
    block_t: int = 128
    block_docs: int = 8
    block_q: int = 16
    shortlist: int = 8
    rescan_every: int = 7
    chunk_docs: int = 256

    def validate(self) -> "KernelConfig":
        if self.shortlist < self.rescan_every + 1:
            raise ValueError(
                f"invalid config: shortlist={self.shortlist} < "
                f"rescan_every={self.rescan_every} + 1 (exactness bound)")
        for f in dataclasses.fields(self):
            if getattr(self, f.name) < 1:
                raise ValueError(f"invalid config: {f.name} < 1")
        return self


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def shape_key(kind: str, shape: dict, *, platform: str | None = None,
              measured: bool = False) -> tuple:
    """Canonical cache key: kind, platform, mode, bucketed shape.

    Batch-like axes (samples, docs, queries) bucket to powers of two —
    configs are insensitive to small count changes and this keeps the
    cache (and the jit caches keyed on the resulting static args) from
    growing per ragged shape.  Per-item axes (m, l, dim) stay exact:
    they bound tile legality and the shortlist exactness proof.
    Non-integral entries pass through exactly: the candidate router
    keys its score ``threshold`` (a float) into the serving table
    (``backend.tuned_routing_blocks``), and truncating it to int would
    collide distinct thresholds onto one cache entry.  String entries
    pass through too — the serving tables key the compression codec
    tag ("int8", "residual4", ...) so fp32/int8/residual buckets of
    the same shape tune independently.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown tuning kind {kind!r}; one of {KINDS}")
    platform = platform or jax.default_backend()
    bucketed = []
    for name in sorted(shape):
        raw = shape[name]
        v = (raw if isinstance(raw, str)
             else float(raw) if isinstance(raw, float) else int(raw))
        if name in ("n_samples", "n_docs", "n_q"):
            v = _pow2_at_least(max(int(v), 1))
        bucketed.append((name, v))
    return (kind, platform, "measured" if measured else "heuristic",
            tuple(bucketed))


def _pruning_heuristic(shape: dict, platform: str,
                       vmem_budget: int) -> KernelConfig:
    n = int(shape.get("n_samples", 2048))
    m = int(shape.get("m", 128))
    dim = int(shape.get("dim", 128))

    # Kernel tiles: token tile lane-aligned, sample tile shrunk until
    # (samples + tokens + scores) f32 tiles fit the VMEM budget.
    block_t = min(512, max(8, _round_up(min(m, 512), 128)))
    block_s = min(1024, max(8, _round_up(min(n, 256), 8)))
    while block_s > 8 and 4 * (block_s * dim + block_t * dim
                               + block_s * block_t) > vmem_budget:
        block_s //= 2

    # Shortlist schedule: per-step work is O(N*K), the amortized rescan
    # O(N*m / R) with R = K - 1, so K ~ sqrt(m) balances them.  Lane-
    # friendly powers of two; exactness bound K >= R + 1 holds by
    # construction.
    k = _pow2_at_least(max(int(m ** 0.5), 2))
    k = max(4, min(32, k))
    k = min(k, max(m, 2))
    rescan = max(1, k - 1)
    return KernelConfig(block_s=block_s, block_t=block_t,
                        shortlist=k, rescan_every=rescan).validate()


# Device memory one vmapped pruning dispatch may hold on TPU.  The
# shortlist scan keeps ~12 bytes per (sample, token) of every document
# in flight (compiled for v5e at 10k samples x 180 tokens it holds 9.4),
# so a 10k-sample job at width 180 runs 256 documents per dispatch.
PRUNING_HBM_BUDGET = 6 * 1024 ** 3


def pruning_docs_per_block(n_samples: int, width: int,
                           platform: str | None = None) -> int | None:
    """Documents per vmapped pruning dispatch of one ``width``-token
    bucket: on TPU the largest power of two whose working set fits
    :data:`PRUNING_HBM_BUDGET`; ``None`` (the whole bucket in one
    dispatch) elsewhere, where host memory is the bound."""
    if (platform or jax.default_backend()) != "tpu":
        return None
    per_doc = 12 * max(n_samples, 1) * max(width, 1)
    docs = 1
    while 2 * docs * per_doc <= PRUNING_HBM_BUDGET:
        docs *= 2
    return docs


def _tpu_serving_block_docs(n_q: int, m: int, l: int, dim: int,
                            vmem_budget: int) -> int:
    """Largest power-of-two doc block in [8, 128] whose compiled MaxSim
    kernel fits VMEM.  Per doc token (m padded to the kernels' sublane
    tile) the kernel holds the double-buffered f32 doc row, the three
    bf16 parts of a ``Precision.HIGHEST`` matmul operand, and one f32
    score chunk plus its masked copy, ``SCORE_LANES`` (query, token)
    columns wide at most.  The model reproduces, at dim 128, m 32..256
    and n_q 1..32, the largest block that compiles for v5e; the floor
    of 8 is the kernels' sublane tile (``colbert_maxsim.doc_block``)."""
    from repro.kernels.colbert_maxsim.colbert_maxsim import (SCORE_LANES,
                                                              SUBLANES)
    tokens = _round_up(m, SUBLANES)
    lanes = _round_up(min(n_q * l, SCORE_LANES), 128)
    per_token = 4 * (2 * dim + 2 * lanes) + 3 * 2 * dim
    fixed = 2 * 4 * n_q * l * dim
    block_docs = 128
    while block_docs > SUBLANES and (
            fixed + block_docs * tokens * per_token > vmem_budget):
        block_docs //= 2
    return block_docs


def _serving_heuristic(shape: dict, platform: str,
                       vmem_budget: int) -> KernelConfig:
    n_q = int(shape.get("n_q", 16))
    n_docs = int(shape.get("n_docs", 256))
    m = int(shape.get("m", 128))
    l = int(shape.get("l", 32))
    dim = int(shape.get("dim", 128))
    # Streaming top-k callers (repro.serve.retrieval.topk_search) extend
    # the key with the merge fan-in ``k`` and the candidate-axis shard
    # count ``n_shards``: knobs are then sized for the SHARD-LOCAL slice
    # of the bucket, not its global doc count.  Under multi-host bucket
    # placement the host-group count ``n_groups`` joins the key as well
    # (``backend.tuned_streaming_blocks(n_groups=...)``), and under a
    # replicated plan so does ``replicas`` — the heuristic math is
    # already shard-local so it reads only ``n_shards``, but
    # measured-mode entries must not leak between the flat, grid, and
    # replicated-grid layouts.
    k = int(shape.get("k", 0))
    n_shards = max(1, int(shape.get("n_shards", 1)))
    n_local = -(-n_docs // n_shards)

    block_q = min(_pow2_at_least(max(n_q, 1)), 32)
    if platform == "tpu":
        block_docs = _tpu_serving_block_docs(block_q, m, l, dim, vmem_budget)
    else:
        # Doc block: largest power of two whose (docs + queries + scores)
        # f32 tiles fit the budget; bigger blocks amortize kernel
        # launches and feed the MXU larger matmuls.
        block_docs = 128
        while block_docs > 4 and 4 * (block_docs * m * dim
                                      + block_q * l * dim
                                      + block_docs * m * block_q * l
                                      ) > vmem_budget:
            block_docs //= 2
    block_docs = min(block_docs, _pow2_at_least(max(n_local, 1)))

    # Streaming chunk: the doc slab scored-then-reduced per merge step.
    # On TPU the fused path's live state per chunk is only the
    # (n_q, chunk) score strip, so big chunks amortize the per-chunk
    # top-k; off-TPU the reference scorer materializes the
    # (n_q, chunk, l, m) slab, so the chunk shrinks until that slab sits
    # comfortably inside the working-set budget.  Chunks never drop
    # below ~2k (each chunk must feed the merge at least k candidates
    # to keep the fan-in small) nor exceed the shard-local doc count.
    cap = _pow2_at_least(max(n_local, 1))
    if platform == "tpu":
        chunk = min(cap, 2048)
    else:
        chunk = 256
        while chunk > 8 and 4 * n_q * chunk * l * m > vmem_budget // 2:
            chunk //= 2
    chunk = max(chunk, min(_pow2_at_least(max(2 * k, 1)), cap))
    chunk = min(chunk, cap)
    return KernelConfig(block_docs=max(block_docs, 1),
                        block_q=max(block_q, 1),
                        chunk_docs=max(chunk, 1)).validate()


def heuristic_config(kind: str, *, platform: str | None = None,
                     vmem_budget: int | None = None,
                     **shape) -> KernelConfig:
    """Static-table config for (kind, shape, platform).  Pure.

    ``vmem_budget=None`` resolves per platform: the half-VMEM budget on
    TPU (tiles must genuinely fit), the LLC-ish working-set budget
    elsewhere (interpret-mode kernels have no VMEM and bigger blocks
    amortize launch overhead)."""
    platform = platform or jax.default_backend()
    if vmem_budget is None:
        vmem_budget = (INTERPRET_WORKING_SET_BUDGET if platform != "tpu"
                       else SERVING_VMEM_BUDGET if kind == "serving"
                       else DEFAULT_VMEM_BUDGET)
    if kind == "pruning":
        return _pruning_heuristic(shape, platform, vmem_budget)
    if kind == "serving":
        return _serving_heuristic(shape, platform, vmem_budget)
    raise ValueError(f"unknown tuning kind {kind!r}; one of {KINDS}")


# ----------------------------------------------------------------------
# Measured mode: one-shot candidate race, cached in-process.
# ----------------------------------------------------------------------

_CACHE: dict[tuple, KernelConfig] = {}
_env_cache_loaded = False
# One lock for every compound _CACHE mutation (merge loops, measured
# races, bulk loads).  Single-key get/set are GIL-atomic, but two
# threads measuring simultaneously would interleave the
# seed-heuristic/race/store sequence — and two concurrent races would
# also poison each other's wall-clock timings, so the measured branch
# serializes under this lock by design.  RLock because a measured race
# that produced a new entry re-dumps the env cache file from inside the
# locked region.
_CACHE_LOCK = threading.RLock()

# dump_cache file-lock knobs: bounded retry, then the lockfile is
# presumed orphaned (a crashed writer) and broken.
_LOCK_RETRIES = 50
_LOCK_RETRY_S = 0.02


@contextlib.contextmanager
def _file_lock(path: str):
    """``O_EXCL`` lockfile serializing cross-process merge+rename.

    ``atomic_json_dump``'s tmp+rename keeps each write internally
    consistent, but merge-on-dump is read-merge-rename: two processes
    that both read, then both rename, silently drop whichever entries
    only the first writer held.  Holding ``path + ".lock"`` across the
    read AND the rename closes that window.  Acquisition is a bounded
    retry (``_LOCK_RETRIES`` x ``_LOCK_RETRY_S``); on exhaustion the lockfile is
    presumed orphaned by a crashed writer and broken — dropping one
    concurrent writer's entry beats deadlocking every dump forever.
    """
    lock_path = path + ".lock"
    for attempt in range(_LOCK_RETRIES):
        try:
            fd = os.open(lock_path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            break
        except FileExistsError:
            time.sleep(_LOCK_RETRY_S)
    else:
        # Orphaned lock: break it and take over.
        try:
            os.unlink(lock_path)
        except FileNotFoundError:
            pass
        fd = os.open(lock_path, os.O_CREAT | os.O_WRONLY)
    try:
        os.write(fd, str(os.getpid()).encode())
        os.close(fd)
        yield
    finally:
        try:
            os.unlink(lock_path)
        except FileNotFoundError:
            pass


def _key_to_jsonable(key: tuple) -> dict:
    kind, platform, mode, shape = key
    return {"kind": kind, "platform": platform, "mode": mode,
            "shape": [[n, v] for n, v in shape]}


def _key_from_jsonable(d: dict) -> tuple:
    # float shape entries (router threshold keys) roundtrip as floats
    # and string entries (codec tags) as strings; everything else stays
    # int, matching shape_key's canonical form.
    return (str(d["kind"]), str(d["platform"]), str(d["mode"]),
            tuple((str(n), v if isinstance(v, str)
                   else float(v) if isinstance(v, float) else int(v))
                  for n, v in d["shape"]))


def _read_entries(path: str) -> dict[tuple, KernelConfig]:
    """Parse a :func:`dump_cache` file.  Every config is re-validated,
    so a hand-edited file cannot smuggle in an illegal schedule."""
    with open(path) as f:
        payload = json.load(f)
    if payload.get("format", 0) > _CACHE_FORMAT:
        raise IOError(f"{path}: tuning-cache format {payload['format']} is "
                      f"newer than this reader (format {_CACHE_FORMAT})")
    return {_key_from_jsonable(e["key"]): KernelConfig(**e["config"]).validate()
            for e in payload.get("entries", [])}


def dump_cache(path: str, *, merge: bool = True) -> int:
    """Write the in-process tuning cache to ``path`` as JSON (atomic
    tmp+rename).  Returns the number of entries written.

    ``merge=True`` (default) first folds in entries already in the file
    that this process doesn't hold — in-process entries win per key —
    so concurrent writers sharing one file keep each other's
    measurements instead of overwriting the whole file with their local
    view.  The read+merge+rename sequence holds ``path + ".lock"``
    (``O_EXCL``, bounded retry) so two processes dumping concurrently
    cannot interleave read-then-rename and drop each other's entries;
    ``_CACHE_LOCK`` makes the in-process merge atomic against threads
    measuring simultaneously.  ``merge=False`` writes exactly the
    in-process snapshot (e.g. to prune a stale file)."""
    from repro.train.checkpoint import atomic_json_dump
    with _file_lock(path):
        with _CACHE_LOCK:
            if merge and os.path.exists(path):
                for key, cfg in _read_entries(path).items():
                    _CACHE.setdefault(key, cfg)
            payload = {
                "format": _CACHE_FORMAT,
                "entries": [{"key": _key_to_jsonable(k),
                             "config": dataclasses.asdict(v)}
                            for k, v in _CACHE.items()],
            }
        atomic_json_dump(path, payload)
    return len(payload["entries"])


def load_cache(path: str) -> int:
    """Merge a :func:`dump_cache` file into the in-process cache (file
    entries win over in-process ones — the file is the shared
    measurement pass).  Returns the number of entries merged."""
    entries = _read_entries(path)
    with _CACHE_LOCK:
        _CACHE.update(entries)
    return len(entries)


def _maybe_load_env_cache() -> None:
    """Lazy one-shot load of the ``REPRO_AUTOTUNE_CACHE`` file (if the
    env var is set and the file exists) before the first resolution."""
    global _env_cache_loaded
    with _CACHE_LOCK:
        if _env_cache_loaded:
            return
        _env_cache_loaded = True
        path = os.environ.get(_CACHE_ENV_VAR)
        if path and os.path.exists(path):
            load_cache(path)


def _time_once(fn) -> float:
    out = fn()
    jax.block_until_ready(out)           # warmup + compile
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t0


def _measure_pruning(shape: dict, base: KernelConfig) -> KernelConfig:
    """Race the ``shortlist_topk`` scan, the one the chip runs, at
    K in {K/2, K, 2K} with R = K - 1."""
    import jax.numpy as jnp

    from repro.core import voronoi
    from repro.core.sampling import sample_sphere

    n = int(shape.get("n_samples", 2048))
    m = int(shape.get("m", 128))
    dim = int(shape.get("dim", 128))
    key = jax.random.PRNGKey(0)
    d = jax.random.normal(key, (m, dim))
    mask = jnp.ones((m,), bool)
    samples = sample_sphere(jax.random.PRNGKey(1), n, dim)

    ks = sorted({max(2, min(k, m)) for k in
                 (base.shortlist // 2, base.shortlist, base.shortlist * 2)})
    best, best_t = base, float("inf")
    for k in ks:
        cand = dataclasses.replace(base, shortlist=k, rescan_every=k - 1)
        # every knob pinned explicitly: a None would consult the tuner
        # from inside the race (re-entrant on the very key being tuned)
        fn = lambda cand=cand: voronoi.pruning_order_shortlist(
            d, mask, samples, shortlist=cand.shortlist,
            rescan_every=cand.rescan_every, block_s=cand.block_s,
            block_t=cand.block_t)[0]
        t = _time_once(fn)
        if t < best_t:
            best, best_t = cand, t
    return best


def _measure_serving(shape: dict, base: KernelConfig) -> KernelConfig:
    import jax.numpy as jnp

    from repro.serve import retrieval

    n_q = int(shape.get("n_q", 16))
    n_docs = int(shape.get("n_docs", 256))
    m = int(shape.get("m", 128))
    l = int(shape.get("l", 32))
    dim = int(shape.get("dim", 128))
    key = jax.random.PRNGKey(0)
    d = jax.random.normal(key, (n_docs, m, dim))
    masks = jnp.ones((n_docs, m), bool)
    q = jax.random.normal(jax.random.fold_in(key, 1), (n_q, l, dim))
    index = retrieval.TokenIndex.build(d, masks)

    cands = sorted({max(1, min(bd, n_docs)) for bd in
                    (base.block_docs // 2, base.block_docs,
                     base.block_docs * 2)})
    best, best_t = base, float("inf")
    for bd in cands:
        cand = dataclasses.replace(base, block_docs=bd)
        fn = lambda cand=cand: retrieval.maxsim_scores(
            index, q, backend="fused", block_docs=cand.block_docs,
            block_q=cand.block_q)
        t = _time_once(fn)
        if t < best_t:
            best, best_t = cand, t
    return best


def tune(kind: str, *, measure: bool | None = None,
         platform: str | None = None, vmem_budget: int | None = None,
         **shape) -> KernelConfig:
    """Resolve a :class:`KernelConfig` for (kind, shape).

    ``measure=None`` reads the ``REPRO_AUTOTUNE`` env var
    (``"measure"`` enables the one-shot measured race; anything else —
    including unset — stays heuristic).  Results are cached in-process
    per (kind, platform, mode, shape bucket): the heuristic is pure so
    the cache is just memoization; the measured race runs exactly once
    per key.  Call this OUTSIDE jit — measured mode times real
    executions, and the resulting ints become static jit arguments.
    """
    if measure is None:
        measure = os.environ.get(_ENV_VAR, "").lower() == "measure"
    _maybe_load_env_cache()
    key = shape_key(kind, shape, platform=platform, measured=measure)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    if not measure:
        # Heuristic configs are pure — two threads racing to fill the
        # same key compute identical values, so a bare GIL-atomic store
        # suffices and the hot path never touches the lock.
        cfg = heuristic_config(kind, platform=platform,
                               vmem_budget=vmem_budget, **shape)
        _CACHE[key] = cfg
        return cfg
    with _CACHE_LOCK:
        # Double-check under the lock: another thread may have finished
        # racing this key while we waited.  Measured races also MUST be
        # serialized — concurrent races poison each other's wall-clock.
        hit = _CACHE.get(key)
        if hit is not None:
            return hit
        cfg = heuristic_config(kind, platform=platform,
                               vmem_budget=vmem_budget, **shape)
        # Seed the cache with the heuristic BEFORE racing: the race runs
        # real pruning/serving calls, and if any of them consults the
        # tuner for this same key (e.g. a knob left unpinned) it must
        # get the heuristic answer, not recurse into another race.
        _CACHE[key] = cfg
        cfg = (_measure_pruning(shape, cfg) if kind == "pruning"
               else _measure_serving(shape, cfg)).validate()
        _CACHE[key] = cfg
        # Share the measurement pass: re-dump the merged cache whenever
        # a race produced a new entry and the env hook names a file.
        path = os.environ.get(_CACHE_ENV_VAR)
        if path:
            dump_cache(path)
    return cfg


def clear_cache() -> None:
    global _env_cache_loaded
    with _CACHE_LOCK:
        _CACHE.clear()
        _env_cache_loaded = False


def cache_info() -> dict[tuple, KernelConfig]:
    """Snapshot of the in-process tuning cache (tests/debugging)."""
    with _CACHE_LOCK:
        return dict(_CACHE)
