"""Concurrent micro-batched serving loop (DESIGN_BACKENDS.md §Serving
loop; ROADMAP "Production serving loop").

``RetrievalServer.query_batch`` answers one batch at a time; real
traffic arrives as a stream of small, independent requests.
:class:`ServeLoop` sits in front of the server and turns that stream
into the batch shapes the stack is already optimized for:

* **Submission queue + micro-batching.**  Clients ``submit()`` single
  queries (or small batches) and get a future.  A dispatcher thread
  collects arrivals until the flush deadline (``flush_ms``) or the
  batch cap (``max_batch``) is hit, groups them by exact per-query
  token shape ``(l, dim)``, and pads each group's query count up to
  the next power of two — exactly the ``n_q`` bucketing the autotuner
  (``core.tuning.shape_key``) and the server's closure LRU key on, so
  steady-state traffic reuses a small, bounded set of compiled
  programs no matter how request counts fluctuate.  Pad rows repeat a
  real query; per-query MaxSim is row-independent (each query's
  scores, merges, and top-k read only its own row), so padding never
  perturbs real rows and the demuxed per-query answer is bit-identical
  to serving that query alone — the loop-vs-serial parity contract,
  gated in the bench and the 4-device harness.
* **Per-query demux.**  The merged :class:`TopKResult` is sliced back
  per request; every answer carries the batch's ``coverage`` and
  ``epoch_key`` (the ``(generation, mutation_gen, index.epoch)``
  snapshot it was computed under).
* **Per-epoch result cache.**  Answers at full coverage are cached
  under ``(epoch_key, sha1(query bytes))``.  Invalidation is free: the
  key joins the same triple the server's closure LRU keys on, so an
  epoch swap or delta-log update changes the key and every stale
  entry simply stops matching (and ages out of the bounded LRU).
  Degraded answers (coverage < 1) are never cached — they describe
  fleet state, not corpus state.
* **Mutations serialized against in-flight flushes.**  ``swap_index``
  / ``apply_mutation`` pass through to the server, whose write gate
  drains in-flight queries first — so every flush's merged batch is
  answered by exactly one epoch, and a swap lands strictly between
  flushes, never inside one.
* **Spans and queue wait.**  With :mod:`repro.obs` on, the dispatcher
  marks ``repro.loop.collect`` (first request in hand to the flush) and
  ``repro.loop.flush`` around ``repro.loop.lookup`` (cache hashing),
  ``repro.loop.batch`` (stack and pad), the server's own spans and
  ``repro.loop.demux``; each carries the flush's ``flush`` id, and the
  flush span its ``rows``, ``real_rows``, ``padded_rows`` and summed
  ``queue_wait_s``.  ``LoopStats.queue_wait_s`` counts the waits always.

The async dispatch half lives below this module: under a grid
placement the monitored exchange in ``retrieval._topk_search_grid``
fans the per-group programs and their deadline-bounded candidate
fetches over a worker pool (one straggler costs max, not sum), with
``FleetMonitor`` accounting made thread-safe for exactly that fan-out.
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
import queue
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future

import numpy as np

from repro import obs
from repro.core.tuning import _pow2_at_least
from repro.serve.retrieval import TopKResult
from repro.sharding.specs import axis_rules, current_rules

__all__ = ["ServeLoop", "LoopStats"]

_SHUTDOWN = object()


class _Request:
    """One submitted query batch awaiting its demuxed answers."""

    __slots__ = ("q", "n", "future", "t_submit", "cached")

    def __init__(self, q: np.ndarray, clock) -> None:
        self.q = q
        self.n = q.shape[0]
        self.future: Future = Future()
        self.t_submit = clock()
        self.cached: list = [None] * self.n   # per-row cache hits


class LoopStats:
    """Counters + latency reservoir the loop maintains under its own
    lock; ``snapshot()`` returns a plain dict (p50/p99 in seconds).

    ``queue_wait_s`` sums, over answered queries, the time from the
    query's submit to the start of the flush that answered it, on the
    loop's clock: ``queue_wait_s / queries`` is the mean queue wait."""

    def __init__(self, window: int = 4096) -> None:
        self._lock = threading.Lock()
        self._lat: list = []
        self._window = int(window)
        self.flushes = 0
        self.queries = 0
        self.batches = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.padded_rows = 0
        self.queue_wait_s = 0.0
        self.shapes: dict = {}

    def record_flush(self, n_batches: int, padded: int) -> None:
        with self._lock:
            self.flushes += 1
            self.batches += n_batches
            self.padded_rows += padded

    def record_shape(self, shape: tuple) -> None:
        with self._lock:
            self.shapes[shape] = self.shapes.get(shape, 0) + 1

    def record_query(self, latency_s: float, *, hit: bool,
                     wait_s: float = 0.0) -> None:
        with self._lock:
            self.queries += 1
            self.queue_wait_s += wait_s
            if hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1
            self._lat.append(latency_s)
            if len(self._lat) > self._window:
                del self._lat[:len(self._lat) - self._window]

    def snapshot(self) -> dict:
        with self._lock:
            lat = sorted(self._lat)

            def pct(p):
                if not lat:
                    return float("nan")
                return lat[min(len(lat) - 1, int(p * (len(lat) - 1)))]

            return {
                "flushes": self.flushes,
                "queries": self.queries,
                "batches": self.batches,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "padded_rows": self.padded_rows,
                "queue_wait_s": self.queue_wait_s,
                "batch_shapes": dict(self.shapes),
                "p50_latency_s": pct(0.50),
                "p99_latency_s": pct(0.99),
            }


def _qhash(row: np.ndarray) -> bytes:
    """Content hash of one query's embedding block (shape + dtype +
    bytes): two bit-identical queries share an answer, two queries
    differing in any mantissa bit do not."""
    h = hashlib.sha1()
    h.update(str((row.shape, row.dtype.str)).encode())
    h.update(np.ascontiguousarray(row).tobytes())
    return h.digest()


class ServeLoop:
    """The concurrent micro-batched front-end of a
    :class:`~repro.serve.retrieval.RetrievalServer`.

    ``submit(q)`` (one ``(l, dim)`` query or an ``(n, l, dim)`` batch)
    enqueues and returns a :class:`concurrent.futures.Future` whose
    result is a list of per-query :class:`TopKResult`\\ s (host
    arrays, each carrying ``coverage`` and ``epoch_key``).  ``query()``
    is the blocking single-query convenience.  The dispatcher thread
    flushes when ``max_batch`` queries are waiting or ``flush_ms``
    elapsed since the oldest arrival, whichever is first.

    ``result_cache_size`` bounds the per-epoch result cache (0
    disables it).  ``swap_index``/``apply_mutation`` are the mutation
    pass-throughs — safe to call while clients are submitting; the
    server's write gate serializes them against in-flight flushes.

    Use as a context manager or call :meth:`close` — pending requests
    are flushed, not dropped.
    """

    def __init__(self, server, *, flush_ms: float = 2.0,
                 max_batch: int = 32, result_cache_size: int = 4096,
                 clock=time.monotonic) -> None:
        if flush_ms < 0:
            raise ValueError(f"flush_ms={flush_ms} < 0")
        if max_batch < 1:
            raise ValueError(f"max_batch={max_batch} < 1")
        self.server = server
        self.flush_ms = float(flush_ms)
        self.max_batch = int(max_batch)
        self._clock = clock
        self.stats = LoopStats()
        self._queue: queue.Queue = queue.Queue()
        self._cache_size = max(0, int(result_cache_size))
        self._cache: OrderedDict = OrderedDict()
        self._cache_lock = threading.Lock()
        self._closed = False
        self._close_lock = threading.Lock()
        # Axis rules are thread-local (sharding.specs._state); capture
        # the rule set active HERE so the dispatcher thread serves the
        # same (possibly grid-placed) dataflow the constructor saw —
        # without this, a loop built inside axis_rules(serve_rules(...))
        # would silently trace unsharded closures.
        self._rules = current_rules()
        self._flush_ids = itertools.count(1)    # dispatcher thread only
        self._thread = threading.Thread(
            target=self._run, name="serve-loop-dispatch", daemon=True)
        self._thread.start()

    # -- client API ------------------------------------------------------

    def submit(self, q) -> Future:
        """Enqueue one query (``(l, dim)``) or batch (``(n, l, dim)``);
        returns a future resolving to ``[TopKResult, ...]`` (one per
        row, in submission order)."""
        q = np.asarray(q)
        if q.ndim == 2:
            q = q[None]
        if q.ndim != 3:
            raise ValueError(
                f"submit wants (l, dim) or (n, l, dim); got {q.shape}")
        if self._closed:
            raise RuntimeError("ServeLoop is closed")
        req = _Request(q, self._clock)
        self._queue.put(req)
        return req.future

    def query(self, q) -> TopKResult:
        """Blocking single-query serve: ``TopKResult`` for one
        ``(l, dim)`` query."""
        q = np.asarray(q)
        if q.ndim != 2:
            raise ValueError(f"query wants one (l, dim) query; "
                             f"got {q.shape}")
        return self.submit(q).result()[0]

    def query_many(self, q) -> list:
        """Blocking batch submit: ``[TopKResult, ...]`` per row of an
        ``(n, l, dim)`` batch."""
        return self.submit(q).result()

    # -- mutation pass-throughs -----------------------------------------

    def swap_index(self, index, *, mutation=None, routing=None) -> None:
        """Epoch swap, serialized against in-flight flushes by the
        server's write gate: the swap drains running query batches and
        blocks new ones, so no flush ever straddles two epochs."""
        self.server.swap_index(index, mutation=mutation, routing=routing)

    def apply_mutation(self, mutation) -> None:
        """Delta-log update, same serialization as :meth:`swap_index`."""
        self.server.apply_mutation(mutation)

    # -- lifecycle -------------------------------------------------------

    def close(self, *, timeout: float | None = 30.0) -> None:
        """Stop accepting work, flush what is queued, join the
        dispatcher."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self._queue.put(_SHUTDOWN)
        self._thread.join(timeout=timeout)

    def __enter__(self) -> "ServeLoop":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- dispatcher ------------------------------------------------------

    def _run(self) -> None:
        ctx = (axis_rules(self._rules) if self._rules is not None
               else contextlib.nullcontext())
        with ctx:
            self._run_inner()

    def _run_inner(self) -> None:
        while True:
            req = self._queue.get()
            if req is _SHUTDOWN:
                return
            flush = next(self._flush_ids)
            pending = [req]
            rows = req.n
            deadline = self._clock() + self.flush_ms / 1000.0
            stop = False
            with obs.span("repro.loop.collect", flush=flush) as sp:
                while rows < self.max_batch:
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        break
                    try:
                        nxt = self._queue.get(timeout=remaining)
                    except queue.Empty:
                        break
                    if nxt is _SHUTDOWN:
                        stop = True
                        break
                    pending.append(nxt)
                    rows += nxt.n
                sp.set_metadata(rows=rows)
            with obs.span("repro.loop.flush", flush=flush) as sp:
                self._flush(pending, flush, sp)
            if stop:
                return

    def _flush(self, pending: list, flush: int, span) -> None:
        """Answer every pending request: resolve cache hits, group the
        misses by (l, dim), run one padded pow2 ``query_batch`` per
        group, demux, cache, resolve futures.  Each request's queue
        wait ends here, at the flush's start; ``span`` is the flush's
        own, given its row counts and summed wait."""
        t_flush = self._clock()
        # (l, dim) -> list of (request, row index in request)
        groups: dict = {}
        with obs.span("repro.loop.lookup", flush=flush):
            epoch_key = self.server.epoch_key
            for req in pending:
                for i in range(req.n):
                    hit = self._cache_get(epoch_key, req.q[i])
                    if hit is not None:
                        req.cached[i] = hit
                    else:
                        groups.setdefault(req.q.shape[1:],
                                          []).append((req, i))
        try:
            merged = {}
            for shape, slots in sorted(groups.items(),
                                       key=lambda kv: kv[0]):
                merged[shape] = self._run_group(shape, slots, flush)
        except BaseException as e:
            for req in pending:
                if not req.future.done():
                    req.future.set_exception(e)
            return
        padded = sum(p for _, p in merged.values())
        self.stats.record_flush(len(groups), padded)
        if obs.enabled():
            span.set_metadata(
                rows=sum(req.n for req in pending),
                real_rows=sum(len(s) for s in groups.values()),
                padded_rows=padded,
                queue_wait_s=sum((t_flush - req.t_submit) * req.n
                                 for req in pending))
        with obs.span("repro.loop.demux", flush=flush):
            self._demux(pending, groups, merged, t_flush)

    def _demux(self, pending, groups, merged, t_flush) -> None:
        """Slice each group's merged answer back per request (row
        order), cache full-coverage answers, resolve the futures."""
        sliced: dict = {}
        for shape, slots in groups.items():
            out, _ = merged[shape]
            for j, (req, i) in enumerate(slots):
                res = TopKResult(out.top_idx[j], out.top_scores[j],
                                 out.coverage)
                res.epoch_key = out.epoch_key
                if out.coverage >= 1.0:
                    self._cache_put(out.epoch_key, req.q[i], res)
                sliced.setdefault(id(req), {})[i] = res
        now = self._clock()
        for req in pending:
            answers = []
            per = sliced.get(id(req), {})
            for i in range(req.n):
                res = req.cached[i] if req.cached[i] is not None \
                    else per[i]
                answers.append(res)
                self.stats.record_query(now - req.t_submit,
                                        hit=req.cached[i] is not None,
                                        wait_s=t_flush - req.t_submit)
            req.future.set_result(answers)

    def _run_group(self, shape: tuple, slots: list, flush: int):
        """One (l, dim) group's merged serve: stack the miss rows, pad
        the query axis to the autotuner's pow2 bucket (repeating the
        first row — real data, so masked/kernel paths see nothing
        unusual), run the server once, return (batch TopKResult over
        the REAL rows, padded-row count)."""
        with obs.span("repro.loop.batch", flush=flush):
            q = np.stack([req.q[i] for req, i in slots])
            n_real = q.shape[0]
            n_pad = _pow2_at_least(n_real)
            if n_pad > n_real:
                q = np.concatenate(
                    [q, np.broadcast_to(q[:1], (n_pad - n_real,) + shape)])
        self.stats.record_shape((n_pad,) + shape)
        out = self.server.query_batch(q)
        res = TopKResult(np.asarray(out.top_idx[:n_real]),
                         np.asarray(out.top_scores[:n_real]),
                         out.coverage)
        res.epoch_key = out.epoch_key
        return res, n_pad - n_real

    # -- result cache ----------------------------------------------------

    def _cache_get(self, epoch_key, row: np.ndarray):
        if not self._cache_size:
            return None
        key = (epoch_key, _qhash(row))
        with self._cache_lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
            return hit

    def _cache_put(self, epoch_key, row: np.ndarray, res) -> None:
        if not self._cache_size:
            return
        key = (epoch_key, _qhash(row))
        with self._cache_lock:
            self._cache[key] = res
            self._cache.move_to_end(key)
            while len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)

    def cache_len(self) -> int:
        with self._cache_lock:
            return len(self._cache)
