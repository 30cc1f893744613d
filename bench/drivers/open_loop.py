"""Open loop: single distinct queries sent into ``ServeLoop`` on a fixed
Poisson schedule, whatever the server's pace.

Each request is timed from the moment it was due to be sent to the
moment its answer arrived, so a stall counts against every request it
delays.  The traffic file gives the rate, the loop's flush policy, the
query length law and how many answers are checked."""

from __future__ import annotations

import concurrent.futures
import time

import numpy as np

from benchlib import common, inputs, serving


def run(r) -> dict:
    from repro.serve.loop import ServeLoop
    tr = r.traffic
    server, packed, facts = serving.open_server(r)
    rng = inputs.rng_for(r.seed, 1)
    sched = inputs.poisson_schedule(rng, tr["rate_qps"], r.seconds)
    n = len(sched)
    pool = serving.encode_pool(r, n, rng)
    # Warm every batch shape the loop can dispatch: its flushes pad the
    # query count to a power of two up to max_batch.
    b = 1
    while b <= tr["max_batch"]:
        server.query_batch(pool[:b])
        b *= 2
    timed = serving.TimedServer(server, r.spans)
    loop = ServeLoop(timed, flush_ms=tr["flush_ms"], max_batch=tr["max_batch"])
    before = loop.stats.snapshot()
    setup_s = r.setup_done()

    done = np.full(n, np.inf)
    late = np.zeros(n)
    futs = [None] * n

    def finished(i):
        def cb(_):
            done[i] = time.perf_counter()
        return cb

    trace_at = (tr["trace_start_s"], tr["trace_start_s"] + tr["trace_s"])
    pauses = common.GcPauses()
    r.counter.armed = True
    t0 = time.perf_counter() + 0.05
    for i in range(n):
        due = t0 + sched[i]
        if r.trace and not r.tracing and sched[i] >= trace_at[0] \
                and r.traced_s is None:
            r.trace_start()
        elif r.tracing and sched[i] >= trace_at[1]:
            r.trace_stop()
        wait = due - time.perf_counter()
        if wait > 0:
            with r.spans.span("generator"):
                time.sleep(wait)
        late[i] = time.perf_counter() - due
        with r.spans.span("submit"):
            futs[i] = loop.submit(pool[i])
        futs[i].add_done_callback(finished(i))
    if r.tracing:
        r.trace_stop()
    # Answers may come up to a minute past the window's close; one that
    # never comes is charged the wait to that deadline.
    deadline = t0 + r.seconds + 60
    concurrent.futures.wait(futs, timeout=max(deadline - time.perf_counter(),
                                              0))
    r.counter.armed = False
    pauses.close()
    loop.close()
    after = loop.stats.snapshot()
    peak = common.memory_peak(r.devices)

    answered = [i for i in range(n) if futs[i].done()
                and futs[i].exception() is None]
    failed = n - len(answered)
    lat_ms = (np.minimum(done, deadline) - (t0 + sched)) * 1e3
    fifth = max(n // 5, 1)
    backlog = float(np.median(lat_ms[-fifth:]) - np.median(lat_ms[:fifth]))
    common.info(f"open loop: {n} requests at {tr['rate_qps']} q/s over "
                f"{r.seconds} s; {len(answered)} answered; median latency "
                f"of the last fifth minus the first {backlog:.3f} ms; "
                f"generator late "
                f"p50 {np.median(late) * 1e3:.3f} ms, p99 "
                f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
                f"{late.max() * 1e3:.3f} ms at "
                f"{sched[int(late.argmax())]:.3f} s; {pauses}")
    counters = {k: after[k] - before[k] for k in
                ("flushes", "queries", "batches", "cache_hits",
                 "cache_misses", "padded_rows")}
    counters["batch_shapes"] = after["batch_shapes"]
    common.info(f"loop counters over the window: {counters}")

    traced = {}
    if r.traced_s is not None:
        a, b = r.traced_s
        traced = {"answered": int(((done >= a) & (done <= b)).sum()),
                  "seconds": b - a}

    # The check: a sample of the answered requests, drawn from the seed.
    pick = inputs.rng_for(r.seed, 2).choice(
        answered, size=min(tr["check_queries"], len(answered)),
        replace=False) if answered else np.array([], np.int64)
    ids = np.stack([futs[i].result()[0].top_idx for i in pick]) \
        if len(pick) else np.zeros((0, serving.K), np.int64)
    scores = np.stack([futs[i].result()[0].top_scores for i in pick]) \
        if len(pick) else np.zeros((0, serving.K), np.float32)
    queries = pool[pick]
    del loop, timed, server, futs, pool
    checks = serving.check_answers(r, packed, queries, ids, scores)
    checks.insert(0, ("unanswered", failed, 0))
    return {"e2e": {"p50_ms": common.percentile(lat_ms, 50),
                    "p99_ms": common.percentile(lat_ms, 99),
                    "setup_s": setup_s},
            "attempted": n, "failed": failed, "checks": checks,
            "facts": facts, "counters": counters,
            "traced": traced, "memory_peak": peak,
            "backlog_ms": backlog,
            "late_ms": {"p50": float(np.median(late) * 1e3),
                        "p99": float(np.percentile(late, 99) * 1e3),
                        "max": float(late.max() * 1e3)}}
