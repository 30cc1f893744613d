"""Program spans (repro.obs) and the counters beside them.

Off, a span is one flag check returning a shared null context; on, it is
a profiler annotation, so the steps of a ServeLoop flush, a server call,
corpus pruning and packing land in a CPU profiler trace here as they do
in a TPU's.  Covered: the off path builds nothing and records nothing;
spans nest and carry their arguments; one flush's span tree with its
shared flush id and row counts equal to LoopStats'; the queue wait under
an injected clock; the closure LRU's build and hit counters; one pruning
dispatch span per dispatch block; the merge program traced once per shape
and keep fraction; the serving program's name.
"""

import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import pruning_pipeline as pp
from repro.core import sampling, voronoi
from repro.serve.index import PackedIndex
from repro.serve.loop import ServeLoop
from repro.serve.retrieval import RetrievalServer, TokenIndex

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")


@pytest.fixture
def spans_on():
    obs.enable(True)
    try:
        yield
    finally:
        obs.enable(False)


def _traced(tmp_path, fn):
    """Run ``fn`` under a CPU profiler trace; return its ``repro.*``
    events as dicts (name, start, end, args, line), by start time."""
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append({"name": ev.name, "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "args": dict(ev.stats), "line": line.name})
    return sorted(out, key=lambda e: (e["start"], -e["end"]))


def _inside(inner, outer):
    return (outer["line"] == inner["line"]
            and outer["start"] <= inner["start"]
            and inner["end"] <= outer["end"])


def _packed(seed=0, n_docs=16, m=8, dim=4, **kw):
    k = jax.random.PRNGKey(seed)
    d = jax.random.normal(k, (n_docs, m, dim)) * 0.5
    n_real = jax.random.randint(jax.random.fold_in(k, 1), (n_docs,),
                                1, m + 1)
    masks = jnp.arange(m)[None, :] < n_real[:, None]
    return d, masks, TokenIndex.build(d, masks).pack(**kw)


def _queries(seed, n_q, l=4, dim=4):
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                        (n_q, l, dim)), np.float32)


# -- the recorder ----------------------------------------------------------

def test_off_builds_nothing_and_records_nothing(monkeypatch, tmp_path):
    assert not obs.enabled()

    def refuse(*a, **kw):
        raise AssertionError("an annotation was built while spans are off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    a = obs.span("repro.a")
    assert obs.span("repro.b", rows=3) is a
    with a as s:
        s.set_metadata(rows=1)
    monkeypatch.undo()

    def work():
        with obs.span("repro.off", x=1):
            jnp.ones(4).block_until_ready()
    assert _traced(tmp_path, work) == []


def test_on_nests_spans_and_carries_args(spans_on, tmp_path):
    def work():
        with obs.span("repro.outer", flush=7):
            with obs.span("repro.inner", width=16) as s:
                jnp.ones(4).block_until_ready()
                s.set_metadata(real_rows=3, queue_wait_s=0.25)
    outer, inner = _traced(tmp_path, work)
    assert outer["name"] == "repro.outer" and inner["name"] == "repro.inner"
    assert _inside(inner, outer)
    assert outer["args"] == {"flush": 7}
    assert inner["args"] == {"width": 16, "real_rows": 3,
                             "queue_wait_s": 0.25}


def test_program_span_names_start_with_repro():
    bench_names = {"server_call", "submit", "generator", "encode", "prune",
                   "pack", "traced_window"}
    names = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            names |= set(re.findall(r'obs\.span\(\s*"([^"]+)"', f.read()))
    assert len(names) >= 15
    assert all(n.startswith(obs.PREFIX) for n in names), names
    assert not names & bench_names


# -- serving ---------------------------------------------------------------

def test_one_flush_gives_the_span_tree(spans_on, tmp_path):
    _, _, packed = _packed()
    server = RetrievalServer(packed, k=3, n_first=packed.n_docs)
    q = _queries(1, 3)
    server.query_batch(np.concatenate([q, q[:1]]))      # warm the 4-row shape
    loop = ServeLoop(server, flush_ms=60_000.0, max_batch=3)
    before = loop.stats.snapshot()

    def work():
        futs = [loop.submit(q[i]) for i in range(3)]
        for f in futs:
            f.result(timeout=60)
    ev = _traced(tmp_path, work)
    loop.close()
    after = loop.stats.snapshot()

    flush, = [e for e in ev if e["name"] == "repro.loop.flush"]
    fid = flush["args"]["flush"]
    collect, = [e for e in ev if e["name"] == "repro.loop.collect"]
    assert collect["args"] == {"flush": fid, "rows": 3}
    assert collect["line"] == flush["line"]
    assert collect["end"] <= flush["start"]
    for name in ("repro.loop.lookup", "repro.loop.batch",
                 "repro.loop.demux"):
        e, = [e for e in ev if e["name"] == name]
        assert e["args"] == {"flush": fid}
        assert _inside(e, flush)
    call, = [e for e in ev if e["name"] == "repro.server.query_batch"]
    assert _inside(call, flush)
    for name in ("repro.server.closure", "repro.server.run",
                 "repro.server.fetch"):
        e, = [e for e in ev if e["name"] == name]
        assert _inside(e, call)
    closure, = [e for e in ev if e["name"] == "repro.server.closure"]
    assert closure["args"] == {"built": 0}
    order = [e["name"] for e in ev if _inside(e, flush) and e is not flush]
    assert order == ["repro.loop.lookup", "repro.loop.batch",
                     "repro.server.query_batch", "repro.server.closure",
                     "repro.server.run", "repro.server.fetch",
                     "repro.loop.demux"]
    # The flush's rows are the loop's own counts over the same window.
    a = flush["args"]
    assert a["rows"] == 3
    assert a["real_rows"] == after["cache_misses"] - before["cache_misses"]
    assert a["padded_rows"] == after["padded_rows"] - before["padded_rows"]
    assert a["padded_rows"] == 1
    assert a["queue_wait_s"] == pytest.approx(
        after["queue_wait_s"] - before["queue_wait_s"], rel=1e-6)


class _Clock:
    """A clock the test moves by hand."""

    def __init__(self, t):
        self.t = t
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            return self.t

    def set(self, t):
        with self._lock:
            self.t = t


def test_queue_wait_is_exact_under_an_injected_clock():
    _, _, packed = _packed()
    server = RetrievalServer(packed, k=3, n_first=packed.n_docs)
    q = _queries(2, 6)
    clock = _Clock(1.0)
    # Flushes only ever fill to max_batch: the deadline lies a minute of
    # the real clock away, and the injected one stands still meanwhile.
    with ServeLoop(server, flush_ms=60_000.0, max_batch=3,
                   clock=clock) as loop:
        a = loop.submit(q[0:2])                # two rows submitted at 1.0
        clock.set(1.25)
        b = loop.submit(q[2])                  # flush starts at 1.25
        a.result(timeout=60), b.result(timeout=60)
        assert loop.stats.snapshot()["queue_wait_s"] == 0.5
        clock.set(3.0)
        c = loop.submit(q[3])
        clock.set(3.5)
        d = loop.submit(q[4])
        clock.set(4.0)
        e = loop.submit(q[5])                  # flush starts at 4.0
        for f in (c, d, e):
            f.result(timeout=60)
        snap = loop.stats.snapshot()
    assert snap["queries"] == 6 and snap["flushes"] == 2
    # per query: 0.25 + 0.25 + 0, then 1.0 + 0.5 + 0
    assert snap["queue_wait_s"] == 2.0


def test_closure_lru_counts_builds_and_hits():
    _, _, packed = _packed()
    server = RetrievalServer(packed, k=3, n_first=packed.n_docs)
    assert (server.closure_builds, server.closure_hits) == (0, 0)
    server.query_batch(_queries(3, 2))
    assert (server.closure_builds, server.closure_hits) == (1, 0)
    server.query_batch(_queries(4, 2))
    assert (server.closure_builds, server.closure_hits) == (1, 1)
    server.query_batch(_queries(5, 4))
    assert (server.closure_builds, server.closure_hits) == (2, 1)


def test_serving_program_has_a_stable_name():
    _, _, packed = _packed()
    server = RetrievalServer(packed, k=3, n_first=packed.n_docs)
    text = server.lowered_text(jnp.asarray(_queries(6, 2)))
    assert text.startswith("module @jit_serve_topk")


# -- the build path ----------------------------------------------------------

def test_prune_corpus_marks_each_dispatch_block(spans_on, tmp_path,
                                                monkeypatch):
    d, masks, _ = _packed(seed=3, n_docs=11, m=24, dim=4)
    samples = sampling.sample_sphere(jax.random.PRNGKey(1), 64, 4)
    # Two documents per block, so that buckets split into several blocks.
    monkeypatch.setattr(pp, "pruning_docs_per_block", lambda n, w: 2)
    plan = pp.bucket_plan(pp.effective_lengths(masks), 24)
    blocks = list(pp._doc_blocks(plan, 64))
    assert len(blocks) > len(plan)

    def work():
        keep, _, _ = pp.prune_corpus(d, masks, samples, 0.5)
        np.asarray(keep)
    ev = _traced(tmp_path, work)
    top, = [e for e in ev if e["name"] == "repro.prune"]
    assert top["args"] == {"docs": 11}
    dispatch = [e for e in ev if e["name"] == "repro.prune.dispatch"]
    assert [(e["args"]["width"], e["args"]["docs"]) for e in dispatch] == [
        (b.width, len(b.indices)) for b, _ in blocks]
    for name in ("repro.prune.plan", "repro.prune.gather",
                 "repro.prune.merge"):
        assert len([e for e in ev if e["name"] == name]) == 1
    assert all(_inside(e, top) for e in ev if e is not top)
    steps = [e["name"] for e in ev if e is not top]
    assert steps == (["repro.prune.plan"]
                     + ["repro.prune.dispatch"] * len(blocks)
                     + ["repro.prune.gather", "repro.prune.merge"])


def test_merge_traces_once_per_shape_and_keep_fraction(spans_on, tmp_path):
    """Same-shape slabs reuse one compiled §4.2 merge; a new width or a
    new keep fraction traces it once more, and ``repro.prune.merge``
    says which calls traced."""
    samples = sampling.sample_sphere(jax.random.PRNGKey(2), 64, 4)
    voronoi._global_keep_masks_local.clear_cache()  # so the first call traces
    slab = [_packed(seed=s, n_docs=13, m=24, dim=4)[:2] for s in (5, 6)]
    wide = _packed(seed=7, n_docs=13, m=40, dim=4)[:2]
    calls = [(*slab[0], 0.5), (*slab[1], 0.5), (*wide, 0.5),
             (*slab[1], 0.25), (*slab[0], 0.25)]
    before = voronoi.merge_traces()

    def work():
        for d, masks, frac in calls:
            np.asarray(pp.prune_corpus(d, masks, samples, frac)[0])
    ev = _traced(tmp_path, work)
    merges = [e for e in ev if e["name"] == "repro.prune.merge"]
    assert [e["args"] for e in merges] == [
        {"traced": t} for t in (1, 0, 1, 1, 0)]
    assert voronoi.merge_traces() - before == 3


def test_pack_marks_the_residual_encode(spans_on, tmp_path):
    d, masks, _ = _packed(seed=4, n_docs=12, m=24, dim=8)
    out = []

    def work():
        out.append(PackedIndex.pack(d, masks, compression="residual",
                                    residual_bits=2, n_centroids=4))
    ev = _traced(tmp_path, work)
    top, = [e for e in ev if e["name"] == "repro.pack"]
    assert top["args"] == {"compression": "residual"}
    res = [e for e in ev if e["name"] == "repro.pack.residual"]
    assert [(e["args"]["cap"], e["args"]["docs"]) for e in res] == [
        (b.cap, b.n_docs) for b in out[0].buckets]
    assert all(_inside(e, top) for e in res)
