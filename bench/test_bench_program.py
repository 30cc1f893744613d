"""CPU checks of the program-span reduction (``benchlib.program``): the
split of the device's idle time between the serving loop and the server,
and the pruning idle per document, on reductions built by hand; the
spans kept from a CPU profiler trace; and the names, which must never
reach the benchmark's own span reduction."""

import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import program, smoke, trace  # noqa: E402

LINE = "python/3"                     # the dispatcher's host line


def _red(shift=0):
    """A 100 ns window; the device runs [24, 38) and [62, 80), inside the
    two server calls of ``_stream_prog``, and idles 68 ns.  The main
    thread's generator span covers the whole window.  ``shift`` moves the
    device's timeline against the host's."""
    return {"window_ns": 100.0,
            "spans": [["generator", 0, 100, {}]],
            "devices": {"/device:TPU:0": [["k", 24 + shift, 14],
                                          ["k", 62 + shift, 18]]}}


def _stream_prog():
    return [
        ["repro.loop.flush", -10, 12, {"flush": 1}, LINE],   # before
        ["repro.loop.flush", 5, 40, {"flush": 2}, LINE],
        ["repro.server.query_batch", 20, 22, {}, LINE],
        ["repro.loop.flush", 55, 40, {"flush": 3}, LINE],
        ["repro.server.query_batch", 58, 27, {}, LINE],
    ]


def test_idle_splits_between_loop_and_server():
    red, prog = _red(), _stream_prog()
    split = program.idle_split(red, prog)
    # the server's calls last 22 + 27 ns, of which the device ran 32
    assert split == {"idle_ns": 68.0, "server_ns": 17.0, "loop_ns": 51.0,
                     "flushes": 2}
    loop = program.loop_idle_ms_per_flush(red, prog)
    server = program.server_idle_ms_per_flush(red, prog)
    assert loop == pytest.approx(25.5e-6) and server == pytest.approx(8.5e-6)
    # by construction: the window's idle ms over the flushes in it
    idle_ms = trace.idle_share(red) * red["window_ns"] / 1e6
    assert loop + server == pytest.approx(idle_ms / 2)


def test_split_holds_under_a_clock_offset():
    """The device's timeline may sit a little early or late against the
    host's: the split does not move, where charging each idle moment to
    the span the host timeline puts it in would."""
    prog = _stream_prog()
    for shift in (-6, 5):
        assert program.idle_split(_red(shift), prog) == \
            program.idle_split(_red(), prog)


def test_main_thread_spans_never_take_a_gap():
    red, prog = _red(), _stream_prog()
    # The benchmark's own breakdown charges every gap to the generator...
    assert dict(trace.idle_gaps(red)) == pytest.approx({"generator": 68e-9})
    # ...the program's split reads only the program's spans.
    red["spans"] = []
    assert program.idle_split(red, prog) == program.idle_split(_red(), prog)
    main = [["repro.prune", 0, 100, {"docs": 1}, "python/0"]]
    assert program.idle_split(red, prog + main) == \
        program.idle_split(red, prog)


def test_prune_idle_per_doc():
    red = _red()
    prog = [["repro.prune", 0, 60, {"docs": 4}, "python/0"],
            ["repro.prune.dispatch", 1, 5, {"width": 8, "docs": 4},
             "python/0"],
            ["repro.prune", 70, 40, {"docs": 4}, "python/0"]]
    # idle inside: [0, 24) [38, 60) and [80, 100): 66 ns, 8 docs
    assert program.prune_idle_ms_per_doc(red, prog) == pytest.approx(
        66e-6 / 8)
    assert program.prune_idle_ms_per_doc(red, prog[1:2]) is None


def test_no_device_or_no_flush_reads_nothing():
    red = _red()
    red["devices"] = {}
    prog = _stream_prog()
    assert program.loop_idle_ms_per_flush(red, prog) is None
    assert program.server_idle_ms_per_flush(red, prog) is None
    assert program.prune_idle_ms_per_doc(red, prog) is None
    assert program.idle_split(_red(), prog[:1]) is None


def test_queue_wait_from_the_loop_counters():
    assert program.queue_wait_ms({"queue_wait_s": 0.5, "queries": 100}) \
        == pytest.approx(5.0)
    # a program without the counter, or an empty window, reads nothing
    assert program.queue_wait_ms({"queries": 100}) is None
    assert program.queue_wait_ms({"queue_wait_s": 0.0, "queries": 0}) is None


def _other():
    from repro import obs
    with obs.span("repro.other", rows=2):
        pass


def test_program_spans_from_a_cpu_trace(tmp_path):
    import jax
    from repro import obs
    obs.enable(True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        with obs.span("repro.before"):
            pass
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with obs.span("repro.main", docs=3):
                t = threading.Thread(target=_other)
                t.start()
                t.join(timeout=30)
        jax.profiler.stop_trace()
    finally:
        obs.enable(False)
    assert not t.is_alive()
    spans = program.program_spans(
        trace.load_xspace(trace.find_xspace(str(tmp_path))))
    assert [s[0] for s in spans] == ["repro.main", "repro.other"]
    main, other = spans
    assert main[3] == {"docs": 3} and other[3] == {"rows": 2}
    assert main[1] >= 0 and main[2] > 0
    assert main[1] <= other[1] and other[1] + other[2] <= main[1] + main[2]
    assert main[4] != other[4]              # two threads, two lines


def test_program_names_stay_out_of_the_benchmark_spans():
    run = smoke.load_run_module()
    assert not any(n.startswith(program.PREFIX) for n in run.SPAN_NAMES)


# -- a traced res2-stream run recorded on a TPU v5e with the program's
# spans on (a 0.2 s window, 19 flushes, just after the trace began) ------

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "stream_res2_spans.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    pd = trace.load_xspace(FIXTURE)
    return (trace.reduce_xspace(pd, ("server_call", "submit", "generator")),
            program.program_spans(pd))


def test_recorded_program_spans(recorded):
    red, prog = recorded
    flushes = [s for s in prog if s[0] == "repro.loop.flush"]
    assert len(flushes) == 19
    assert len({s[4] for s in prog}) == 1          # the dispatcher's line
    for s in flushes:
        a = s[3]
        assert a["rows"] == a["real_rows"]         # no repeats: no hits
        assert a["real_rows"] + a["padded_rows"] in (16, 32)
    ids = [s[3]["flush"] for s in flushes]
    assert ids == list(range(ids[0], ids[0] + 19))
    calls = [s for s in prog if s[0] == "repro.server.query_batch"]
    assert sum(s[2] for s in calls) / len(calls) == pytest.approx(
        7155642.157894737)


def test_recorded_idle_split(recorded):
    red, prog = recorded
    assert red["window_ns"] == 199598256.0
    assert trace.idle_share(red) == pytest.approx(0.4965447443588886)
    assert program.idle_split(red, prog) == {
        "idle_ns": 99109465.0, "server_ns": 35468410.0,
        "loop_ns": 63641055.0, "flushes": 19}
    loop = program.loop_idle_ms_per_flush(red, prog)
    server = program.server_idle_ms_per_flush(red, prog)
    assert loop == pytest.approx(3.349529210526316)
    assert server == pytest.approx(1.8667584210526316)
    per_flush = trace.idle_share(red) * red["window_ns"] / 1e6 / 19
    assert loop + server == pytest.approx(per_flush, rel=0.02)
    # The benchmark's breakdown names nearly all of it "generator".
    assert trace.idle_gaps(red)[0][0] == "generator"
