"""The program's own spans in a profiler trace, and what they tell of the
device's idle time.

With ``repro.obs`` on, the program marks its serving and build steps
with host spans named ``repro.*`` (``src/repro/obs.py``): the dispatcher's
``repro.loop.*`` steps around each ``repro.server.query_batch``, and
``repro.prune`` around its plan, dispatch, gather and merge steps.
:func:`program_spans` keeps them from a recorded XSpace, relative to the
traced window's start, beside :func:`trace.reduce_xspace`'s reduction of
the same trace.  The functions below charge the device's idle time, on
the first traced device as ``trace.idle_gaps`` does, to those spans:

* ``server``: idle time inside ``repro.server.query_batch`` spans, the
  server's host path (closure lookup, copy in, dispatch, ``device_get``);
* ``loop``: every other idle moment of the window, the dispatcher's own
  work (collecting, hashing, stacking, demuxing) and its waits for a
  request; per flush, ``loop + server`` is the window's idle time over
  the flushes that start in it (:func:`idle_split` says how the two are
  told apart);
* ``prune``: idle time inside ``repro.prune`` spans, per document pruned.

Spans of other threads (the benchmark's generator sleeping on the main
thread) never take a gap here: only the program's named steps do.
"""

from __future__ import annotations

from benchlib import trace

PREFIX = "repro."


def program_spans(pd) -> list:
    """[[name, start, dur, args, line], ...] of the ``repro.*`` host
    events that overlap the traced window, in ns from its start, by
    start; ``line`` tells threads apart ("<thread name>/<line index>"),
    since threads of one process can share a name."""
    window, found = None, []
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    args = {k: v for k, v in ev.stats
                            if isinstance(v, (int, float, str))}
                    found.append([ev.name, ev.start_ns, ev.duration_ns,
                                  args, f"{line.name}/{i}"])
    if window is None:
        raise ValueError(f"the trace holds no {trace.WINDOW!r} span")
    t0, t1 = window
    return sorted(([n, s - t0, d, a, ln] for n, s, d, a, ln in found
                   if s < t1 and s + d > t0), key=lambda s: (s[1], -s[2]))


def idle_intervals(red: dict) -> list:
    """[(start, end), ...] in which the first traced device ran nothing,
    inside the window (the gaps ``trace.idle_gaps`` names)."""
    ops = red["devices"][sorted(red["devices"])[0]]
    busy = trace._union((s, s + d) for _, s, d in ops)
    gaps, t = [], 0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if red["window_ns"] > t:
        gaps.append((t, red["window_ns"]))
    return gaps


def _overlap(gaps, spans) -> float:
    """ns of ``gaps`` inside the union of ``spans``' intervals."""
    cover = trace._union((s[1], s[1] + s[2]) for s in spans)
    total, j = 0.0, 0
    for a, b in gaps:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            total += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return total


def idle_split(red: dict, prog: list) -> dict | None:
    """The window's device-idle ns split into the server's host path and
    the loop's, with the flushes that start in the window; None where the
    trace has no device or no flush.

    The server's share is the time inside ``repro.server.query_batch``
    spans less the device's busy time: the serving program is dispatched
    and awaited inside those spans and nothing else runs on the device,
    so every busy moment lies inside one.  Charging by that, and not by
    where the device's intervals fall on the host's timeline, keeps the
    split clear of the offset between the two clocks (0.6 to 1.8 ms on a
    v5e, differing from trace to trace: PERF.md section 5), which is up to
    a sixth of a flush cycle."""
    if not red["devices"]:
        return None
    window = red["window_ns"]
    flushes = sum(1 for s in prog if s[0] == "repro.loop.flush"
                  and 0 <= s[1] < window)
    if not flushes:
        return None
    idle = sum(b - a for a, b in idle_intervals(red))
    calls = trace._union((max(s[1], 0), min(s[1] + s[2], window))
                         for s in prog if s[0] == "repro.server.query_batch")
    in_calls = sum(b - a for a, b in calls if b > a)
    server = max(in_calls - (window - idle), 0.0)
    return {"idle_ns": idle, "server_ns": server, "loop_ns": idle - server,
            "flushes": flushes}


def loop_idle_ms_per_flush(red: dict, prog: list) -> float | None:
    split = idle_split(red, prog)
    return split and split["loop_ns"] / 1e6 / split["flushes"]


def server_idle_ms_per_flush(red: dict, prog: list) -> float | None:
    split = idle_split(red, prog)
    return split and split["server_ns"] / 1e6 / split["flushes"]


def prune_idle_ms_per_doc(red: dict, prog: list) -> float | None:
    """Device-idle ms inside ``repro.prune`` spans per document of the
    prune spans that start in the window."""
    if not red["devices"]:
        return None
    prune = [s for s in prog if s[0] == "repro.prune"]
    docs = sum(s[3].get("docs", 0) for s in prune
               if 0 <= s[1] < red["window_ns"])
    if not docs:
        return None
    return _overlap(idle_intervals(red), prune) / 1e6 / docs


def queue_wait_ms(counters: dict) -> float | None:
    """Mean queue wait per query over the window, from the loop's
    ``queue_wait_s`` and ``queries`` counted over it."""
    if "queue_wait_s" not in counters or not counters.get("queries"):
        return None
    return 1e3 * counters["queue_wait_s"] / counters["queries"]
