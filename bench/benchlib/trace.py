"""From a profiler trace to the numbers the per-layer metrics read.

A traced run wraps its traced part in a host span named ``traced_window``
and every call it makes into a measured layer in a span of its own (see
``common.Spans``).  :func:`reduce_xspace` keeps from the profiler's
XSpace only what the metrics need: the device's operations (one line of
each TPU plane), the benchmark's host spans, and the traced window, all
in nanoseconds from the window's start.  The functions below work on that
reduced form, which is plain JSON; ``fixtures/`` holds one recorded on
the chip, and the tests check these functions against it.

What each function counts:

* busy time: the union of the intervals in which some device operation
  ran, clipped to the window, averaged over the devices traced;
* idle gaps: the complement of that union inside the window, each gap
  named by the innermost benchmark span that covers its midpoint
  ("none" where no span does);
* an operation's time: the sum of its events' device durations, clipped
  to the window.
"""

from __future__ import annotations

import bisect
import collections
import glob
import gzip
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# An XLA op event is named by its HLO text, "%fusion.5 = (...) fusion(...)":
# keep the instruction's name without its number.
OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")
OPS_LINE = "XLA Ops"
WINDOW = "traced_window"


def find_xspace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xspace(path: str):
    from jax.profiler import ProfileData
    raw = open(path, "rb").read()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def op_name(text: str) -> str:
    m = OP_NAME.match(text)
    return m.group(1) if m else text[:64]


def reduce_xspace(pd, span_names) -> dict:
    """{"window_ns", "devices": {plane: [[op, start, dur], ...]},
    "spans": [[name, start, dur, args], ...]}, times relative to the
    traced window's start."""
    spans, window = [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in span_names:
                    args = {k: v for k, v in ev.stats
                            if isinstance(v, (int, float, str))}
                    spans.append([ev.name, ev.start_ns, ev.duration_ns, args])
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    t0, t1 = window
    devices = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = max(ev.start_ns, t0), min(ev.start_ns + ev.duration_ns,
                                                 t1)
                if b > a:
                    ops.append([op_name(ev.name), a - t0, b - a])
        devices[plane.name] = sorted(ops, key=lambda o: o[1])
    spans = [[n, s - t0, d, a] for n, s, d, a in spans
             if s >= t0 and s + d <= t1]
    return {"window_ns": t1 - t0, "devices": devices,
            "spans": sorted(spans, key=lambda s: s[1])}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(red: dict) -> float:
    """Mean over traced devices of the union of operation intervals."""
    per = [sum(b - a for a, b in _union((s, s + d) for _, s, d in ops))
           for ops in red["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def idle_share(red: dict) -> float | None:
    if not red["devices"] or red["window_ns"] <= 0:
        return None
    return 1.0 - busy_ns(red) / red["window_ns"]


def _covering(spans, name):
    """A lookup of the span named ``name`` that covers a time: spans of
    one name come from one host thread, so they do not overlap, and the
    only candidate is the last one to start at or before that time."""
    mine = sorted((s[1], s[1] + s[2]) for s in spans if s[0] == name)
    starts = [a for a, _ in mine]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        return mine[i] if i >= 0 and t < mine[i][1] else None
    return find


def idle_gaps(red: dict) -> list:
    """Idle time on the first traced device, summed by the benchmark span
    the host was in: [[span name, seconds], ...], longest first, <= 10."""
    if not red["devices"]:
        return []
    ops = red["devices"][sorted(red["devices"])[0]]
    busy = _union((s, s + d) for _, s, d in ops)
    gaps, t = [], 0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if red["window_ns"] > t:
        gaps.append((t, red["window_ns"]))
    finders = {n: _covering(red["spans"], n)
               for n in {s[0] for s in red["spans"]}}
    by = collections.Counter()
    for a, b in gaps:
        mid = (a + b) / 2
        inner = [(c[1] - c[0], n) for n, f in finders.items()
                 if (c := f(mid)) is not None]
        by[min(inner)[1] if inner else "none"] += (b - a) / 1e9
    return [[n, v] for n, v in by.most_common(10)]


def top_ops(red: dict, n: int = 10) -> list:
    """[[op name, seconds], ...] of the device operations that took most
    self time, summed over their events and over the traced devices.  An
    operation that holds others (a ``while`` around its body's fusions)
    is charged only what its children leave over."""
    by = collections.Counter()
    for ops in red["devices"].values():
        stack = []                      # [name, end, self time] open ops
        for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
            while stack and stack[-1][1] <= s:
                done = stack.pop()
                by[done[0]] += done[2] / 1e9
            if stack:
                stack[-1][2] -= min(d, stack[-1][1] - s)
            stack.append([name, s + d, d])
        for name, _, self_ns in stack:
            by[name] += self_ns / 1e9
    return [[k, v] for k, v in by.most_common(n)]


def op_seconds(red: dict, match) -> float:
    """Seconds of every device operation in the window whose name
    satisfies ``match``, summed over its events and the traced devices."""
    return sum(d for ops in red["devices"].values()
               for name, _, d in ops if match(name)) / 1e9


def breakdown(red: dict) -> dict:
    return {"device_ops": top_ops(red), "idle_gaps": idle_gaps(red)}
