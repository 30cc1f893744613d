"""Readings shared by the per-layer metric readers in ``bench/metrics``:
which device operations are the scorer kernel, and the serving and
build shares of the chip's peak."""

from __future__ import annotations

import re

from benchlib import serving, trace, work

# The serving scorer kernel's device operations (the Pallas MaxSim
# kernels of kernels/colbert_maxsim, fp32 and residual).
SCORER = re.compile(r"maxsim", re.IGNORECASE)


def maxsim_roofline(ctx):
    """Percent of the least time that the scorer kernel's work needs over
    the device time of every scorer-kernel event in the traced window.
    The work is ``work.serve_call_work`` of the real rows of each server
    call that lies wholly inside the window, by the peak that bounds it.
    Kernels of a call cut by the window's edge count in the time and not
    in the work, so the share can only read low there, never high."""
    red = ctx["trace"]
    if red is None:
        return None
    kernel_s = trace.op_seconds(red, lambda n: bool(SCORER.search(n)))
    if kernel_s <= 0:
        return None
    least = sum(work.least_time(*serving.call_work(ctx["facts"],
                                                   int(s[3]["n_real"])),
                                ctx["peaks"])[0]
                for s in red["spans"] if s[0] == "server_call")
    return 100.0 * least / kernel_s


def serve_mfu(ctx):
    """Percent of the bf16 peak: the least FLOPs of the queries answered
    in the traced window (work.serve_query_flops each) over its length."""
    t, f = ctx["traced"], ctx["facts"]
    if not t or t["seconds"] <= 0 or not t["answered"]:
        return None
    flops = t["answered"] * work.serve_query_flops(f["l"], f["dim"],
                                                   f["kept_tokens"])
    return 100.0 * flops / t["seconds"] / ctx["peaks"].flops


def idle_share(ctx):
    red = ctx["trace"]
    share = None if red is None else trace.idle_share(red)
    return None if share is None else 100.0 * share
