"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a configuration file and a
traffic file; the traffic file's ``kind`` names the driver under
``bench/drivers/`` that runs it, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` the last line of
standard output carries the cell's end-to-end metrics; with ``--trace 1``
a short steady part of the window is traced with ``jax.profiler`` and the
line carries the per-layer metrics, the device's busy time and the
breakdown.  Every run checks what its window produced against the plain
reference and prints each number compared beside its limit, last on
standard error and last in the result line.  ``--control 1`` puts the
reference, one precision below the configuration's, in the program's
place for what is compared, and has to come out as not correct.

The run exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from benchlib import common, trace as trace_lib  # noqa: E402
from benchlib.peaks import peaks_for  # noqa: E402

SPAN_NAMES = ("server_call", "submit", "generator", "encode", "prune",
              "pack")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """One run's state, handed to the cell's driver."""

    def __init__(self, args, cell, devices, t_start):
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.control = bool(args.trace), bool(args.control)
        self.cell, self.devices, self.t_start = cell, devices, t_start
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.spans = common.Spans(self.trace)
        self.counter = common.CompileCounter()
        self.cfg = common.model_config(self.config["model"])
        self.params = common.make_weights(self.cfg,
                                          self.config["weights_seed"])
        self.tracing = False
        self.traced_s = None
        self.trace_dir = os.path.join(common.CACHE, "traces", cell["name"])
        self._window = None
        self.setup_s = None

    def setup_done(self) -> float:
        self.spans.items.clear()             # warm-up calls are not the window
        # What set-up made (the pool, the index, compiled programs) is
        # moved out of the collector's reach, so that its full passes in
        # the window scan only what the window makes.
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t_start
        common.info(f"set-up {self.setup_s:.3f} s")
        return self.setup_s

    def trace_start(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no Python calls
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
        self._window.__enter__()
        self.tracing = True
        self._t_trace = time.perf_counter()

    def trace_stop(self):
        import jax
        t = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False
        self.traced_s = (self._t_trace, t)


def per_layer(cell, ctx) -> dict:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell["per_layer"]:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                          "metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, chip_check=True, t_start=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    import jax
    if chip_check:
        devices = common.require_chips(cell["chips"])
    else:
        devices = jax.devices()[:cell["chips"]]
    common.info(f"compile cache: {common.enable_compile_cache()}")
    run = Run(args, cell, devices, T_START if t_start is None else t_start)
    driver = load_module(os.path.join(BENCH, "drivers",
                                      cell["traffic"]["kind"] + ".py"),
                         "driver_" + cell["traffic"]["kind"])
    res = driver.run(run)
    common.info(f"compiles inside the window: {run.counter.counts}")
    if "late_ms" in res:
        common.info(f"generator lateness (ms): {res['late_ms']}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak"]}
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    breakdown = None
    if run.trace:
        red = trace_lib.reduce_xspace(
            trace_lib.load_xspace(trace_lib.find_xspace(run.trace_dir)),
            SPAN_NAMES)
        device["busy_s"] = trace_lib.busy_ns(red) / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        breakdown = trace_lib.breakdown(red)
        peaks = (peaks_for(devices[0].device_kind) if chip_check
                 else peaks_for("TPU v5 lite"))
        ctx = {"trace": red, "spans": run.spans.items, "e2e": res["e2e"],
               "counters": res["counters"], "facts": res["facts"],
               "traced": res["traced"], "peaks": peaks,
               "config": run.config, "traffic": run.traffic}
        metrics = per_layer(cell, ctx)
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in res["e2e"].items() if k in units}
    correct = all(v <= lim for _, v, lim in res["checks"])
    if run.control:
        common.info("control: the reference one precision lower answered "
                    "in the program's place")
    for name, value, limit in res["checks"]:
        common.info(f"check {name}: {value!r} (limit {limit!r})")
    print(common.result_line(correct=correct, attempted=res["attempted"],
                             failed=res["failed"], metrics=metrics,
                             device=device, checks=res["checks"],
                             breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except common.NoChip as e:
        print(str(e), file=sys.stderr)
        sys.exit(2)
