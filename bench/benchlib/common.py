"""What every cell's run shares: the cell's files, the chip check, the
compile cache, seeded weights, host spans, the in-window compile count,
and the result line."""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


class NoChip(SystemExit):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration and
    traffic files read, and the metrics that this cell reports."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; have {sorted(cells)}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    cell["config"] = load_json(os.path.join(root,
                                            configs[cell["config"]]["file"]))
    cell["traffic"] = load_json(os.path.join(BENCH, "traffic",
                                             cell["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    cell["end_to_end"] = mine(bench["end_to_end"])
    cell["per_layer"] = mine(bench["per_layer"])
    return cell


def require_chips(n: int):
    """The devices of the chip this run measures; exits non-zero, before
    any result, when JAX finds no TPU or fewer than ``n`` chips, or when
    the kernels would run in the Pallas interpreter."""
    import jax
    from repro.core import backend as backend_lib
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: JAX found {devices[0].platform} "
                     "devices")
    if len(devices) < n:
        raise NoChip(f"bench: the cell needs {n} TPU chips, found "
                     f"{len(devices)}")
    if backend_lib.default_interpret(None):
        raise NoChip("bench: Pallas kernels would run in interpret mode")
    if os.environ.get("REPRO_BACKEND"):
        raise NoChip("bench: REPRO_BACKEND is set; cells run the platform "
                     "defaults")
    return devices[:n]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (inside the checkout
    unless JAX_COMPILATION_CACHE_DIR says otherwise), with every program
    cached however fast it compiled, so that only a cell's first run in a
    checkout compiles."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def model_config(model: dict):
    """The ColBERT encoder configuration a config file states."""
    import jax.numpy as jnp
    from repro.models.colbert import ColBERTConfig
    dtype = getattr(jnp, model["dtype"])
    return ColBERTConfig(
        name=model["name"], vocab=model["vocab"],
        n_layers=model["n_layers"], d_model=model["d_model"],
        n_heads=model["n_heads"], d_ff=model["d_ff"],
        out_dim=model["out_dim"], query_len=model["query_len"],
        doc_len=model["doc_len"], norm=model["norm"],
        param_dtype=dtype, compute_dtype=dtype)


def make_weights(cfg, seed: int):
    """Encoder weights from ``seed``, made on the device in one jitted
    call, in the type they are served in."""
    import functools
    import jax
    from repro.models import colbert
    init = jax.jit(functools.partial(colbert.init_params, cfg=cfg))
    params = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


class Spans:
    """Host spans of the benchmark's own calls into each layer: kept in
    memory as (name, start, end, args) on ``time.perf_counter``, and
    written into the profiler's trace while one is being taken.  Off
    (a no-op) unless the run traces."""

    def __init__(self, on: bool):
        self.on = on
        self.items: list = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str, **args):
        if not self.on:
            yield
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name, **args):
            yield
        t1 = time.perf_counter()
        with self._lock:
            self.items.append((name, t0, t1, args))


class CompileCounter:
    """Counts JAX traces, lowerings and compilations (including those
    served from the persistent cache) while ``armed``."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "cache_loads"}

    def __init__(self):
        import jax
        self.armed = False
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


class GcPauses:
    """The garbage collector's pauses while open: how many, and the
    longest, to tell a collection from other host stalls."""

    def __init__(self):
        import gc
        self.n, self.longest, self.gen, self._t = 0, 0.0, None, None
        self._gc = gc
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.n += 1
            if d > self.longest:
                self.longest, self.gen = d, info["generation"]

    def close(self):
        self._gc.callbacks.remove(self._on)

    def __str__(self):
        return (f"{self.n} gc collections, the longest "
                f"{self.longest * 1e3:.3f} ms (generation {self.gen})")


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), by linear interpolation between the
    closest ranks; +inf entries (requests never answered) count."""
    v = np.sort(np.asarray(values, np.float64))
    if not v.size:
        return float("nan")
    return float(np.percentile(v, q, method="linear"))


def info(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list,
                breakdown: dict | None = None) -> str:
    """The run's last stdout line.  ``checks`` is [(name, value, limit)];
    it comes last, under its own key."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return json.dumps(out)
