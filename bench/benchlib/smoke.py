"""Smoke-size cells for CPU rehearsals of the benchmark's drivers: the
cell's own files with the model, corpus, samples and traffic cut to what
the Pallas interpreter runs in seconds."""

from __future__ import annotations

import copy
import importlib.util
import json
import os

from benchlib import common

MODEL = {"name": "colbert-smoke", "vocab": 512, "n_layers": 2,
         "d_model": 64, "n_heads": 4, "d_ff": 128, "out_dim": 32,
         "query_len": 8, "doc_len": 24, "norm": "sphere",
         "dtype": "float32"}
DOC_LAW = {"median": 12, "sigma": 0.5, "min": 2, "max": 24}
QUERY_LAW = {"median": 4, "sigma": 0.4, "min": 2, "max": 8}


def cell(name: str) -> dict:
    c = copy.deepcopy(common.load_cell(name))
    cfg, t = c["config"], c["traffic"]
    cfg["model"] = dict(MODEL)
    cfg["corpus"].update(n_docs=48, doc_lengths=dict(DOC_LAW))
    cfg["prune"]["n_samples"] = 256
    # On the CPU the program prunes in fp32, whatever a TPU would do.
    cfg["precision"]["prune"] = "highest"
    if cfg["codec"].get("n_centroids"):
        cfg["codec"]["n_centroids"] = 8
    if t["kind"] == "open_loop":
        t.update(rate_qps=60, trace_start_s=0.2, trace_s=0.3,
                 check_queries=16, query_lengths=dict(QUERY_LAW))
    elif t["kind"] == "build_slabs":
        t.update(slab_docs=8, doc_lengths=dict(DOC_LAW),
                 length_ranges=[[0, 8], [8, 16], [16, 24]], check_slabs=1,
                 trace_after_slabs=1, trace_slabs=1)
    return c


def load_run_module():
    path = os.path.join(common.BENCH, "run.py")
    spec = importlib.util.spec_from_file_location("bench_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(monkeypatch, tmp_path, capsys, name: str, *, seconds=0.4,
             trace=0, seed=2 ** 40 + 7, control=0, traffic=None,
             precision=None) -> dict:
    """One run of the smoke-size cell on the CPU, skipping the look for a
    chip; returns its result line, with what it printed on standard error
    under ``"stderr"``.  ``traffic`` and ``precision`` override keys of
    the traffic file and of the configuration's precisions."""
    smoke = cell(name)
    smoke["traffic"].update(traffic or {})
    smoke["config"]["precision"].update(precision or {})
    monkeypatch.setattr(common, "load_cell", lambda n, root=None: smoke)
    monkeypatch.setattr(common, "CACHE", str(tmp_path))
    monkeypatch.setattr(common, "enable_compile_cache", lambda: "off")
    run = load_run_module()
    run.main(["--workload", name, "--seed", str(seed), "--seconds",
              str(seconds), "--trace", str(trace), "--control",
              str(control)], chip_check=False)
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    res["stderr"] = out.err
    return res
