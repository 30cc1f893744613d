"""CPU rehearsals of every cell's driver at smoke size, and the faults the
comparison has to catch: each drives the rest of a run with the timed
path broken underneath and sees ``correct`` come out false."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import common, smoke  # noqa: E402

CELLS = ("res2-stream", "fp32-build")


def run(monkeypatch, tmp_path, capsys, name, **kw):
    return smoke.run_cell(monkeypatch, tmp_path, capsys, name, **kw)


def _metrics(cell, kind):
    return {m["name"] for m in smoke.cell(cell)[kind]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(monkeypatch, tmp_path, capsys, name):
    out = run(monkeypatch, tmp_path, capsys, name)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == _metrics(name, "end_to_end")
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert "setup_s" in out["metrics"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-2:] == ["checks", "stderr"]
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("name", CELLS)
def test_cell_traced(monkeypatch, tmp_path, capsys, name):
    out = run(monkeypatch, tmp_path, capsys, name, trace=1)
    assert out["correct"] is True, out["checks"]
    # The CPU trace has no TPU plane: readers of device operations find
    # nothing and their metrics are left out, never reported as 0.
    allowed = _metrics(name, "per_layer")
    assert set(out["metrics"]) <= allowed
    assert not any(k.startswith(("maxsim_roofline", "idle_share"))
                   for k in out["metrics"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_answers(monkeypatch):
    from repro.serve import retrieval
    orig = retrieval.RetrievalServer.query_batch

    def altered(self, q):
        out = orig(self, q)
        ids = np.array(out.top_idx)
        ids[:, 0] = (ids[:, -1] + 1) % self.index.n_docs
        res = retrieval.TopKResult(ids, out.top_scores, out.coverage)
        res.epoch_key = out.epoch_key
        return res
    monkeypatch.setattr(retrieval.RetrievalServer, "query_batch", altered)


@pytest.mark.parametrize("name,fault", [("res2-stream", _alter_answers)])
def test_serving_fault_is_not_correct(monkeypatch, tmp_path, capsys, name,
                                      fault):
    fault(monkeypatch)
    out = run(monkeypatch, tmp_path, capsys, name)
    assert out["correct"] is False
    assert out["checks"]["gap"]["value"] > out["checks"]["gap"]["limit"]


def _keep_unchanged(monkeypatch):
    from repro.core import pruning_pipeline
    monkeypatch.setattr(pruning_pipeline, "prune_corpus",
                        lambda e, mk, s, f, **kw: (mk, None, None))


def _half_slab(monkeypatch):
    import jax.numpy as jnp
    from repro.core import pruning_pipeline
    orig = pruning_pipeline.prune_corpus

    def half(e, mk, s, f, **kw):
        h = e.shape[0] // 2
        keep, _, _ = orig(e[:h], mk[:h], s, f, **kw)
        return jnp.concatenate([keep, mk[h:]]), None, None
    monkeypatch.setattr(pruning_pipeline, "prune_corpus", half)


def _alter_token(monkeypatch):
    from repro.serve.index import PackedIndex
    orig = PackedIndex.pack.__func__

    def altered(cls, *a, **kw):
        out = orig(cls, *a, **kw)
        b = out.buckets[0]
        b.embs = b.embs.at[0, 0, 0].add(1.0)
        return out
    monkeypatch.setattr(PackedIndex, "pack", classmethod(altered))


@pytest.mark.parametrize("fault,check", [
    (_keep_unchanged, "keep_mismatch"),
    (_half_slab, "keep_mismatch"),
    (_alter_token, "pack_mismatch"),
])
def test_build_fault_is_not_correct(monkeypatch, tmp_path, capsys, fault,
                                    check):
    fault(monkeypatch)
    out = run(monkeypatch, tmp_path, capsys, "fp32-build")
    assert out["correct"] is False
    assert out["checks"][check]["value"] > out["checks"][check]["limit"]


def test_build_control_is_not_correct(monkeypatch, tmp_path, capsys):
    """The Voronoi reference one precision below the configuration's
    (fp8 operands for bf16 pruning), put in the program's place, fails
    the keep-mask limit and the run is not correct."""
    out = run(monkeypatch, tmp_path, capsys, "fp32-build", control=1,
              traffic={"slab_docs": 32, "check_slabs": 2},
              precision={"prune": "bf16"})
    assert out["correct"] is False
    assert out["checks"]["keep_mismatch"]["value"] > \
        out["checks"]["keep_mismatch"]["limit"]
    assert out["checks"]["pack_mismatch"]["value"] == 0


def test_serving_cell_control_is_not_correct(monkeypatch, tmp_path, capsys):
    """The exhaustive reference one precision below the configuration's,
    put in the program's place, answers the sampled queries and the run
    is not correct.  At smoke widths the rounding of three bf16 passes is
    too small to read, so the smoke configuration serves at bf16 and the
    control runs at fp8."""
    out = run(monkeypatch, tmp_path, capsys, "res2-stream", control=1,
              precision={"serve": "bf16"})
    assert out["correct"] is False
    assert "control: the reference one precision lower" in out["stderr"]
    assert out["checks"]["gap"]["value"] > out["checks"]["gap"]["limit"]


def test_serving_control_is_not_correct():
    """At the serving widths (dim 128, 32 query tokens), on tokens that
    share a direction as encoder outputs do (scores near 31, as on the
    chip), the exhaustive reference at three bf16 passes departs from the
    fp32 one by more than the configurations' answer-gap limit."""
    from benchlib import reference
    limits = [common.load_json(os.path.join(common.ROOT, c["file"]))["limits"]
              for c in common.load_json(os.path.join(
                  common.ROOT, "BENCHMARK.json"))["configs"]]
    limit = min(x["serve_gap"] for x in limits if "serve_gap" in x)
    rng = np.random.default_rng(7)

    base = rng.standard_normal(128).astype(np.float32)

    def unit(*shape):
        x = rng.standard_normal(shape).astype(np.float32) + 5.0 * base
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    docs, q = unit(512, 64, 128), unit(16, 32, 128)
    masks = np.arange(64)[None] < rng.integers(8, 65, 512)[:, None]
    ref = reference.maxsim_scores(q, docs, masks)
    ids, sc = reference.topk(ref, 10)
    assert reference.answer_gap(ids, sc, ref)["gap"] == 0.0
    low = reference.maxsim_scores(q, docs, masks, precision="high")
    c_ids, c_sc = reference.topk(low, 10)
    assert reference.answer_gap(c_ids, c_sc, ref)["gap"] > limit


def test_same_seed_same_inputs(monkeypatch, tmp_path, capsys):
    a = run(monkeypatch, tmp_path, capsys, "res2-stream", seed=2 ** 33 + 5)
    b = run(monkeypatch, tmp_path / "b", capsys, "res2-stream",
            seed=2 ** 33 + 5)
    assert a["checks"] == b["checks"]


def test_no_chip_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(common.BENCH, "run.py"),
                        "--workload", "res2-stream", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env,
                       cwd=common.ROOT, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bench_files_alone_exit_without_result(tmp_path):
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", ".scratch",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "res2-stream", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=tmp_path, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_its_files():
    bench = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(common.ROOT, c["file"]))
        assert common.load_json(os.path.join(common.ROOT,
                                             c["file"]))["name"] == c["name"]
    for w in bench["workloads"]:
        t = common.load_json(os.path.join(common.BENCH, "traffic",
                                          w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(common.BENCH, "drivers",
                                           t["kind"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(common.BENCH, "metrics",
                                           m["name"] + ".py"))
    assert json.dumps(bench).isascii()
