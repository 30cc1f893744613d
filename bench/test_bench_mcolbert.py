"""CPU rehearsals of the ``mcolbert-build`` cell (GTE-ModernColBERT-v1 at
PyLate's 300-token documents) at smoke size: its driver end to end, the
encoder's work count by hand, the benchmark's encoder reference against
the program, the control and the program faults the ``encode_gap`` check
has to catch, and the two new metric readers."""

import copy
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import common, reference_modernbert, smoke  # noqa: E402
from benchlib import work_modernbert  # noqa: E402

CELL = "mcolbert-build"
MODEL = {"name": "gte-moderncolbert-smoke", "vocab": 512, "n_layers": 4,
         "d_model": 64, "n_heads": 4, "d_ff": 96, "out_dim": 32,
         "query_len": 8, "doc_len": 40, "norm": "sphere",
         "dtype": "float32", "backbone": "modernbert", "global_every": 3,
         "local_window": 8, "rope_theta": 160000.0,
         "local_rope_theta": 10000.0, "attend_expansion": False}


def smoke_cell() -> dict:
    """The cell's own files with the model, samples and slabs cut to what
    the CPU runs in seconds.  On the CPU the program encodes and prunes
    in fp32; the encoder's stated precision stays bf16, so that the
    control (fp8) is the one the chip's runs use."""
    c = copy.deepcopy(common.load_cell(CELL))
    c["config"]["model"] = dict(MODEL)
    c["config"]["prune"]["n_samples"] = 256
    c["config"]["precision"]["prune"] = "highest"
    c["traffic"].update(slab_docs=8,
                        doc_lengths={"median": 20, "sigma": 0.5, "min": 2,
                                     "max": 40},
                        length_ranges=[[0, 8], [8, 16], [16, 32], [32, 40]],
                        check_slabs=1, trace_after_slabs=1, trace_slabs=1)
    return c


def run(monkeypatch, tmp_path, capsys, *, trace=0, control=0,
        seed=2 ** 40 + 11) -> dict:
    cell = smoke_cell()
    monkeypatch.setattr(common, "load_cell", lambda n, root=None: cell)
    monkeypatch.setattr(common, "CACHE", str(tmp_path))
    monkeypatch.setattr(common, "enable_compile_cache", lambda: "off")
    smoke.load_run_module().main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "0.4",
         "--trace", str(trace), "--control", str(control)],
        chip_check=False)
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    res["stderr"] = out.err
    return res


def test_cell_runs_correct(monkeypatch, tmp_path, capsys):
    out = run(monkeypatch, tmp_path, capsys)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"build_docs_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["checks"]) == {"pack_mismatch", "keep_mismatch",
                                  "encode_gap"}
    assert out["checks"]["encode_gap"]["value"] < 1e-5
    assert out["attempted"] > 0 and out["failed"] == 0
    # Nothing is lowered or compiled inside the window.
    assert "'lowerings': 0, 'compiles': 0, 'cache_loads': 0}" in \
        out["stderr"]


def test_cell_traced(monkeypatch, tmp_path, capsys):
    out = run(monkeypatch, tmp_path, capsys, trace=1)
    assert out["correct"] is True, out["checks"]
    names = {m["name"] for m in smoke_cell()["per_layer"]}
    assert names == {"prune_ms_per_doc.build", "idle_share.build",
                     "encode_mfu.build", "build_mfu.build",
                     "encode_pad_share.mbuild"}
    # The CPU trace has no TPU plane, so the idle share is left out.
    assert set(out["metrics"]) == names - {"idle_share.build"}
    pad = out["metrics"]["encode_pad_share.mbuild"]["value"]
    assert 0 < pad < 100


def test_control_is_not_correct(monkeypatch, tmp_path, capsys):
    """The encoder reference at fp8 (one below the stated bf16), put in
    the program's place, fails the encode_gap limit."""
    out = run(monkeypatch, tmp_path, capsys, control=1)
    assert out["correct"] is False
    gap = out["checks"]["encode_gap"]
    assert gap["value"] > gap["limit"]
    assert out["checks"]["pack_mismatch"]["value"] == 0


def _faulty(monkeypatch, change):
    """The program's document encoder with ``change`` made to what it
    computes, freshly traced so no cached program answers instead."""
    import jax
    from repro.launch import serve
    from repro.models import colbert

    def encode_docs(params, cfg, ids):
        bad = change(cfg)
        return jax.jit(lambda p, i: colbert.encode_docs(p, bad, i))(params,
                                                                    ids)
    monkeypatch.setattr(serve, "encode_docs", encode_docs)


def _no_window(monkeypatch):
    _faulty(monkeypatch, lambda c: dataclasses.replace(c,
                                                       local_window=10 ** 4))


def _swapped_rope(monkeypatch):
    _faulty(monkeypatch, lambda c: dataclasses.replace(
        c, rope_theta=c.local_rope_theta, local_rope_theta=c.rope_theta))


def _swiglu(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.models import transformer

    def swiglu(x, w_in, w_out):
        a, g = jnp.split(x @ w_in, 2, axis=-1)
        return (jax.nn.silu(a) * g) @ w_out
    monkeypatch.setattr(transformer, "geglu", swiglu)
    _faulty(monkeypatch, lambda c: c)


@pytest.mark.parametrize("fault", [_no_window, _swapped_rope, _swiglu])
def test_encoder_fault_is_not_correct(monkeypatch, tmp_path, capsys, fault):
    fault(monkeypatch)
    out = run(monkeypatch, tmp_path, capsys)
    assert out["correct"] is False
    gap = out["checks"]["encode_gap"]
    assert gap["value"] > gap["limit"]


def test_encoder_reference_matches_program():
    """The benchmark's reference reads the program's weights and lands on
    its fp32 embedding to rounding; bf16 and fp8 rounding move it by
    more, in that order."""
    import jax
    import jax.numpy as jnp
    from repro.models import colbert
    from benchlib import inputs
    limit = common.load_json(os.path.join(
        common.BENCH, "configs", "gte-moderncolbert.json"))["limits"][
            "encode_gap"]
    drv = smoke.load_run_module().load_module(
        os.path.join(common.BENCH, "drivers", "build_encoder_slabs.py"),
        "driver_build_encoder_slabs")
    c = drv.model_config(MODEL)
    params = colbert.init_params(jax.random.PRNGKey(3), c)
    rng = inputs.rng_for(9)
    ids = inputs.token_ids(rng, rng.integers(2, 41, 12), 40, 512,
                           inputs.D_MARK)
    with jax.default_matmul_precision("highest"):
        got, mask = colbert.encode_docs(params, c, jnp.asarray(ids))
    gaps = {}
    for prec in ("highest", "bf16", "fp8"):
        want, m = reference_modernbert.encode_docs(params, MODEL, ids,
                                                   precision=prec, block=5)
        assert np.array_equal(m, np.asarray(mask))
        gaps[prec] = reference_modernbert.encode_gap(got, want, m)
    assert gaps["highest"] < 1e-6
    assert gaps["highest"] < gaps["bf16"] < limit < gaps["fp8"]


def test_work_counts_by_hand():
    model = {"n_layers": 22, "d_model": 768, "d_ff": 1152, "out_dim": 128,
             "global_every": 3, "local_window": 128}
    assert work_modernbert.n_global(model) == 8
    # n = 40: every pair lies inside +-64, so local layers attend n^2.
    assert work_modernbert.local_pairs(40, 128) == 40 * 40
    # n = 300: 300 on the diagonal, 2 x (299 + 298 + ... + 236) off it.
    assert work_modernbert.local_pairs(300, 128) == 300 + 2 * sum(
        range(236, 300)) == 34540
    for n, pairs in ((40, 1600), (300, 34540)):
        dense = 22 * (8 * n * 768 ** 2 + 6 * n * 768 * 1152)
        attn = 4 * 768 * (8 * n * n + 14 * pairs)
        assert work_modernbert.encoder_flops(n, model) == (
            dense + attn + 2 * n * 768 * 128)
    assert work_modernbert.local_pairs(0, 128) == 0
    assert work_modernbert.local_pairs(1, 128) == 1


def _reader(name):
    return smoke.load_run_module().load_module(
        os.path.join(common.BENCH, "metrics", name + ".py"), "m")


def test_readers_from_a_synthetic_ctx():
    """The accepted encoder and build readers take this cell's FLOPs from
    its spans and traced work as they take fp32-build's; the new reader
    takes the program's counter."""
    from benchlib.peaks import peaks_for
    peaks = peaks_for("TPU v5 lite")
    model = common.load_cell(CELL)["config"]["model"]
    enc = work_modernbert.encoder_flops(190, model)
    spans = [("encode", 0.0, 0.5, {"docs": 64, "flops": 197e12 * 0.1}),
             ("prune", 0.5, 1.0, {"docs": 64, "flops": 1}),
             ("encode", 1.0, 1.5, {"docs": 64, "flops": 197e12 * 0.2})]
    mfu = _reader("encode_mfu.build").read({"spans": spans,
                                           "peaks": peaks})
    assert mfu == pytest.approx(30.0)
    assert _reader("encode_mfu.build").read({"spans": [],
                                            "peaks": peaks}) is None
    build = _reader("build_mfu.build")
    assert build.read({"traced": {"flops": 64 * enc, "seconds": 0.5},
                       "peaks": peaks}) == pytest.approx(
        100 * 64 * enc / 0.5 / 197e12)
    assert build.read({"traced": {}, "peaks": peaks}) is None
    pad = _reader("encode_pad_share.mbuild")
    assert pad.read({"counters": {"encode_real_tokens": 300,
                                  "encode_slots": 400}}) == 25.0
    # A program without the counter (the parent's) gives nothing to read.
    assert pad.read({"counters": {"slabs": 3}}) is None
