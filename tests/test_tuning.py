"""Shape-aware autotuner: determinism, cache keying, legality of every
emitted config, and the one-shot guarantee of measured mode."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _proptest import sweep
from repro.core import backend as backend_lib
from repro.core import sampling, tuning, voronoi


@pytest.fixture(autouse=True)
def _fresh_cache():
    tuning.clear_cache()
    yield
    tuning.clear_cache()


class TestHeuristics:
    @sweep(n_cases=12, seed=0, n_samples=[64, 2048, 100_000],
           m=[2, 8, 48, 180, 1000], dim=[8, 128, 768])
    def test_pruning_configs_always_legal(self, n_samples, m, dim):
        for platform in ("cpu", "tpu"):
            cfg = tuning.heuristic_config("pruning", n_samples=n_samples,
                                          m=m, dim=dim, platform=platform)
            cfg.validate()
            assert cfg.shortlist >= cfg.rescan_every + 1  # exactness bound
            assert cfg.shortlist <= max(m, 2)
            assert cfg.block_s % 8 == 0
        # on TPU the tiles must genuinely fit the VMEM budget
        cfg = tuning.heuristic_config("pruning", n_samples=n_samples,
                                      m=m, dim=dim, platform="tpu")
        assert 4 * (cfg.block_s * dim + cfg.block_t * dim
                    + cfg.block_s * cfg.block_t) \
            <= tuning.DEFAULT_VMEM_BUDGET

    @sweep(n_cases=8, seed=1, n_q=[1, 16, 200], n_docs=[8, 256, 10_000],
           m=[16, 128, 512], l=[8, 32])
    def test_serving_configs_always_legal(self, n_q, n_docs, m, l):
        cfg = tuning.heuristic_config("serving", n_q=n_q, n_docs=n_docs,
                                      m=m, l=l, dim=128)
        cfg.validate()
        assert cfg.block_docs >= 1 and cfg.block_q >= 1
        assert cfg.block_q <= max(tuning._pow2_at_least(n_q), 1)

    def test_deterministic(self):
        a = tuning.heuristic_config("pruning", n_samples=2048, m=48, dim=128)
        b = tuning.heuristic_config("pruning", n_samples=2048, m=48, dim=128)
        assert a == b

    def test_vmem_budget_shrinks_tiles(self):
        big = tuning.heuristic_config("pruning", n_samples=4096, m=512,
                                      dim=768)
        small = tuning.heuristic_config("pruning", n_samples=4096, m=512,
                                        dim=768, vmem_budget=256 * 1024)
        assert small.block_s <= big.block_s
        assert 4 * (small.block_s * 768 + small.block_t * 768
                    + small.block_s * small.block_t) <= 256 * 1024 \
            or small.block_s == 8  # floor reached

    def test_pruning_docs_per_block(self):
        """TPU dispatches fit the HBM budget (256 docs at the full
        10k-sample, 180-token shape); off-TPU the bucket goes whole."""
        assert tuning.pruning_docs_per_block(10_000, 180, "tpu") == 256
        for n, w in ((64, 8), (10_000, 180), (100_000, 512)):
            per = tuning.pruning_docs_per_block(n, w, "tpu")
            assert per >= 1 and per & (per - 1) == 0
            assert per == 1 or 12 * n * w * per <= tuning.PRUNING_HBM_BUDGET
            assert tuning.pruning_docs_per_block(n, w, "cpu") is None

    def test_tpu_serving_blocks_are_sublane_tiles(self):
        """TPU serving doc blocks are whole sublane tiles that the VMEM
        model admits, shrinking as the bucket cap grows."""
        blocks = [tuning.heuristic_config(
            "serving", platform="tpu", n_q=16, n_docs=4096, m=m, l=32,
            dim=128).block_docs for m in (32, 64, 128, 180, 256)]
        assert all(b % 8 == 0 for b in blocks)
        assert blocks == sorted(blocks, reverse=True)

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="kind"):
            tuning.heuristic_config("nope", m=8)
        with pytest.raises(ValueError, match="kind"):
            tuning.shape_key("nope", {})

    def test_invalid_config_rejected(self):
        with pytest.raises(ValueError, match="exactness"):
            tuning.KernelConfig(shortlist=4, rescan_every=4).validate()
        with pytest.raises(ValueError, match="< 1"):
            tuning.KernelConfig(block_docs=0).validate()


class TestCacheKeying:
    def test_batchlike_axes_bucket_pow2(self):
        k1 = tuning.shape_key("pruning", dict(n_samples=1500, m=48, dim=128))
        k2 = tuning.shape_key("pruning", dict(n_samples=2048, m=48, dim=128))
        k3 = tuning.shape_key("pruning", dict(n_samples=2049, m=48, dim=128))
        assert k1 == k2 != k3

    def test_per_item_axes_exact(self):
        k1 = tuning.shape_key("pruning", dict(n_samples=2048, m=48, dim=128))
        k2 = tuning.shape_key("pruning", dict(n_samples=2048, m=49, dim=128))
        assert k1 != k2

    def test_kind_platform_mode_disambiguate(self):
        base = dict(m=48, dim=128)
        assert tuning.shape_key("pruning", base) \
            != tuning.shape_key("serving", base)
        assert tuning.shape_key("pruning", base, platform="cpu") \
            != tuning.shape_key("pruning", base, platform="tpu")
        assert tuning.shape_key("pruning", base, measured=True) \
            != tuning.shape_key("pruning", base, measured=False)

    def test_codec_tag_keys_separately(self):
        """Compressed buckets tune independently of fp32 at the same
        shape — the in-kernel decode shifts the bandwidth/compute
        balance — while fp32 keys stay EXACTLY as before the codec
        existed (cache files from older builds keep hitting)."""
        base = dict(n_q=4, n_docs=256, m=16, l=8, dim=64)
        k_fp = tuning.shape_key("serving", base)
        k_r4 = tuning.shape_key("serving", base | {"codec": "residual4"})
        k_r2 = tuning.shape_key("serving", base | {"codec": "residual2"})
        k_i8 = tuning.shape_key("serving", base | {"codec": "int8"})
        assert len({k_fp, k_r4, k_r2, k_i8}) == 4
        assert ("codec", "residual4") in k_r4[-1]
        assert not any(n == "codec" for n, _ in k_fp[-1])

    def test_tuned_serving_blocks_codec_passthrough(self):
        """backend.tuned_serving_blocks(codec=...) creates a distinct
        cache entry; codec=None resolves through the original key."""
        shape = dict(n_q=4, n_docs=256, m=16, l=8, dim=64)
        backend_lib.tuned_serving_blocks(**shape)
        assert len(tuning.cache_info()) == 1
        backend_lib.tuned_serving_blocks(**shape, codec="residual4")
        assert len(tuning.cache_info()) == 2
        backend_lib.tuned_serving_blocks(**shape)       # hits entry 1
        backend_lib.tuned_serving_blocks(**shape, codec="residual4")
        assert len(tuning.cache_info()) == 2

    def test_tune_memoizes(self):
        a = tuning.tune("pruning", n_samples=2048, m=48, dim=128)
        assert len(tuning.cache_info()) == 1
        b = tuning.tune("pruning", n_samples=1100, m=48, dim=128)  # same bucket
        assert b is a and len(tuning.cache_info()) == 1
        tuning.tune("pruning", n_samples=2048, m=64, dim=128)
        assert len(tuning.cache_info()) == 2


class TestMeasuredMode:
    def test_one_shot_and_cached(self, monkeypatch):
        calls = []
        real = tuning._measure_pruning

        def counting(shape, base):
            calls.append(dict(shape))
            return real(dict(shape, n_samples=64, m=9, dim=4), base)

        monkeypatch.setattr(tuning, "_measure_pruning", counting)
        shape = dict(n_samples=64, m=9, dim=4)
        a = tuning.tune("pruning", measure=True, **shape)
        b = tuning.tune("pruning", measure=True, **shape)
        assert len(calls) == 1          # the race ran exactly once
        assert a is b
        a.validate()
        assert a.shortlist >= a.rescan_every + 1

    def test_env_var_measured_race_runs_real_candidates(self, monkeypatch):
        """Regression: with REPRO_AUTOTUNE=measure the real candidate
        race must terminate — the raced pruning calls pin every knob,
        and the cache is pre-seeded, so no re-entrant race can recurse."""
        monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
        cfg = tuning.tune("pruning", n_samples=64, m=12, dim=4)
        cfg.validate()

    def test_race_times_the_kernel_scan(self, monkeypatch):
        """The measured race times the pruning scan the chip runs: every
        candidate rescans through the ``maxsim_topk`` kernel."""
        seen = []
        real = voronoi.maxsim_topk_op

        def counting(*args, **kw):
            seen.append(kw["k"])
            return real(*args, **kw)

        monkeypatch.setattr(voronoi, "maxsim_topk_op", counting)
        jax.clear_caches()              # the kernel is called while tracing
        shape = dict(n_samples=72, m=11, dim=4)
        base = tuning.heuristic_config("pruning", **shape)
        best = tuning._measure_pruning(shape, base)
        raced = {max(2, min(k, 11)) for k in
                 (base.shortlist // 2, base.shortlist, base.shortlist * 2)}
        assert set(seen) == raced
        assert best.shortlist in raced

    def test_env_var_enables(self, monkeypatch):
        hits = []
        monkeypatch.setattr(tuning, "_measure_pruning",
                            lambda shape, base: hits.append(1) or base)
        monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
        tuning.tune("pruning", n_samples=64, m=9, dim=4)
        assert hits == [1]
        monkeypatch.setenv("REPRO_AUTOTUNE", "heuristic")
        tuning.clear_cache()
        tuning.tune("pruning", n_samples=64, m=9, dim=4)
        assert hits == [1]              # heuristic mode never measures


class TestConsumersConsultTuner:
    def test_shortlist_knobs_flow_from_tuner(self, monkeypatch):
        """pruning_order_batch with no explicit knobs must run with the
        tuner's (K, R) — pin an unusual-but-legal config and verify the
        flat path still matches the oracle (exactness is K/R-independent,
        so parity passing with the pinned config proves it was applied
        without breaking the result)."""
        seen = []
        pinned = tuning.KernelConfig(shortlist=5, rescan_every=3,
                                     block_s=32, block_t=16)

        def fake_tune(kind, **shape):
            seen.append(kind)
            return pinned

        monkeypatch.setattr(backend_lib, "tuned", fake_tune)
        d = jax.random.normal(jax.random.PRNGKey(0), (3, 14, 8)) * 0.5
        masks = jnp.arange(14)[None, :] < jnp.array([4, 14, 9])[:, None]
        S = sampling.sample_sphere(jax.random.PRNGKey(1), 300, 8)
        out = voronoi.pruning_order_batch(d, masks, S,
                                          backend="shortlist_topk")
        assert "pruning" in seen
        ref = voronoi.pruning_order_batch(d, masks, S, backend="reference")
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(ref[0]))

    def test_explicit_knobs_win(self, monkeypatch):
        def boom(kind, **shape):
            raise AssertionError("tuner consulted despite explicit knobs")

        monkeypatch.setattr(backend_lib, "tuned", boom)
        d = jax.random.normal(jax.random.PRNGKey(0), (10, 8)) * 0.5
        S = sampling.sample_sphere(jax.random.PRNGKey(1), 200, 8)
        voronoi.pruning_order_shortlist(d, jnp.ones((10,), bool), S,
                                        shortlist=6, rescan_every=4,
                                        block_s=32, block_t=16)


class TestPersistedCache:
    def test_dump_load_roundtrip(self, tmp_path):
        path = str(tmp_path / "tune.json")
        a = tuning.tune("pruning", n_samples=2048, m=48, dim=128)
        b = tuning.tune("serving", n_q=16, n_docs=256, m=128, l=32, dim=128)
        assert tuning.dump_cache(path) == 2
        tuning.clear_cache()
        assert tuning.cache_info() == {}
        assert tuning.load_cache(path) == 2
        # a reload serves the persisted configs without recomputation
        assert tuning.tune("pruning", n_samples=2048, m=48, dim=128) == a
        assert tuning.tune("serving", n_q=16, n_docs=256, m=128, l=32,
                           dim=128) == b

    def test_load_validates_entries(self, tmp_path):
        path = str(tmp_path / "tune.json")
        tuning.tune("pruning", n_samples=64, m=9, dim=4)
        tuning.dump_cache(path)
        import json
        with open(path) as f:
            payload = json.load(f)
        payload["entries"][0]["config"]["shortlist"] = 1   # breaks K >= R+1
        with open(path, "w") as f:
            json.dump(payload, f)
        tuning.clear_cache()
        with pytest.raises(ValueError, match="exactness"):
            tuning.load_cache(path)

    def test_newer_format_refused(self, tmp_path):
        path = str(tmp_path / "tune.json")
        import json
        with open(path, "w") as f:
            json.dump({"format": tuning._CACHE_FORMAT + 1, "entries": []}, f)
        with pytest.raises(IOError):
            tuning.load_cache(path)

    def test_env_hook_loads_and_dumps(self, tmp_path, monkeypatch):
        """REPRO_AUTOTUNE_CACHE: measured results land in the shared
        file; a fresh process (cleared cache) resolves from it without
        re-measuring."""
        path = str(tmp_path / "shared.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
        races = []
        pinned = tuning.KernelConfig(shortlist=6, rescan_every=5)
        monkeypatch.setattr(tuning, "_measure_pruning",
                            lambda shape, base: races.append(1) or pinned)
        monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
        got = tuning.tune("pruning", n_samples=64, m=9, dim=4)
        assert races == [1] and got == pinned
        import os
        assert os.path.exists(path)          # race auto-dumped
        tuning.clear_cache()                 # "new process"
        got2 = tuning.tune("pruning", n_samples=64, m=9, dim=4)
        assert races == [1]                  # shared pass, no second race
        assert got2 == pinned


class TestCacheConcurrency:
    """dump_cache(merge=True) is read-merge-write against a shared
    file; the O_EXCL lockfile + in-process _CACHE lock must make
    racing dumps lose no entries."""

    def test_racing_merged_dumps_lose_nothing(self, tmp_path):
        import json
        import os
        import threading
        path = str(tmp_path / "tune.json")
        tuning.clear_cache()
        errors = []

        def dump(worker):
            try:
                for i in range(6):
                    cfg = tuning.KernelConfig(shortlist=4 + worker,
                                              rescan_every=3)
                    key = tuning.shape_key(
                        "pruning", {"n_samples": 64 << worker,
                                    "m": 8 + i, "dim": 4})
                    with tuning._CACHE_LOCK:
                        tuning._CACHE[key] = cfg
                    tuning.dump_cache(path, merge=True)
            except Exception as e:       # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=dump, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        with open(path) as f:
            payload = json.load(f)        # never torn: valid JSON
        assert len(payload["entries"]) == 4 * 6
        assert not os.path.exists(path + ".lock")   # lock released
        tuning.clear_cache()

    def test_file_lock_breaks_orphans(self, tmp_path):
        """A crashed process's leftover lockfile must not wedge every
        future dump: after the bounded retry budget the lock is broken
        and the dump proceeds."""
        import os
        path = str(tmp_path / "tune.json")
        with open(path + ".lock", "w") as f:
            f.write("999999")            # orphan from a dead process
        tuning.clear_cache()
        key = tuning.shape_key("pruning",
                               {"n_samples": 64, "m": 8, "dim": 4})
        tuning._CACHE[key] = tuning.KernelConfig(shortlist=4,
                                                 rescan_every=3)
        n = tuning.dump_cache(path, merge=True)
        assert n == 1
        assert not os.path.exists(path + ".lock")
        tuning.clear_cache()
