"""Compile-only checks: the main path's Pallas kernels at real widths
compile for a TPU v5e (Mosaic), with no chip attached.

Each case lowers a kernel with ``interpret=False`` against shapes placed
on one device of a described ``v5e:2x2`` topology, compiles it with the
installed TPU compiler, and asserts the program holds the compiled
kernel (``tpu_custom_call``); the §4.2 merge, which has no kernel,
compiles as one program.  Widths are the ColBERT serving widths
(dim 128, query length 32) over bucket caps m in {32, 180, 256}, and
the pruning kernel's bucket widths {32, 128, 180, 256, 300} at 1,024
and at the build cells' 10,000 samples; block sizes are the ones
``core.tuning`` picks on TPU.  The Pallas
interpreter cannot catch what these catch: lane/sublane misalignment,
unsupported vector layouts, scoped-VMEM overflow.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tuning import heuristic_config
from repro.kernels.colbert_maxsim import colbert_maxsim as cm
from repro.kernels.maxsim_topk.maxsim_topk import maxsim_topk

DIM, L, N_Q, N_DOCS, N_SAMPLES = 128, 32, 16, 64, 1024
CAPS = (32, 180, 256)
PRUNING_CAPS = (32, 128, 180, 256, 300)
CENTROIDS = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_compiles(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


def _serving_block(m: int) -> int:
    return heuristic_config("serving", platform="tpu", n_q=N_Q,
                            n_docs=N_DOCS, m=m, l=L, dim=DIM).block_docs


@pytest.mark.parametrize("m", CAPS)
def test_colbert_maxsim_multi(one_chip, m):
    bd = _serving_block(m)
    _assert_compiles(
        lambda q, d, mk: cm.colbert_maxsim_multi(q, d, mk, block_d=bd,
                                                 interpret=False),
        _spec(one_chip, (N_Q, L, DIM)), _spec(one_chip, (N_DOCS, m, DIM)),
        _spec(one_chip, (N_DOCS, m), jnp.bool_))


@pytest.mark.parametrize("bits", (2, 4))
def test_colbert_maxsim_residual_multi(one_chip, bits):
    pb = DIM * bits // 8
    for m in CAPS:
        bd = _serving_block(m)
        _assert_compiles(
            lambda q, c, r, s, cb, mk: cm.colbert_maxsim_residual_multi(
                q, c, r, s, cb, mk, bits=bits, block_d=bd, interpret=False),
            _spec(one_chip, (N_Q, L, DIM)),
            _spec(one_chip, (N_DOCS, m), jnp.int8),
            _spec(one_chip, (N_DOCS, m, pb), jnp.uint8),
            _spec(one_chip, (N_DOCS, m, 1)),
            _spec(one_chip, (CENTROIDS, DIM)),
            _spec(one_chip, (N_DOCS, m), jnp.bool_))


def test_colbert_maxsim_single_query(one_chip):
    for m in CAPS:
        _assert_compiles(
            lambda q, d, mk: cm.colbert_maxsim(q, d, mk, interpret=False),
            _spec(one_chip, (L, DIM)), _spec(one_chip, (N_DOCS, m, DIM)),
            _spec(one_chip, (N_DOCS, m), jnp.bool_))


def test_colbert_maxsim_residual_rerank(one_chip):
    for bits in (2, 4):
        pb = DIM * bits // 8
        for m in CAPS:
            _assert_compiles(
                lambda q, c, r, s, cb, mk: cm.colbert_maxsim_residual_rerank(
                    q, c, r, s, cb, mk, bits=bits, interpret=False),
                _spec(one_chip, (L, DIM)),
                _spec(one_chip, (N_DOCS, m), jnp.int8),
                _spec(one_chip, (N_DOCS, m, pb), jnp.uint8),
                _spec(one_chip, (N_DOCS, m, 1)),
                _spec(one_chip, (N_DOCS, CENTROIDS, DIM)),
                _spec(one_chip, (N_DOCS, m), jnp.bool_))


def _assert_pruning_kernel_compiles(one_chip, n_samples, m):
    cfg = heuristic_config("pruning", platform="tpu", n_samples=n_samples,
                           m=m, dim=DIM)
    _assert_compiles(
        lambda s, t, a: maxsim_topk(s, t, a, k=cfg.shortlist,
                                    block_s=cfg.block_s, block_t=cfg.block_t,
                                    interpret=False),
        _spec(one_chip, (n_samples, DIM)), _spec(one_chip, (m, DIM)),
        _spec(one_chip, (m,), jnp.bool_))


@pytest.mark.parametrize("m", (*CAPS, 128))
def test_pruning_kernels(one_chip, m):
    _assert_pruning_kernel_compiles(one_chip, N_SAMPLES, m)


@pytest.mark.parametrize("m", PRUNING_CAPS)
def test_pruning_kernels_at_10k_samples(one_chip, m):
    """The build cells' 10,000 samples: the last sample block overhangs N."""
    _assert_pruning_kernel_compiles(one_chip, 10_000, m)


def test_pruning_kernel_at_document_length_300(one_chip):
    """GTE-ModernColBERT's 300-token documents prune in a bucket 300 wide,
    which is not a multiple of the kernel's token block."""
    _assert_pruning_kernel_compiles(one_chip, N_SAMPLES, 300)


@pytest.mark.parametrize("n_docs,m", [(64, 180), (64, 300), (8192, 180)])
def test_global_merge_compiles(one_chip, n_docs, m):
    """The §4.2 merge compiles for the chip as one program at a build
    slab of each document length and at a serving index's corpus."""
    from repro.core import voronoi
    compiled = voronoi._global_keep_masks_local.lower(
        _spec(one_chip, (n_docs, m), jnp.int32),
        _spec(one_chip, (n_docs, m)),
        _spec(one_chip, (n_docs, m), jnp.bool_),
        keep_fraction=0.5).compile()
    assert "sort(" in compiled.as_text()


def test_shortlist_scan_has_no_gather(one_chip, monkeypatch):
    """The vmapped ``shortlist_topk`` pruning scan, compiled for the chip
    at width 128 with the tuner's K and R, holds no element gather: the
    inner step picks each sample's best index by a select over K."""
    from repro.core import backend as backend_lib
    from repro.core import voronoi
    m, docs = 128, 3
    cfg = heuristic_config("pruning", platform="tpu", n_samples=N_SAMPLES,
                           m=m, dim=DIM)
    fn = jax.vmap(lambda e, k, s: voronoi._pruning_order_shortlist_impl(
        e, k, s, shortlist=cfg.shortlist, rescan_every=cfg.rescan_every,
        block_s=cfg.block_s, block_t=cfg.block_t), in_axes=(0, 0, None))
    # The kernel resolves compiled-vs-interpreted at trace time; trace
    # it as on the chip, and keep that trace out of later CPU tests.
    monkeypatch.setattr(backend_lib, "on_tpu", lambda: True)
    jax.clear_caches()
    try:
        text = jax.jit(fn).lower(
            _spec(one_chip, (docs, m, DIM)),
            _spec(one_chip, (docs, m), jnp.bool_),
            _spec(one_chip, (N_SAMPLES, DIM))).compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in text
    assert "gather(" not in text
