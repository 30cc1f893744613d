"""Backend dispatch for the two serving/pruning hot paths (DESIGN §Backends).

This module is the single seam through which the algorithmic layer
(`repro.core.voronoi`, `repro.serve.retrieval`) reaches the Pallas
kernels (`repro.kernels.maxsim_topk`, `repro.kernels.colbert_maxsim`).
Every later scaling PR (sharded serving, multi-host pruning) plugs into
this seam rather than into the call sites.

Path matrix
-----------

======================  ==========================  =======================
path                    what it does                where it runs
======================  ==========================  =======================
``reference``           pure-jnp oracle; caches     off-TPU (the platform
                        the full (N, m) score       default there), parity
                        matrix (pruning) or the     tests, and pruning at
                        4-D (n_q, n_docs, l, m)     ``step_size > 1`` on
                        einsum tensor (serving)     every platform
``fused``               Pallas serving kernels;     serving on TPU (the
(serving only)          score tiles live in VMEM,   platform default)
                        the 4-D tensor never
                        reaches HBM
``shortlist_topk``      exact top-K shortlist       pruning on TPU (the
(pruning only)          scan, O(N*K) a step, whose  platform default): no
                        periodic rescan runs the    (N, m) matrix, no TopK
                        ``maxsim_topk`` Pallas      custom-call, partitions
                        kernel                      over samples and docs
======================  ==========================  =======================

``resolve_backend(None)`` picks, on TPU, the kernel path the caller
allows (``shortlist_topk`` for pruning, ``fused`` for serving) and
``reference`` where it allows none (pruning at ``step_size > 1``);
off-TPU it picks ``reference``.  The ``REPRO_BACKEND`` environment
variable overrides (useful to force a kernel path through the Pallas
interpreter off-TPU for parity debugging).

``tuned(kind, **shape)`` is the autotuner seam: call sites that used to
hardcode block sizes / shortlist schedules ask it for a
``repro.core.tuning.KernelConfig`` resolved from (shape, platform, VMEM
budget) — static heuristics by default, a cached one-shot measured race
with ``REPRO_AUTOTUNE=measure``.  Explicit arguments at the call sites
always win; the autotuner only fills ``None``s.

``default_interpret(None)`` is the companion policy for the raw kernel
entry points: Pallas ``interpret`` mode everywhere except on real TPU
backends, so direct kernel callers get compiled Mosaic kernels on TPU
and the (bit-identical) interpreter elsewhere — previously the raw
wrappers hardcoded ``interpret=True`` and silently ran the interpreter
even on TPU.
"""

from __future__ import annotations

import os

import jax

__all__ = [
    "BACKENDS",
    "REFERENCE",
    "FUSED",
    "PRUNING",
    "SERVING",
    "SHORTLIST_TOPK",
    "default_interpret",
    "on_tpu",
    "resolve_backend",
    "tuned",
    "tuned_routing_blocks",
    "tuned_serving_blocks",
    "tuned_streaming_blocks",
]

REFERENCE = "reference"
FUSED = "fused"
SHORTLIST_TOPK = "shortlist_topk"
BACKENDS = (REFERENCE, FUSED, SHORTLIST_TOPK)
# Per-path allow sets: each hot path has its oracle and one kernel path.
PRUNING = (REFERENCE, SHORTLIST_TOPK)
SERVING = (REFERENCE, FUSED)

_ENV_VAR = "REPRO_BACKEND"


def on_tpu() -> bool:
    """True when the default jax backend is a real TPU."""
    return jax.default_backend() == "tpu"


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve a kernel entry point's ``interpret`` argument.

    ``None`` (the default everywhere) means "compiled Mosaic kernel on
    TPU, Pallas interpreter elsewhere".  An explicit bool wins.
    """
    if interpret is None:
        return not on_tpu()
    return interpret


def _platform_default(allow: tuple[str, ...]) -> str:
    """TPU takes the kernel path the caller allows: ``shortlist_topk``
    (pruning), else ``fused`` (serving), else ``reference``.  Off-TPU
    the materializing reference path wins (Pallas runs through the
    interpreter there)."""
    if on_tpu():
        for path in (SHORTLIST_TOPK, FUSED):
            if path in allow:
                return path
    return REFERENCE


def resolve_backend(backend: str | None = None,
                    *, allow: tuple[str, ...] = BACKENDS) -> str:
    """Resolve a user-facing ``backend=`` argument to a concrete path.

    Precedence: explicit argument > ``REPRO_BACKEND`` env var > platform
    default (:func:`_platform_default`).  ``allow`` restricts the valid
    set for entry points that support fewer paths (``PRUNING``,
    ``SERVING``).
    An explicit argument outside ``allow`` raises; an env-var value that
    is a *valid* backend but outside this path's ``allow`` falls back to
    the platform default (a global override must not crash paths it
    cannot apply to), while an env-var value that is no backend at all
    raises everywhere (typo safety).

    Call this OUTSIDE jit: it reads the environment, and a jitted
    caller would pin the first-seen value into its trace cache.
    """
    source = "backend argument"
    if backend is None:
        env = os.environ.get(_ENV_VAR)
        if env:
            if env not in BACKENDS:     # typo'd env var: fail loudly
                raise ValueError(
                    f"backend={env!r} (from {_ENV_VAR} env var) is not a "
                    f"known backend; choose one of {list(BACKENDS)}")
            if env not in allow:
                # valid backend that doesn't exist for this path (e.g.
                # fused on pruning): fall back to platform default
                # rather than crash paths the override can't apply to.
                env = None
        backend = env or _platform_default(allow)
    if backend not in allow:
        raise ValueError(
            f"backend={backend!r} (from {source}) not supported here; "
            f"choose one of {list(allow)}")
    return backend


def tuned(kind: str, **shape):
    """Autotuner seam: a ``repro.core.tuning.KernelConfig`` for
    (kind, shape) on the current platform.  Lazy import keeps the
    dispatch module dependency-free for the kernel layer below it.
    """
    from repro.core import tuning
    return tuning.tune(kind, **shape)


def tuned_serving_blocks(n_q: int, n_docs: int, m: int, l: int, dim: int,
                         block_docs: int | None = None,
                         block_q: int | None = None, *,
                         codec: str | None = None) -> tuple[int, int]:
    """Resolve the serving sweep's ``(block_docs, block_q)`` chunking
    knobs for one doc array of shape (n_docs, m, dim).  Explicit values
    win; ``None``s come from the autotuner.

    ``m`` here is the *token capacity of the array being scored*, not
    necessarily the corpus max length: the packed index scores one
    capacity bucket at a time, so each bucket shape (n_docs_b, cap_b)
    keys its own tuning entry — narrow buckets legitimately get bigger
    doc blocks than the full-width dense index would.  ``codec``
    (``"int8"``, ``"residual4"``, ...) joins the key when set —
    in-kernel decode shifts the bandwidth/compute balance, so
    compressed buckets tune independently of fp32 ones at the same
    shape; fp32 (``None``) keys stay exactly as before.
    """
    if block_docs is None or block_q is None:
        shape = dict(n_q=n_q, n_docs=n_docs, m=m, l=l, dim=dim)
        if codec is not None:   # fp32 keys stay unchanged
            shape["codec"] = codec
        cfg = tuned("serving", **shape)
        block_docs = cfg.block_docs if block_docs is None else block_docs
        block_q = cfg.block_q if block_q is None else block_q
    return block_docs, block_q


def tuned_routing_blocks(n_q: int, n_buckets: int, n_centroids: int,
                         l: int, dim: int, *,
                         n_probe: int | None = None,
                         threshold: float | None = None,
                         block_docs: int | None = None,
                         block_q: int | None = None) -> tuple[int, int]:
    """Resolve the candidate router's ``(block_docs, block_q)`` for the
    centroid-table MaxSim pass (serve/routing.py).

    The centroid table is scored as ONE extra bucket shape — each
    capacity bucket plays the role of a document with ``n_centroids``
    tokens — so it keys the same ``serving`` tuning table as any
    bucket, with the table dimensions in the bucket slots.  The routed
    dispatch knobs (``n_probe``, score ``threshold``) join the key
    only when set: they don't change this pass's shape, but a measured
    race may legitimately prefer different chunking when the router is
    followed by a narrow vs. wide candidate sweep, and default-route
    keys must stay unchanged (the optional-key discipline of
    ``tuned_streaming_blocks``).  Explicit values win; ``None``s come
    from the autotuner.  Call OUTSIDE jit.
    """
    if block_docs is None or block_q is None:
        shape = dict(n_q=n_q, n_docs=n_buckets, m=n_centroids, l=l,
                     dim=dim)
        if n_probe is not None:
            shape["n_probe"] = n_probe
        if threshold is not None:
            shape["threshold"] = threshold
        cfg = tuned("serving", **shape)
        block_docs = cfg.block_docs if block_docs is None else block_docs
        block_q = cfg.block_q if block_q is None else block_q
    return block_docs, block_q


def tuned_streaming_blocks(n_q: int, n_docs: int, m: int, l: int, dim: int,
                           k: int, *, n_shards: int = 1, n_groups: int = 1,
                           replicas: int = 1,
                           block_docs: int | None = None,
                           block_q: int | None = None,
                           chunk_docs: int | None = None,
                           codec: str | None = None
                           ) -> tuple[int, int, int]:
    """Resolve the streaming top-k sweep's ``(block_docs, block_q,
    chunk_docs)`` for one doc array (bucket) of shape (n_docs, m, dim).

    The tuning key extends the serving key with the merge fan-in ``k``
    and the candidate-axis shard count ``n_shards`` — under sharded
    serving each shard scores only ``ceil(n_docs / n_shards)`` docs of
    the bucket, and the knobs (doc block, per-merge-step chunk) are
    sized for that SHARD-LOCAL slice, not the bucket's global doc
    count.  Under multi-host placement (``n_groups > 1``) the host
    group count joins the key too: a bucket pinned to a group spans
    only that group's candidates row, and its measured optimum need
    not match the flat layout's at the same shard count.  Replicated
    placements (``replicas > 1``) likewise key separately — a group
    serving replica copies scores more buckets per query than the
    unreplicated layout at the same group count, shifting the measured
    optimum.  ``codec`` keys compressed buckets separately (see
    :func:`tuned_serving_blocks`).  Explicit values win; ``None``s come
    from the autotuner.  Call OUTSIDE jit (the server's ``_warm_tuner``
    pre-resolves every key its closures will ask for).
    """
    if block_docs is None or block_q is None or chunk_docs is None:
        shape = dict(n_q=n_q, n_docs=n_docs, m=m, l=l, dim=dim,
                     k=k, n_shards=n_shards)
        if n_groups > 1:    # flat-layout keys stay unchanged
            shape["n_groups"] = n_groups
        if replicas > 1:    # unreplicated grid keys stay unchanged
            shape["replicas"] = replicas
        if codec is not None:   # fp32 keys stay unchanged
            shape["codec"] = codec
        cfg = tuned("serving", **shape)
        block_docs = cfg.block_docs if block_docs is None else block_docs
        block_q = cfg.block_q if block_q is None else block_q
        chunk_docs = cfg.chunk_docs if chunk_docs is None else chunk_docs
    return block_docs, block_q, chunk_docs
