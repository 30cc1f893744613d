"""Packed serving index: pruning that actually shrinks the index.

`TokenIndex` (repro.serve.retrieval) keeps the full dense
(n_docs, m, dim) tensor plus a keep-mask — the right view for sweeping
pruning ratios, but its ``storage()`` savings are *reported*, never
realized: HBM and disk still hold every pruned token.  `PackedIndex` is
the serving artifact that realizes them:

* **Capacity-bucketed ragged storage** — kept tokens are compacted to
  the front of each row and documents are grouped by kept-token count
  into power-of-two capacity buckets (the same pow2
  ``pruning_pipeline.bucket_plan`` the pruning pipeline uses, so the
  number of distinct compiled shapes stays O(log m)).  Each bucket is a
  dense ``(n_docs_b, cap_b, dim)`` array that the fused
  ``colbert_maxsim`` kernels consume directly — no new kernel shapes,
  just narrower ones.  A per-bucket ``doc_ids`` remap scatters bucket
  scores back to corpus-global positions for the global top-k.
* **Optional compression** — ``"int8"``: per-block symmetric int8 with
  scales (``train/compress.quantize_int8``, the gradient-compression
  codec); ~4x fewer bytes again on top of pruning, dequantized on the
  fly inside the jitted scoring path.  ``"residual"``: ColBERTv2-style
  centroid + b-bit residual — each kept token is a 1-byte centroid id
  into the bucket's Lloyd's codebook (the SAME seeded clustering the
  routing sidecar runs, ``serve.routing.bucket_codebook``) plus a
  bit-packed b-bit quantized residual under a per-token scale
  (``train/compress.quantize_residual``); ~5-10x fewer bytes than fp32
  packed.  Residual buckets travel through serving as
  :class:`ResidualView` pytrees and are decoded **inside** the fused
  ``colbert_maxsim`` kernels tile-by-tile in VMEM — the fp32 bucket
  never materializes in HBM (asserted on the compiled HLO by the bench
  ``--check`` gate).
* **A sharding spec** — ``shard_axes`` names the logical axes of every
  bucket (docs are the "candidates" axis), resolved to mesh axes by the
  active ``sharding/specs`` rule set, so buckets place over the
  candidate-parallel axis of the production mesh like the dense index
  did.

``storage()["bytes_stored"]`` is the sum of *actual* array bytes — the
number the paper's "index size" claims are about (~keep_fraction x the
dense fp32 bytes; ~4x smaller again under int8), asserted in
tests/test_packed_index.py.

Exactness: compaction preserves the original token order within a doc
and drops only masked-out columns; MaxSim's per-query-token max over
document tokens is subset/order-invariant, so packed scores are
bit-identical to masked scores on the fp path (and the global top-k ids
identical) — the parity suite pins this per backend.

Persistence lives in ``repro.serve.index_io`` (versioned manifest +
the train/checkpoint atomic/async writer).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.pruning_pipeline import bucket_plan
from repro.sharding import spec_for
from repro.train import compress

__all__ = ["COMPRESSIONS", "PackedBucket", "PackedIndex", "ResidualView"]

COMPRESSIONS = ("none", "int8", "residual")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ResidualView:
    """The compressed form of one residual bucket as serving sees it:
    a pytree the streaming/sharded/rerank paths thread through jit in
    place of a dense ``(n, cap, dim)`` fp32 array.  The fused backend
    hands the four arrays straight to the decode-fused
    ``colbert_maxsim`` kernels; the reference backend decodes a slab at
    a time with :meth:`dense` (the oracle).  Slicing the doc axis
    (``view[a:b]``, the streaming chunk walk) slices the per-token
    scales along with codes/resq and shares only the codebook."""

    codes: jnp.ndarray     # (n, cap) int8 centroid ids
    resq: jnp.ndarray      # (n, cap, dim * bits // 8) uint8 packed
    scale: jnp.ndarray     # (n, cap, 1) f32 per-token residual scales
    codebook: jnp.ndarray  # (n_centroids, dim) f32
    bits: int
    dim: int

    def tree_flatten(self):
        return ((self.codes, self.resq, self.scale, self.codebook),
                (self.bits, self.dim))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def __getitem__(self, sl):
        return ResidualView(self.codes[sl], self.resq[sl], self.scale[sl],
                            self.codebook, self.bits, self.dim)

    @property
    def n_docs(self) -> int:
        return self.codes.shape[0]

    @property
    def cap(self) -> int:
        return self.codes.shape[1]

    def dense(self) -> jnp.ndarray:
        """Eager decode to (n, cap, dim) fp32 — the reference oracle
        and the host-side view builders (pooled/padded/routing)."""
        return compress.dequantize_residual(
            self.resq, self.scale, self.codes, self.codebook, self.bits)


@dataclasses.dataclass
class PackedBucket:
    """One capacity bucket of the packed index.

    ``masks`` is prefix-dense by construction (kept tokens compacted to
    the front); a document that lost every token to pruning has an
    all-false row.  Exactly one of ``embs`` (fp), ``q8``/``scales``
    (int8 blocks + per-block scales), or ``codes``/``resq``/``rscale``/
    ``codebook`` (centroid + b-bit residual) is populated, per the
    owning index's ``compression``.
    """

    cap: int
    doc_ids: jnp.ndarray              # (n_docs_b,) int32, global doc ids
    masks: jnp.ndarray                # (n_docs_b, cap) bool
    embs: jnp.ndarray | None = None   # (n_docs_b, cap, dim) float
    q8: jnp.ndarray | None = None     # (n_blocks, 256) int8
    scales: jnp.ndarray | None = None  # (n_blocks,) float32
    codes: jnp.ndarray | None = None   # (n_docs_b, cap) int8
    resq: jnp.ndarray | None = None    # (n_docs_b, cap, dim*b//8) uint8
    rscale: jnp.ndarray | None = None  # (n_docs_b, cap, 1) float32
    codebook: jnp.ndarray | None = None  # (n_centroids, dim) float32

    @property
    def n_docs(self) -> int:
        return self.masks.shape[0]

    def residual_bits(self, dim: int) -> int:
        """b recovered from the packed byte width: vpb = 8 // b values
        per byte, so resq's last axis is dim // vpb."""
        return self.resq.shape[-1] * 8 // dim

    def residual_view(self, dim: int) -> "ResidualView":
        return ResidualView(self.codes, self.resq, self.rscale,
                            self.codebook, self.residual_bits(dim), dim)

    def dense_embs(self, dim: int) -> jnp.ndarray:
        """The (n_docs_b, cap, dim) fp32 bucket the kernels score.
        int8 buckets dequantize here — inside jit this fuses into the
        scoring computation; nothing fp32-sized persists in HBM.
        Residual buckets decode eagerly here (host-side view builders
        and the reference oracle only); the fused serving path bypasses
        this entirely via :meth:`residual_view`."""
        if self.embs is not None:
            return self.embs
        if self.codes is not None:
            return self.residual_view(dim).dense()
        n = self.n_docs * self.cap * dim
        return compress.dequantize_int8(self.q8, self.scales,
                                        (self.n_docs, self.cap, dim), n)

    def nbytes(self) -> int:
        arrs = (self.doc_ids, self.masks, self.embs, self.q8, self.scales,
                self.codes, self.resq, self.rscale, self.codebook)
        return sum(int(a.nbytes) for a in arrs if a is not None)

    def shard_view(self, dim: int, n_shards: int, pad_id: int):
        """(embs, masks, doc_ids) with the doc axis padded up to a
        multiple of ``n_shards`` so the bucket places evenly over the
        candidates mesh axis (streaming sharded serving).

        Pad rows are all-masked docs carrying the sentinel ``pad_id``
        (callers use ``n_docs``, one past every real id) — the streaming
        merge forces their candidate scores to -inf, so a pad can never
        displace a real document, including real empty-after-prune docs
        whose finite sentinel scores sit above -inf.  The doc-id remap
        rides along with the shard: each shard maps its local top-k hits
        straight to corpus-global ids before the merge tree ever sees
        them.

        A bucket with **zero** documents (a host group that owns no
        bucket, or a group view of an index whose buckets all live
        elsewhere) still emits one explicit pad row per shard, carrying
        the reserved id ``-1``: an all-empty shard used to produce a
        0-row view whose candidate reduction emitted NaN-free but
        id-garbage rows — an all-masked pad scores the same finite
        sentinel as a real empty-after-prune document and, carrying a
        low id, would *beat* it on the tie-break.  The streaming merge
        audits for both sentinels (``id >= pad_id`` and ``id < 0``) and
        forces their candidates to -inf (tests/test_placement.py).
        """
        compressed = self.codes is not None
        e = self.residual_view(dim) if compressed else self.dense_embs(dim)
        mk, ids = self.masks, self.doc_ids
        n_shards = max(n_shards, 1)
        pad = (-self.n_docs) % n_shards if self.n_docs else n_shards
        if pad:
            if compressed:
                # Pad rows decode to garbage (codebook[0] - bias*scale)
                # but are all-masked, so the merge forces them to -inf
                # exactly like fp pad rows.
                e = ResidualView(
                    jnp.pad(e.codes, ((0, pad), (0, 0))),
                    jnp.pad(e.resq, ((0, pad), (0, 0), (0, 0))),
                    jnp.pad(e.scale, ((0, pad), (0, 0), (0, 0))),
                    e.codebook, e.bits, e.dim)
            else:
                e = jnp.pad(e, ((0, pad), (0, 0), (0, 0)))
            mk = jnp.pad(mk, ((0, pad), (0, 0)))
            ids = jnp.pad(ids, (0, pad),
                          constant_values=pad_id if self.n_docs else -1)
        return e, mk, ids

    def __repr__(self):  # keep test failure output readable
        return (f"PackedBucket(cap={self.cap}, n_docs={self.n_docs}, "
                f"compressed={self.embs is None})")


@dataclasses.dataclass
class PackedIndex:
    """Compacted token index: the artifact pruning produces and serving
    loads.  Build with :meth:`pack` (or ``TokenIndex.pack()``), persist
    with ``repro.serve.index_io``, serve through
    ``repro.serve.retrieval`` (``maxsim_scores``/``search``/
    ``RetrievalServer`` accept a `PackedIndex` wherever they accept a
    `TokenIndex`).
    """

    n_docs: int
    m: int                      # original padded doc length (provenance)
    dim: int
    tokens_total: int           # alive tokens before pruning
    compression: str
    buckets: list[PackedBucket]
    # Logical axes of each bucket's (docs, tokens, dim) arrays; the
    # active sharding/specs rule set resolves "candidates" to the mesh's
    # candidate-parallel axis (``model`` in the canonical rules).
    shard_axes: tuple = ("candidates", None, None)
    # Mutation epoch: 0 for a freshly packed index, bumped by each
    # committed compaction (serve.mutation.Compactor).  Joins the
    # serving closure cache keys so an epoch swap can never be answered
    # by a program compiled over the previous epoch's arrays.
    epoch: int = 0
    # b of the residual codec (0 for "none"/"int8"); persisted in the
    # manifest so a loaded index re-encodes mutations identically.
    residual_bits: int = 0
    _pooled: jnp.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _padded: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _padded_res: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @classmethod
    def pack(cls, d_embs, d_masks, keep=None, *, compression: str = "none",
             granularity: int | str = "pow2", min_width: int = 8,
             residual_bits: int = 4, n_centroids: int = 8,
             seed: int = 0) -> "PackedIndex":
        """Compact ``keep & d_masks`` tokens into capacity buckets.

        Host-side by design (like ``bucket_plan``): the layout is
        data-dependent.  ``keep=None`` packs the unpruned index.
        ``granularity`` is the bucket rounding of
        ``pruning_pipeline.bucket_plan`` ("pow2" or an int multiple);
        finer granularity trades more compiled shapes for less padding.
        ``residual_bits``/``n_centroids``/``seed`` shape the
        ``"residual"`` codec only: each bucket's kept tokens are
        Lloyd's-clustered (``serve.routing.bucket_codebook``, the same
        seeded split the routing sidecar uses) and every token stores
        its nearest-centroid id plus a bit-packed ``residual_bits``-bit
        residual under a per-token scale.  With :mod:`repro.obs` on the
        call is marked ``repro.pack``, and each bucket's codebook and
        residual encode ``repro.pack.residual``.
        """
        if compression not in COMPRESSIONS:
            raise ValueError(f"compression={compression!r}; "
                             f"one of {COMPRESSIONS}")
        with obs.span("repro.pack", compression=compression):
            embs = np.asarray(d_embs)
            masks = np.asarray(d_masks, bool)
            active = masks if keep is None else np.asarray(keep, bool) & masks
            n_docs, m = active.shape
            dim = embs.shape[-1]
            if compression == "residual":
                if residual_bits not in compress.RESIDUAL_BITS:
                    raise ValueError(f"residual_bits={residual_bits}; one of "
                                     f"{compress.RESIDUAL_BITS}")
                if dim % (8 // residual_bits):
                    raise ValueError(f"dim={dim} must be a multiple of "
                                     f"{8 // residual_bits} for "
                                     f"{residual_bits}-bit residuals")
                if not 1 <= n_centroids <= 127:
                    raise ValueError("n_centroids must fit int8 codes "
                                     f"(1..127), got {n_centroids}")
            buckets = []
            if n_docs:
                plan = bucket_plan(active.sum(1), m, granularity=granularity,
                                   min_width=min_width)
                for bi, b in enumerate(plan):
                    act = active[b.indices]
                    # stable argsort on ~mask: kept positions first,
                    # original token order preserved (MaxSim doesn't
                    # care, pooled sums do).
                    sel = np.argsort(~act, axis=1, kind="stable")[:, :b.width]
                    e = np.take_along_axis(embs[b.indices], sel[:, :, None],
                                           axis=1)
                    mk = np.take_along_axis(act, sel, axis=1)
                    e[~mk] = 0  # deterministic bytes in the padded tail
                    bucket = PackedBucket(cap=b.width,
                                          doc_ids=jnp.asarray(b.indices,
                                                              jnp.int32),
                                          masks=jnp.asarray(mk))
                    if compression == "int8":
                        bucket.q8, bucket.scales = compress.quantize_int8(
                            jnp.asarray(e, jnp.float32))
                    elif compression == "residual":
                        with obs.span("repro.pack.residual",
                                      cap=b.width, docs=len(b.indices)):
                            cls._encode_residual(
                                bucket, np.asarray(e, np.float32), mk,
                                residual_bits, n_centroids, seed, bi)
                    else:
                        bucket.embs = jnp.asarray(e)
                    buckets.append(bucket)
            return cls(n_docs=n_docs, m=m, dim=dim,
                       tokens_total=int(masks.sum()), compression=compression,
                       buckets=buckets,
                       residual_bits=(residual_bits
                                      if compression == "residual" else 0))

    @staticmethod
    def _encode_residual(bucket, e, mk, bits, n_centroids, seed, bi):
        """Codebook + codes + packed residuals for one bucket.  Masked
        slots store code 0 / residual 0 (deterministic bytes); decode
        garbage there is inert because every scorer applies ``masks``."""
        from repro.serve.routing import bucket_codebook  # circular-safe
        dim = e.shape[-1]
        cb, cbm = bucket_codebook(e.reshape(-1, dim), mk.reshape(-1),
                                  n_centroids, seed=seed, bucket_index=bi)
        d2 = ((e[:, :, None, :] - cb[None, None]) ** 2).sum(-1)
        d2 = np.where(cbm[None, None], d2, np.inf)
        codes = (d2.argmin(-1) if cbm.any()
                 else np.zeros(d2.shape[:2], np.int64))
        codes = np.where(mk, codes, 0).astype(np.int8)
        r = e - cb[codes.astype(np.int64)]
        r[~mk] = 0
        resq, rscale = compress.quantize_residual(jnp.asarray(r), bits)
        bucket.codes = jnp.asarray(codes)
        bucket.resq = resq
        bucket.rscale = jnp.asarray(rscale, jnp.float32)
        bucket.codebook = jnp.asarray(cb, jnp.float32)

    # -- introspection ---------------------------------------------------

    @property
    def tokens_kept(self) -> int:
        return int(sum(int(b.masks.sum()) for b in self.buckets))

    @property
    def cap_max(self) -> int:
        return max((b.cap for b in self.buckets), default=0)

    @property
    def n_centroids(self) -> int:
        """Residual codebook size (0 unless compression == "residual");
        the Compactor re-encodes new epochs with the same value."""
        return max((b.codebook.shape[0] for b in self.buckets
                    if b.codebook is not None), default=0)

    def codec_tag(self) -> str | None:
        """The autotuner's codec key: None for fp32 (default keys stay
        unchanged), "int8", or "residual{b}" — fp32/int8/residual
        buckets tune independently (decode changes the kernel's
        bandwidth/compute balance)."""
        if self.compression == "none":
            return None
        if self.compression == "residual":
            return f"residual{self.residual_bits}"
        return self.compression

    def spec(self):
        """PartitionSpec for one bucket under the active rule set."""
        return spec_for(*self.shard_axes)

    def storage(self) -> dict:
        """Measured footprint.  Unlike ``TokenIndex.storage()`` (which
        *reports* what a compacted index would cost), ``bytes_stored``
        here sums the bytes of the arrays this process actually holds."""
        kept = self.tokens_kept
        slots = sum(b.n_docs * b.cap for b in self.buckets)
        return {
            "tokens_total": self.tokens_total,
            "tokens_kept": kept,
            "remain_pct": 100.0 * kept / max(self.tokens_total, 1),
            "bytes_stored": sum(b.nbytes() for b in self.buckets),
            "bytes_fp32": kept * self.dim * 4,
            "bytes_fp32_unpruned": self.tokens_total * self.dim * 4,
            "bytes_dense_fp32": self.n_docs * self.m * self.dim * 4,
            "compression": self.compression,
            "n_buckets": len(self.buckets),
            "cap_max": self.cap_max,
            # pow2 rounding + empty-doc floors: stored slots per kept token
            "padding_overhead": slots / max(kept, 1),
            **({"residual_bits": self.residual_bits}
               if self.compression == "residual" else {}),
        }

    # -- serving views ---------------------------------------------------

    def pooled(self) -> jnp.ndarray:
        """(n_docs, dim) mean-pooled doc vectors for the cheap first
        stage, scattered to global doc order.  Cached when built outside
        a trace (the server's first stage then reuses one buffer across
        query batches); inside a jit trace the result is a tracer and
        must NOT be cached — it would leak into later traces.  The
        server warms these views eagerly before jitting."""
        if self._pooled is not None:
            return self._pooled
        out = jnp.zeros((self.n_docs, self.dim), jnp.float32)
        for b in self.buckets:
            e = b.dense_embs(self.dim)
            w = b.masks[..., None].astype(e.dtype)
            p = (e * w).sum(1) / jnp.maximum(w.sum(1), 1.0)
            out = out.at[b.doc_ids].set(p)
        if not isinstance(out, jax.core.Tracer):
            self._pooled = out
        return out

    def padded(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Gatherable view ((n_docs, cap_max, dim) embs, (n_docs,
        cap_max) masks) for the two-stage rerank, whose per-query
        candidate gather needs one uniform token axis.  cap_max-wide —
        still the *compacted* width, not the original m.  Lazily built
        and cached (same tracer rule as :meth:`pooled`); counted
        separately from ``bytes_stored`` (it is serving scratch, only
        materialized by two-stage search, and a deployment that only
        runs e2e scoring never pays it)."""
        if self._padded is not None:
            return self._padded
        e = jnp.zeros((self.n_docs, self.cap_max, self.dim), jnp.float32)
        mk = jnp.zeros((self.n_docs, self.cap_max), bool)
        for b in self.buckets:
            e = e.at[b.doc_ids, :b.cap].set(b.dense_embs(self.dim))
            mk = mk.at[b.doc_ids, :b.cap].set(b.masks)
        if not isinstance(e, jax.core.Tracer):
            self._padded = (e, mk)
        return e, mk

    def padded_residual(self) -> tuple:
        """Compressed gatherable view for the fused two-stage rerank:
        ``(codes (n_docs, cap_max) int8, resq (n_docs, cap_max, pb)
        uint8, bucket_of (n_docs,) int32, masks (n_docs, cap_max),
        codebooks (n_buckets, C, dim) f32, rscales (n_docs, cap_max, 1)
        f32)``.  Candidates gather compressed rows (their per-token
        scales ride the same doc axis; only the tiny codebook is looked
        up via ``bucket_of``) and the rerank kernel decodes per tile —
        the fp32 ``padded()`` scratch is never built on the fused path.
        Same tracer-caching rule as :meth:`padded`."""
        if self._padded_res is not None:
            return self._padded_res
        pb = self.dim * self.residual_bits // 8
        c_max = self.n_centroids
        codes = jnp.zeros((self.n_docs, self.cap_max), jnp.int8)
        resq = jnp.zeros((self.n_docs, self.cap_max, pb), jnp.uint8)
        bucket_of = jnp.zeros((self.n_docs,), jnp.int32)
        mk = jnp.zeros((self.n_docs, self.cap_max), bool)
        cbs = jnp.zeros((len(self.buckets), c_max, self.dim), jnp.float32)
        scales = jnp.zeros((self.n_docs, self.cap_max, 1), jnp.float32)
        for bi, b in enumerate(self.buckets):
            codes = codes.at[b.doc_ids, :b.cap].set(b.codes)
            resq = resq.at[b.doc_ids, :b.cap].set(b.resq)
            bucket_of = bucket_of.at[b.doc_ids].set(bi)
            mk = mk.at[b.doc_ids, :b.cap].set(b.masks)
            cbs = cbs.at[bi, :b.codebook.shape[0]].set(b.codebook)
            scales = scales.at[b.doc_ids, :b.cap].set(b.rscale)
        out = (codes, resq, bucket_of, mk, cbs, scales)
        if not isinstance(codes, jax.core.Tracer):
            self._padded_res = out
        return out
