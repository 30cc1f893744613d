"""Recsys model zoo: DLRM, DCN-v2, Wide&Deep, BERT4Rec.

JAX has no nn.EmbeddingBag and no CSR sparse — per the assignment, the
EmbeddingBag IS part of this system: `embedding_bag` implements
multi-hot lookup + segment-sum reduction with `jnp.take` +
`jax.ops.segment_sum`, and `repro.kernels.embedding_bag` provides the
fused Pallas TPU version.  Tables are row-sharded over the `model` mesh
axis ("table_rows" logical axis); the `retrieval_cand` shape scores one
query against 10^6 candidates as a single sharded matmul (top-k merged
across shards), not a loop.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.common import dense_init, embed_init, layer_norm
from repro.sharding import constrain


def embedding_bag(table: jax.Array, ids: jax.Array, bag_ids: jax.Array,
                  n_bags: int, weights: jax.Array | None = None,
                  mode: str = "sum") -> jax.Array:
    """EmbeddingBag(sum/mean): rows = table[ids], reduced per bag.

    table: (V, D); ids/bag_ids: (nnz,); -> (n_bags, D).
    """
    rows = jnp.take(table, ids, axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    out = jax.ops.segment_sum(rows, bag_ids, num_segments=n_bags)
    if mode == "mean":
        cnt = jax.ops.segment_sum(jnp.ones_like(ids, table.dtype), bag_ids,
                                  num_segments=n_bags)
        out = out / jnp.maximum(cnt[:, None], 1.0)
    return out


def alltoall_lookup(tables: jax.Array, ids: jax.Array, *,
                    capacity_factor: float = 2.0) -> jax.Array:
    """Production-DLRM embedding exchange (§Perf `a2a_lookup` variant).

    tables: (F, V, D) with rows sharded over the `model` axis; ids:
    (B, F) with batch sharded over all data-parallel axes.  The baseline
    gather dense-ifies table gradients into a (F, V_shard, D) all-reduce
    (~0.9 GB/chip/step at B=65536).  Here each chip instead:

      1. buckets its (B_local*F) row requests by owner shard (sort),
      2. exchanges fixed-capacity request buckets via all-to-all,
      3. answers with local row lookups, all-to-alls the rows back,
      4. un-sorts into (B_local, F, D).

    Gradients retrace the same route (all-to-all transposes to the
    reverse all-to-all; local scatter-add into the owned shard), so the
    collective volume is ACTIVATION-sized (~MBs) in both directions and
    no table-sized reduction ever exists.  Requests beyond an owner's
    bucket capacity (ceil(cf * B_local * F / n_shards)) are dropped to
    zero vectors — the standard capacity contract; cf=2 makes overflow
    vanishingly rare for hash-distributed ids (tested).

    Falls back to a plain gather when no mesh is active (CPU tests).
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.specs import current_rules

    rules = current_rules() or {}
    mesh = rules.get("__mesh__")
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                        in_axes=(0, 1), out_axes=1)(tables, ids)
    shard_axes = tuple(rules.get("__lookup_axes__", ("model",)))
    n_shards = 1
    for a in shard_axes:
        n_shards *= mesh.shape[a]
    dp_axes = tuple(a for a in mesh.axis_names if a not in shard_axes)
    F, V, D = tables.shape
    B = ids.shape[0]
    b_local = B // (mesh.devices.size)  # batch sharded over ALL axes
    vsh = V // n_shards
    n_req = b_local * F
    import math
    cap = max(1, math.ceil(capacity_factor * n_req / n_shards))

    def body(tshard, ids_local):
        # tshard (F, vsh, D); ids_local (b_local, F)
        flat = ids_local.reshape(-1)                       # (n_req,)
        owner = flat // vsh
        order = jnp.argsort(owner, stable=True)
        so, sid = owner[order], flat[order]
        counts = jnp.zeros((n_shards,), jnp.int32).at[so].add(1)
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(counts)[:-1]])
        slot = jnp.arange(n_req, dtype=jnp.int32) - offs[so]
        keep = slot < cap
        # request buckets (n_shards, cap): local row index at the owner
        req = jnp.full((n_shards, cap), 0, jnp.int32)
        req = req.at[jnp.where(keep, so, 0),
                     jnp.where(keep, slot, 0)].set(
            jnp.where(keep, sid % vsh, 0))
        # feature id travels with the request (rows live in table[f]);
        # flat index i corresponds to (batch i//F, feature i%F)
        f_of = (order % F).astype(jnp.int32)
        fbuf = jnp.zeros((n_shards, cap), jnp.int32)
        fbuf = fbuf.at[jnp.where(keep, so, 0),
                       jnp.where(keep, slot, 0)].set(
            jnp.where(keep, f_of, 0))
        # exchange requests: recv[j] = bucket sent by peer j
        ax = tuple(shard_axes) if len(shard_axes) > 1 else shard_axes[0]
        req_x = jax.lax.all_to_all(req, ax, 0, 0, tiled=False)
        fbuf_x = jax.lax.all_to_all(fbuf, ax, 0, 0, tiled=False)
        # answer locally: rows (n_shards, cap, D)
        rows = tshard[fbuf_x, req_x]                        # gather
        # send answers back
        rows_back = jax.lax.all_to_all(rows, ax, 0, 0, tiled=False)
        # reassemble: my request at (bucket=so, slot) -> rows_back[so, slot]
        got = rows_back[jnp.where(keep, so, 0), jnp.where(keep, slot, 0)]
        got = jnp.where(keep[:, None], got, 0.0)            # dropped -> 0
        unsort = jnp.argsort(order, stable=True)
        emb = got[unsort].reshape(b_local, F, D)
        return emb

    dp = dp_axes + shard_axes
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(P(None, shard_axes, None), P(dp, None)),
                        out_specs=P(dp, None, None),
                        check_vma=False)(tables, ids)
    return out


def _table_lookup(tables: jax.Array, ids: jax.Array) -> jax.Array:
    """(F, V, D) x (B, F) -> (B, F, D); routes to the all-to-all exchange
    when the active sharding rules request it (§Perf a2a_lookup)."""
    from repro.sharding.specs import current_rules
    rules = current_rules() or {}
    if rules.get("__lookup__") == "a2a":
        return alltoall_lookup(tables, ids)
    tables = constrain(tables, "table_axis", "table_rows", None)
    return jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                    in_axes=(0, 1), out_axes=1)(tables, ids)


def _mlp_params(key, dims, dtype):
    ws, bs = [], []
    for i in range(len(dims) - 1):
        key, k = jax.random.split(key)
        ws.append(dense_init(k, dims[i], dims[i + 1], dtype))
        bs.append(jnp.zeros((dims[i + 1],), dtype))
    return {"ws": tuple(ws), "bs": tuple(bs)}


def _mlp_apply(p, x, final_act=False):
    h = x
    n = len(p["ws"])
    for i, (w, b) in enumerate(zip(p["ws"], p["bs"])):
        h = h @ w + b
        if i < n - 1 or final_act:
            h = jax.nn.relu(h)
    return h


# ---------------------------------------------------------------------------
# DLRM (RM-2) [arXiv:1906.00091]
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str = "dlrm-rm2"
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 64
    table_rows: int = 1_000_000
    bot_mlp: tuple = (13, 512, 256, 64)
    top_mlp_hidden: tuple = (512, 512, 256, 1)
    interaction: str = "dot"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def param_count(self) -> int:
        n = self.n_sparse * self.table_rows * self.embed_dim
        dims = self.bot_mlp
        for i in range(len(dims) - 1):
            n += dims[i] * dims[i + 1] + dims[i + 1]
        n_f = self.n_sparse + 1
        inter = n_f * (n_f - 1) // 2 + self.embed_dim
        dims = (inter,) + self.top_mlp_hidden
        for i in range(len(dims) - 1):
            n += dims[i] * dims[i + 1] + dims[i + 1]
        return n


def dlrm_init(key, cfg: DLRMConfig):
    kt, kb, ktop = jax.random.split(key, 3)
    tables = embed_init(kt, cfg.n_sparse * cfg.table_rows, cfg.embed_dim,
                        cfg.param_dtype)  # stacked tables, one big matrix
    n_f = cfg.n_sparse + 1
    inter_dim = n_f * (n_f - 1) // 2 + cfg.embed_dim
    return {
        "tables": tables.reshape(cfg.n_sparse, cfg.table_rows, cfg.embed_dim),
        "bot": _mlp_params(kb, cfg.bot_mlp, cfg.param_dtype),
        "top": _mlp_params(ktop, (inter_dim,) + cfg.top_mlp_hidden,
                           cfg.param_dtype),
    }


def dlrm_forward(params, cfg: DLRMConfig, dense: jax.Array,
                 sparse_ids: jax.Array) -> jax.Array:
    """dense: (B, n_dense) f32; sparse_ids: (B, n_sparse) one id per feature
    (multi-hot handled by embedding_bag at the data layer). -> (B,) logits.
    """
    B = dense.shape[0]
    x0 = _mlp_apply(params["bot"], dense.astype(cfg.compute_dtype),
                    final_act=True)                      # (B, D)
    emb = _table_lookup(params["tables"], sparse_ids)    # (B, F, D)
    emb = constrain(emb, "batch", None, None)
    feats = jnp.concatenate([x0[:, None, :], emb], axis=1)  # (B, F+1, D)
    if cfg.interaction == "dot":
        z = jnp.einsum("bid,bjd->bij", feats, feats)
        iu = jnp.triu_indices(feats.shape[1], k=1)
        z = z[:, iu[0], iu[1]]                               # (B, F(F+1)/2)
        z = jnp.concatenate([z, x0], axis=-1)
    else:
        z = feats.reshape(B, -1)
    return _mlp_apply(params["top"], z)[:, 0]


# ---------------------------------------------------------------------------
# DCN-v2 [arXiv:2008.13535]
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DCNConfig:
    name: str = "dcn-v2"
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 16
    table_rows: int = 1_000_000
    n_cross_layers: int = 3
    mlp: tuple = (1024, 1024, 512)
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    @property
    def x0_dim(self) -> int:
        return self.n_dense + self.n_sparse * self.embed_dim

    def param_count(self) -> int:
        n = self.n_sparse * self.table_rows * self.embed_dim
        d = self.x0_dim
        n += self.n_cross_layers * (d * d + d)
        dims = (d,) + self.mlp + (1,)
        for i in range(len(dims) - 1):
            n += dims[i] * dims[i + 1] + dims[i + 1]
        return n


def dcn_init(key, cfg: DCNConfig):
    kt, kc, km = jax.random.split(key, 3)
    tables = embed_init(kt, cfg.n_sparse * cfg.table_rows, cfg.embed_dim,
                        cfg.param_dtype)
    d = cfg.x0_dim
    cross = []
    for _ in range(cfg.n_cross_layers):
        kc, k = jax.random.split(kc)
        cross.append({"w": dense_init(k, d, d, cfg.param_dtype, scale=0.01),
                      "b": jnp.zeros((d,), cfg.param_dtype)})
    return {
        "tables": tables.reshape(cfg.n_sparse, cfg.table_rows, cfg.embed_dim),
        "cross": tuple(cross),
        "mlp": _mlp_params(km, (d,) + cfg.mlp + (1,), cfg.param_dtype),
    }


def dcn_forward(params, cfg: DCNConfig, dense, sparse_ids):
    emb = _table_lookup(params["tables"], sparse_ids)
    B = dense.shape[0]
    x0 = jnp.concatenate([dense.astype(cfg.compute_dtype),
                          emb.reshape(B, -1)], axis=-1)
    x = x0
    for cl in params["cross"]:
        # x_{l+1} = x0 * (W x_l + b) + x_l   (DCN-v2 full-rank cross)
        x = x0 * (x @ cl["w"] + cl["b"]) + x
        x = constrain(x, "batch", None)
    logit = _mlp_apply(params["mlp"], x)[:, 0]
    return logit


# ---------------------------------------------------------------------------
# Wide & Deep [arXiv:1606.07792]
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WideDeepConfig:
    name: str = "wide-deep"
    n_sparse: int = 40
    embed_dim: int = 32
    table_rows: int = 1_000_000
    mlp: tuple = (1024, 512, 256)
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def param_count(self) -> int:
        n = self.n_sparse * self.table_rows * (self.embed_dim + 1)
        dims = (self.n_sparse * self.embed_dim,) + self.mlp + (1,)
        for i in range(len(dims) - 1):
            n += dims[i] * dims[i + 1] + dims[i + 1]
        return n


def widedeep_init(key, cfg: WideDeepConfig):
    kt, kw, km = jax.random.split(key, 3)
    tables = embed_init(kt, cfg.n_sparse * cfg.table_rows, cfg.embed_dim,
                        cfg.param_dtype)
    wide = embed_init(kw, cfg.n_sparse * cfg.table_rows, 1, cfg.param_dtype)
    return {
        "tables": tables.reshape(cfg.n_sparse, cfg.table_rows, cfg.embed_dim),
        "wide": wide.reshape(cfg.n_sparse, cfg.table_rows),
        "mlp": _mlp_params(km, (cfg.n_sparse * cfg.embed_dim,) + cfg.mlp + (1,),
                           cfg.param_dtype),
        "bias": jnp.zeros((), cfg.param_dtype),
    }


def widedeep_forward(params, cfg: WideDeepConfig, sparse_ids):
    emb = _table_lookup(params["tables"], sparse_ids)
    B = sparse_ids.shape[0]
    deep = _mlp_apply(params["mlp"], emb.reshape(B, -1))[:, 0]
    wide_t = constrain(params["wide"], "table_axis", "table_rows")
    wide = jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                    in_axes=(0, 1), out_axes=1)(wide_t, sparse_ids).sum(-1)
    return deep + wide + params["bias"]


# ---------------------------------------------------------------------------
# BERT4Rec [arXiv:1904.06690] — bidirectional transformer over item seqs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Bert4RecConfig:
    name: str = "bert4rec"
    n_items: int = 1_000_000
    embed_dim: int = 64
    n_blocks: int = 2
    n_heads: int = 2
    seq_len: int = 200
    d_ff: int = 256
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def lm_config(self) -> tfm.LMConfig:
        return tfm.LMConfig(
            name="bert4rec-core", n_layers=self.n_blocks,
            d_model=self.embed_dim, n_heads=self.n_heads,
            n_kv_heads=self.n_heads, d_ff=self.d_ff,
            vocab=self.n_items + 2,      # +mask +pad
            causal=False, tie_embeddings=True, rope_theta=1e4,
            param_dtype=self.param_dtype, compute_dtype=self.compute_dtype,
            remat=False)

    def param_count(self) -> int:
        return self.lm_config().param_count()


def bert4rec_init(key, cfg: Bert4RecConfig):
    return tfm.init_params(key, cfg.lm_config())


def bert4rec_forward(params, cfg: Bert4RecConfig, item_ids, attn_mask=None):
    """Masked-item logits over the catalog: (B, S, n_items+2)."""
    logits, _ = tfm.forward(params, item_ids, cfg.lm_config(),
                            attn_mask=attn_mask)
    return logits


def bert4rec_user_vectors(params, cfg: Bert4RecConfig, item_ids,
                          attn_mask=None):
    """Sequence-token embeddings (late-interaction view) + pooled user vec."""
    h = tfm.hidden_states(params, item_ids, cfg.lm_config(),
                          attn_mask=attn_mask)
    if attn_mask is None:
        pooled = h.mean(axis=1)
    else:
        w = attn_mask[..., None].astype(h.dtype)
        pooled = (h * w).sum(1) / jnp.maximum(w.sum(1), 1.0)
    return h, pooled


def score_candidates(user_vec: jax.Array, item_table: jax.Array) -> jax.Array:
    """retrieval_cand: (B, D) x (n_cand, D) -> (B, n_cand) in one sharded
    matmul; candidates shard over `model`, top-k merge is GSPMD's problem."""
    item_table = constrain(item_table, "candidates", None)
    scores = user_vec @ item_table.T
    return constrain(scores, "batch", "candidates")


def bert4rec_sampled_logits(params, cfg: Bert4RecConfig, item_ids, mask_idx,
                            labels, negatives):
    """Sampled-softmax training head (catalog = 1M items; full-vocab
    logits are not a real training path — DESIGN.md §7).

    item_ids: (B, S); mask_idx: (B, M) masked positions; labels: (B, M)
    gold item ids; negatives: (N,) shared sampled ids.
    Returns (pos_logit (B, M), neg_logits (B, M, N)).
    """
    h = tfm.hidden_states(params, item_ids, cfg.lm_config())   # (B, S, D)
    hm = jnp.take_along_axis(h, mask_idx[..., None], axis=1)   # (B, M, D)
    table = params["embed"].astype(h.dtype)                    # (V, D)
    pos_emb = table[labels]                                    # (B, M, D)
    neg_emb = table[negatives]                                 # (N, D)
    pos_logit = jnp.sum(hm * pos_emb, axis=-1)                 # (B, M)
    neg_logits = jnp.einsum("bmd,nd->bmn", hm, neg_emb)        # (B, M, N)
    return pos_logit, neg_logits


def sampled_softmax_loss(pos_logit, neg_logits):
    all_logits = jnp.concatenate(
        [pos_logit[..., None], neg_logits], axis=-1).astype(jnp.float32)
    return jnp.mean(jax.nn.logsumexp(all_logits, -1) - pos_logit)


def user_tower(params, cfg, dense, sparse_ids) -> jax.Array:
    """Two-tower retrieval head reusing CTR tables: user vector = mean of
    sparse feature embeddings (+ bottom-MLP output when the model has a
    dense tower).  Used by the retrieval_cand shape for DLRM/DCN/W&D."""
    tables = constrain(params["tables"], "table_axis", "table_rows", None)
    emb = jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                   in_axes=(0, 1), out_axes=1)(tables, sparse_ids)
    u = emb.mean(axis=1)                                       # (B, D)
    if dense is not None and "bot" in params:
        u = u + _mlp_apply(params["bot"], dense.astype(u.dtype),
                           final_act=True)
    return u


def retrieve_topk(params, cfg, dense, sparse_ids, *, k: int = 100):
    """retrieval_cand cell: user tower vs item table (= table 0's rows)."""
    u = user_tower(params, cfg, dense, sparse_ids)
    items = params["tables"][0]                                # (V, D)
    scores = score_candidates(u, items)
    return jax.lax.top_k(scores, k)
