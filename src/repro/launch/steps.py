"""Cell builders: (architecture x input-shape x mesh) -> lowerable step.

`build_cell` returns a `Cell` carrying the jit-able function, abstract
input ShapeDtypeStructs (no allocation — the ONLY way full-scale configs
are exercised), and NamedSharding pytrees for the production mesh.  The
dry-run driver lowers+compiles each cell; the trainer uses the same
builders with real arrays.

Sharding variants (`variant=`):
  baseline    — DESIGN.md §8 posture
  ep_moe      — experts over `model` (all-to-all MoE)     [LM hillclimb]
  row_tables  — row-sharded embedding tables              [recsys hillclimb]
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import sharding as shlib
from repro.configs import base as cfgbase
from repro.models import colbert as colbert_lib
from repro.models import gnn as gnn_lib
from repro.models import recsys as recsys_lib
from repro.models import transformer as tfm
from repro.sharding.specs import logical_to_spec
from repro.train import losses, optimizer, train_step

I32 = jnp.int32
F32 = jnp.float32


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_id: str
    kind: str
    fn: Callable
    args: tuple                  # abstract ShapeDtypeStructs
    in_shardings: tuple
    out_shardings: Any
    rules: dict
    model_flops_per_step: float  # 6*N*D (or family analogue)
    skip: str | None = None
    donate: tuple = ()


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _replicated_like(tree_shape):
    return jax.tree_util.tree_map(lambda x: P(), tree_shape)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# LM param/state specs
# ---------------------------------------------------------------------------

def _vocab_ax(cfg):
    """Shard the vocab axis only when it divides the model axis (16);
    granite's 49155 and bert4rec's 1000002 stay replicated."""
    return "model" if cfg.vocab % 16 == 0 else None


def lm_param_specs(cfg: tfm.LMConfig, *, ep_moe: bool = False):
    attn = {
        "wq": P(None, "data", "model"),
        "wk": P(None, "data", "model"),
        "wv": P(None, "data", "model"),
        "wo": P(None, "model", "data"),
        "bq": P(None, "model") if cfg.qkv_bias else None,
        "bk": P(None, "model") if cfg.qkv_bias else None,
        "bv": P(None, "model") if cfg.qkv_bias else None,
    }
    layer = {"ln1": P(None, None), "ln2": P(None, None), "attn": attn}
    if cfg.moe_experts:
        if ep_moe:
            layer["moe"] = {
                "router": P(None, "data", None),
                "w_gate": P(None, "model", "data", None),
                "w_up": P(None, "model", "data", None),
                "w_down": P(None, "model", None, "data"),
            }
        else:
            layer["moe"] = {
                "router": P(None, "data", None),
                "w_gate": P(None, None, "data", "model"),
                "w_up": P(None, None, "data", "model"),
                "w_down": P(None, None, "model", "data"),
            }
    else:
        layer["ffn"] = {
            "w_gate": P(None, "data", "model"),
            "w_up": P(None, "data", "model"),
            "w_down": P(None, "model", "data"),
        }
    specs = {"embed": P(_vocab_ax(cfg), "data"), "layers": layer,
             "ln_f": P(None)}
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("data", _vocab_ax(cfg))
    return specs


def lm_param_specs_fsdp(params_shape, multi_pod: bool):
    """Pure FSDP posture for TRAINING cells: every parameter sharded on a
    single dim across all devices (ZeRO-3-like).

    Rationale (EXPERIMENTS.md §Perf, iteration 0): the training batch
    shards over BOTH mesh axes, so 2-D weight sharding (FSDP x TP) forces
    GSPMD into "involuntary full rematerialization" reshards of the
    (B_local, S, D) activations every matmul — 154 GB/device of temps on
    mixtral.  1-D weight sharding turns every layer into a clean
    all-gather(weights) -> local matmul -> reduce-scatter(grads) FSDP
    schedule that XLA overlaps across scanned layers.
    """
    full = ("pod", "data", "model") if multi_pod else ("data", "model")
    n_full = 512 if multi_pod else 256
    combos = [(full, n_full), (("data", "model"), 256), (("model",), 16),
              (("data",), 16)]
    if multi_pod:
        combos.insert(1, (("data", "model"), 256))

    def spec(path, x):
        shape = x.shape
        lead = 1 if len(shape) >= 3 else 0   # never shard the scan L axis
        for axes, n in combos:
            for d in range(len(shape) - 1, lead - 1, -1):
                if shape[d] % n == 0 and shape[d] >= n:
                    parts = [None] * len(shape)
                    parts[d] = axes if len(axes) > 1 else axes[0]
                    return P(*parts)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def _state_specs(param_specs):
    return {
        "params": param_specs,
        "opt": optimizer.AdamWState(
            step=P(), m=param_specs, v=param_specs),
        "step": P(),
    }


def _opt_cfg():
    return optimizer.AdamWConfig(lr=3e-4, warmup_steps=100,
                                 total_steps=10_000)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_cell(entry, shape: cfgbase.ShapeSpec, mesh, multi_pod, variant):
    cfg: tfm.LMConfig = entry.config
    vset = set(variant.split("+"))
    if "attn_remat" in vset:
        cfg = dataclasses.replace(cfg, remat_attn_chunk=True)
    ep = variant == "ep_moe" and cfg.moe_experts > 0
    pspecs = lm_param_specs(cfg, ep_moe=ep)
    mf = 6.0 * cfg.active_param_count()
    B = shape.dims["global_batch"]
    S = shape.dims["seq_len"]

    if shape.kind == "train":
        rules = shlib.lm_train_rules(multi_pod)
        if ep:
            rules = shlib.lm_rules_ep_moe(rules)
        opt_cfg = _opt_cfg()
        init = lambda k: train_step.make_train_state(
            k, lambda kk: tfm.init_params(kk, cfg), opt_cfg)
        state_shape = jax.eval_shape(init, jax.random.PRNGKey(0))
        pfsdp = lm_param_specs_fsdp(state_shape["params"], multi_pod)
        sspec = _state_specs(pfsdp)
        batch_spec = {"tokens": logical_to_spec(("batch", "seq"), rules)}
        gshard = _ns(mesh, pfsdp) if "rs_grads" in vset else None
        step = train_step.lm_train_step(cfg, opt_cfg, grad_shardings=gshard)

        def fn(state, batch):
            with shlib.axis_rules(rules):
                return step(state, batch)

        args = (state_shape, {"tokens": _sds((B, S), I32)})
        in_sh = (_ns(mesh, sspec), _ns(mesh, batch_spec))
        out_sh = (_ns(mesh, sspec), None)
        return Cell(entry.arch_id, shape.shape_id, "train", fn, args, in_sh,
                    out_sh, rules, mf * B * S, donate=(0,))

    if shape.kind == "prefill":
        rules = shlib.lm_prefill_rules(multi_pod)
        if ep:
            rules = shlib.lm_rules_ep_moe(rules)
        params_shape = jax.eval_shape(
            lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0))

        def fn(params, tokens):
            with shlib.axis_rules(rules):
                x = tfm.hidden_states(params, tokens, cfg)
                head = params.get("lm_head")
                if head is None:
                    head = params["embed"].T
                return x[:, -1, :] @ head.astype(cfg.compute_dtype)

        args = (params_shape, _sds((B, S), I32))
        in_sh = (_ns(mesh, pspecs),
                 NamedSharding(mesh, logical_to_spec(("batch", "seq"), rules)))
        return Cell(entry.arch_id, shape.shape_id, "prefill", fn, args,
                    in_sh, None, rules, 2.0 * cfg.active_param_count() * B * S)

    # decode
    rules = shlib.lm_decode_rules(multi_pod, batch=B)
    if ep:
        rules = shlib.lm_rules_ep_moe(rules)
    window = cfg.window or cfg.attn_window_serving
    if shape.shape_id == "long_500k" and cfg.attn_window_serving:
        window = cfg.attn_window_serving
    params_shape = jax.eval_shape(
        lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0))
    cache_shape = jax.eval_shape(
        functools.partial(tfm.init_cache, cfg, B, S, window=window))
    cache_spec = {
        "k": logical_to_spec((None, "batch", "kv_heads", "kv_len", None),
                             rules),
        "v": logical_to_spec((None, "batch", "kv_heads", "kv_len", None),
                             rules),
    }
    serve = train_step.lm_serve_step(cfg, window=window)

    def fn(params, cache, tokens, pos):
        with shlib.axis_rules(rules):
            return serve(params, cache, tokens, pos)

    args = (params_shape, cache_shape, _sds((B, 1), I32), _sds((), I32))
    in_sh = (_ns(mesh, pspecs), _ns(mesh, cache_spec),
             NamedSharding(mesh, logical_to_spec(("batch", None), rules)),
             NamedSharding(mesh, P()))
    out_sh = (None, _ns(mesh, cache_spec))
    return Cell(entry.arch_id, shape.shape_id, "decode", fn, args, in_sh,
                out_sh, rules, 2.0 * cfg.active_param_count() * B,
                donate=(1,))


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

_GNN_SHAPE_META = {
    # shape_id: (d_feat, n_classes, task)
    "full_graph_sm": (1433, 7, "node"),
    "minibatch_lg": (602, 41, "node"),
    "ogb_products": (100, 47, "node"),
    "molecule": (16, 2, "graph"),
}


def _gnn_cell(entry, shape: cfgbase.ShapeSpec, mesh, multi_pod, variant):
    d_feat, n_classes, task = _GNN_SHAPE_META[shape.shape_id]
    cfg = dataclasses.replace(entry.config, d_feat=d_feat,
                              n_classes=n_classes)
    rules = shlib.gnn_rules(multi_pod)
    opt_cfg = _opt_cfg()
    init = lambda k: train_step.make_train_state(
        k, lambda kk: gnn_lib.init_params(kk, cfg), opt_cfg)
    state_shape = jax.eval_shape(init, jax.random.PRNGKey(0))
    sspec = jax.tree_util.tree_map(lambda x: P(), state_shape)

    dims = shape.dims
    # Edge lists pad to a multiple of 512 (shard boundary for both meshes);
    # padded edges carry edge_mask=False and point at node 0.
    if shape.shape_id == "molecule":
        n_nodes = dims["n_nodes"] * dims["batch"]
        e = dims["n_edges"] * dims["batch"]
        n_labels = dims["batch"]
        graph_ids = True
    elif shape.shape_id == "minibatch_lg":
        n_nodes, e = dims["max_nodes"], dims["max_edges"]
        n_labels = n_nodes
        graph_ids = False
    else:
        n_nodes, e = dims["n_nodes"], dims["n_edges"]
        n_labels = n_nodes
        graph_ids = False
    e_pad = -(-e // 512) * 512
    batch = {
        "x": _sds((n_nodes, d_feat), F32),
        "edge_index": _sds((2, e_pad), I32),
        "edge_mask": _sds((e_pad,), jnp.bool_),
        "labels": _sds((n_labels,), I32),
        "label_mask": _sds((n_labels,), F32),
    }
    espec = logical_to_spec(("edges",), rules)
    bspec = {
        "x": P(), "edge_index": logical_to_spec((None, "edges"), rules),
        "edge_mask": espec, "labels": P(), "label_mask": P(),
    }
    if graph_ids:
        batch["graph_ids"] = _sds((n_nodes,), I32)
        bspec["graph_ids"] = P()

    step = train_step.gin_train_step(cfg, opt_cfg, task=task)

    def fn(state, b):
        with shlib.axis_rules(rules):
            return step(state, b)

    n_edges_eff = batch["edge_index"].shape[1]
    # per-edge gather+add ~ 2*d_hidden flops x layers + node MLPs
    mf = (2.0 * n_edges_eff * cfg.d_hidden * cfg.n_layers
          + 2.0 * batch["x"].shape[0] * cfg.param_count())
    args = (state_shape, batch)
    in_sh = (_ns(mesh, sspec), _ns(mesh, bspec))
    return Cell(entry.arch_id, shape.shape_id, "train", fn, args, in_sh,
                (_ns(mesh, sspec), None), rules, mf)


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

def _recsys_param_specs(arch_id, params_shape):
    """Row-sharded embedding tables over `model` (table count 26/40 does
    not divide 16, and replication would not fit HBM with optimizer
    states).  Lookups over the sharded row axis produce the gather
    collectives the baseline roofline measures; the hillclimb replaces
    them with local-lookup + psum (EXPERIMENTS.md §Perf)."""
    def leaf_spec(path, x):
        name = jax.tree_util.keystr(path)
        if "tables" in name:
            return P(None, "model", None)
        if "wide" in name:
            return P(None, "model")
        return P(*([None] * len(x.shape)))
    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


def _recsys_param_specs_zero(arch_id, params_shape, multi_pod):
    # 1M rows % 256 != 0, so full-1D ZeRO is not an input-legal sharding;
    # 2-D (rows x embed-dim) spreads optimizer state over all 256/512
    # chips instead: rows over data(+pod), dim over model.
    row_ax = ("pod", "data") if multi_pod else ("data",)

    def leaf_spec(path, x):
        name = jax.tree_util.keystr(path)
        if "tables" in name:
            return P(None, row_ax, "model")
        if "wide" in name:
            return P(None, row_ax)
        return P(*([None] * len(x.shape)))
    return jax.tree_util.tree_map_with_path(leaf_spec, params_shape)


_CTR_FWD = {
    "dlrm-rm2": lambda p, cfg, b: recsys_lib.dlrm_forward(
        p, cfg, b["dense"], b["sparse_ids"]),
    "dcn-v2": lambda p, cfg, b: recsys_lib.dcn_forward(
        p, cfg, b["dense"], b["sparse_ids"]),
    "wide-deep": lambda p, cfg, b: recsys_lib.widedeep_forward(
        p, cfg, b["sparse_ids"]),
}

_CTR_INIT = {
    "dlrm-rm2": recsys_lib.dlrm_init,
    "dcn-v2": recsys_lib.dcn_init,
    "wide-deep": recsys_lib.widedeep_init,
}


def _ctr_batch_specs(arch_id, cfg, B, rules):
    has_dense = arch_id != "wide-deep"
    batch = {"sparse_ids": _sds((B, cfg.n_sparse), I32)}
    bspec = {"sparse_ids": logical_to_spec(("batch", None), rules)}
    if has_dense:
        batch["dense"] = _sds((B, cfg.n_dense), F32)
        bspec["dense"] = logical_to_spec(("batch", None), rules)
    return batch, bspec


def _recsys_cell(entry, shape: cfgbase.ShapeSpec, mesh, multi_pod, variant):
    if entry.arch_id == "bert4rec":
        return _bert4rec_cell(entry, shape, mesh, multi_pod, variant)
    cfg = entry.config
    rules = shlib.recsys_rules_rowsharded(multi_pod)
    if variant == "a2a_lookup":
        rules = dict(rules) | {"__lookup__": "a2a", "__mesh__": mesh}
    elif variant == "a2a_zero":
        # rows sharded over EVERY chip; the exchange spans both axes, so
        # table grads are owner-local (no data-axis reduction at all)
        axes = (("pod", "data", "model") if multi_pod
                else ("data", "model"))
        rules = dict(rules) | {"__lookup__": "a2a", "__mesh__": mesh,
                               "__lookup_axes__": axes}
    init_fn = _CTR_INIT[entry.arch_id]
    fwd = _CTR_FWD[entry.arch_id]
    params_shape = jax.eval_shape(
        lambda k: init_fn(k, cfg), jax.random.PRNGKey(0))
    if variant == "zero_tables":
        # §Perf: ZeRO-style row sharding over data + grads pinned
        # to the param sharding (reduce-scatter instead of all-reduce).
        pspec = _recsys_param_specs_zero(entry.arch_id, params_shape,
                                         multi_pod)
    elif variant == "a2a_zero":
        axes = (("pod", "data", "model") if multi_pod
                else ("data", "model"))

        def leaf_spec(path, x):
            name = jax.tree_util.keystr(path)
            if "tables" in name:
                return P(None, axes, None)
            if "wide" in name:
                return P(None, axes)
            return P(*([None] * len(x.shape)))
        pspec = jax.tree_util.tree_map_with_path(leaf_spec, params_shape)
    else:
        pspec = _recsys_param_specs(entry.arch_id, params_shape)
    # dense-tower flops dominate model flops for CTR models
    mlp_params = cfg.param_count() - cfg.n_sparse * cfg.table_rows * (
        cfg.embed_dim + (1 if entry.arch_id == "wide-deep" else 0))

    if shape.kind == "train":
        B = shape.dims["batch"]
        opt_cfg = _opt_cfg()
        state_shape = jax.eval_shape(
            lambda k: train_step.make_train_state(
                k, lambda kk: init_fn(kk, cfg), opt_cfg),
            jax.random.PRNGKey(0))
        sspec = _state_specs(pspec)
        batch, bspec = _ctr_batch_specs(entry.arch_id, cfg, B, rules)
        batch["labels"] = _sds((B,), F32)
        bspec["labels"] = logical_to_spec(("batch",), rules)
        gshard = (_ns(mesh, pspec)
                  if variant in ("zero_tables", "a2a_zero") else None)
        step = train_step.ctr_train_step(
            lambda p, b: fwd(p, cfg, b), opt_cfg, grad_shardings=gshard)

        def fn(state, b):
            with shlib.axis_rules(rules):
                return step(state, b)

        return Cell(entry.arch_id, shape.shape_id, "train", fn,
                    (state_shape, batch),
                    (_ns(mesh, sspec), _ns(mesh, bspec)),
                    (_ns(mesh, sspec), None), rules,
                    6.0 * mlp_params * B)

    if shape.kind == "serve":
        B = shape.dims["batch"]
        batch, bspec = _ctr_batch_specs(entry.arch_id, cfg, B, rules)

        def fn(params, b):
            with shlib.axis_rules(rules):
                return jax.nn.sigmoid(fwd(params, cfg, b))

        return Cell(entry.arch_id, shape.shape_id, "serve", fn,
                    (params_shape, batch),
                    (_ns(mesh, pspec), _ns(mesh, bspec)), None, rules,
                    2.0 * mlp_params * B)

    # retrieval_cand
    B = shape.dims["batch"]
    rules = dict(rules) | {"batch": None}
    has_dense = entry.arch_id != "wide-deep"

    def fn(params, dense, sparse_ids):
        with shlib.axis_rules(rules):
            return recsys_lib.retrieve_topk(params, cfg, dense, sparse_ids)

    args = (params_shape,
            _sds((B, cfg.n_dense), F32) if has_dense else None,
            _sds((B, cfg.n_sparse), I32))
    in_sh = (_ns(mesh, pspec),
             NamedSharding(mesh, P()) if has_dense else None,
             NamedSharding(mesh, P()))
    mf = 2.0 * B * shape.dims["n_candidates"] * cfg.embed_dim
    return Cell(entry.arch_id, shape.shape_id, "retrieval", fn, args, in_sh,
                None, rules, mf)


def _bert4rec_cell(entry, shape, mesh, multi_pod, variant):
    cfg: recsys_lib.Bert4RecConfig = entry.config
    lm = cfg.lm_config()
    rules = shlib.recsys_rules(multi_pod)
    pspecs = lm_param_specs(lm)
    params_shape = jax.eval_shape(
        lambda k: recsys_lib.bert4rec_init(k, cfg), jax.random.PRNGKey(0))
    dims = shape.dims
    S = dims.get("seq_len", cfg.seq_len)

    if shape.kind == "train":
        B, M, N = dims["batch"], dims["n_masked"], dims["n_negatives"]
        opt_cfg = _opt_cfg()
        state_shape = jax.eval_shape(
            lambda k: train_step.make_train_state(
                k, lambda kk: recsys_lib.bert4rec_init(kk, cfg), opt_cfg),
            jax.random.PRNGKey(0))
        sspec = _state_specs(pspecs)
        batch = {
            "items": _sds((B, S), I32),
            "mask_idx": _sds((B, M), I32),
            "labels": _sds((B, M), I32),
            "negatives": _sds((N,), I32),
        }
        bsp = logical_to_spec(("batch", None), rules)
        bspec = {"items": bsp, "mask_idx": bsp, "labels": bsp,
                 "negatives": P()}
        opt = opt_cfg

        def loss_fn(params, b):
            pos, neg = recsys_lib.bert4rec_sampled_logits(
                params, cfg, b["items"], b["mask_idx"], b["labels"],
                b["negatives"])
            return recsys_lib.sampled_softmax_loss(pos, neg)

        def fn(state, b):
            with shlib.axis_rules(rules):
                loss, grads = jax.value_and_grad(loss_fn)(state["params"], b)
                params, ostate, stats = optimizer.apply(
                    opt, state["params"], grads, state["opt"])
                return ({"params": params, "opt": ostate,
                         "step": state["step"] + 1},
                        {"loss": loss, **stats})

        mf = 6.0 * cfg.param_count() * B * S / max(cfg.n_items, 1)  # emb excl.
        mf = 6.0 * (cfg.param_count() - cfg.n_items * cfg.embed_dim) * B * S
        return Cell(entry.arch_id, shape.shape_id, "train", fn,
                    (state_shape, batch),
                    (_ns(mesh, sspec), _ns(mesh, bspec)),
                    (_ns(mesh, sspec), None), rules, mf)

    if shape.kind == "serve":
        B = dims["batch"]
        if dims.get("full_catalog"):
            def fn(params, items):
                with shlib.axis_rules(rules):
                    _, user = recsys_lib.bert4rec_user_vectors(params, cfg,
                                                               items)
                    scores = recsys_lib.score_candidates(
                        user, params["embed"].astype(user.dtype))
                    return jax.lax.top_k(scores, 100)
            args = (params_shape, _sds((B, S), I32))
        else:
            def fn(params, items, target_items):
                with shlib.axis_rules(rules):
                    _, user = recsys_lib.bert4rec_user_vectors(params, cfg,
                                                               items)
                    it = params["embed"][target_items].astype(user.dtype)
                    return jnp.sum(user * it, axis=-1)
            args = (params_shape, _sds((B, S), I32), _sds((B,), I32))
        bsp = logical_to_spec(("batch", None), rules)
        in_sh = (_ns(mesh, pspecs),) + tuple(
            NamedSharding(mesh, bsp if a.ndim == 2 else
                          logical_to_spec(("batch",), rules))
            for a in args[1:])
        mf = 2.0 * (cfg.param_count() - cfg.n_items * cfg.embed_dim) * B * S
        return Cell(entry.arch_id, shape.shape_id, "serve", fn, args, in_sh,
                    None, rules, mf)

    # retrieval_cand
    B = dims["batch"]
    rules = dict(rules) | {"batch": None}

    def fn(params, items):
        with shlib.axis_rules(rules):
            _, user = recsys_lib.bert4rec_user_vectors(params, cfg, items)
            scores = recsys_lib.score_candidates(
                user, params["embed"].astype(user.dtype))
            return jax.lax.top_k(scores, 100)

    args = (params_shape, _sds((B, S), I32))
    in_sh = (_ns(mesh, pspecs), NamedSharding(mesh, P()))
    mf = 2.0 * B * dims["n_candidates"] * cfg.embed_dim
    return Cell(entry.arch_id, shape.shape_id, "retrieval", fn, args, in_sh,
                None, rules, mf)


# ---------------------------------------------------------------------------
# ColBERT cells (the paper's own architecture)
# ---------------------------------------------------------------------------

def _colbert_cell(entry, shape, mesh, multi_pod, variant):
    cfg: colbert_lib.ColBERTConfig = entry.config
    lm = cfg.lm_config()
    rules = shlib.lm_prefill_rules(multi_pod) | {
        "batch": (("pod", "data", "model") if multi_pod
                  else ("data", "model"))}
    pspecs = {"backbone": lm_param_specs(lm), "proj": P(None, None)}
    params_shape = jax.eval_shape(
        lambda k: colbert_lib.init_params(k, cfg), jax.random.PRNGKey(0))
    dims = shape.dims
    mf_tok = 2.0 * (lm.param_count() - lm.vocab * lm.d_model)

    if shape.shape_id == "train_contrastive":
        B = dims["batch"]
        opt_cfg = _opt_cfg()
        state_shape = jax.eval_shape(
            lambda k: train_step.make_train_state(
                k, lambda kk: colbert_lib.init_params(kk, cfg), opt_cfg),
            jax.random.PRNGKey(0))
        sspec = _state_specs(pspecs)
        step = train_step.colbert_train_step(cfg, opt_cfg, reg="sim",
                                             alpha=0.1)
        batch = {"query_ids": _sds((B, dims["query_len"]), I32),
                 "doc_ids": _sds((B, dims["doc_len"]), I32)}
        bsp = logical_to_spec(("batch", None), rules)
        bspec = {"query_ids": bsp, "doc_ids": bsp}

        def fn(state, b):
            with shlib.axis_rules(rules):
                return step(state, b)

        mf = 3.0 * mf_tok * B * (dims["query_len"] + dims["doc_len"])
        return Cell(entry.arch_id, shape.shape_id, "train", fn,
                    (state_shape, batch),
                    (_ns(mesh, sspec), _ns(mesh, bspec)),
                    (_ns(mesh, sspec), None), rules, mf)

    if shape.shape_id == "encode_corpus":
        B = dims["batch"]

        def fn(params, doc_ids):
            with shlib.axis_rules(rules):
                emb, mask = colbert_lib.encode_docs(params, cfg, doc_ids)
                return emb, mask

        args = (params_shape, _sds((B, dims["doc_len"]), I32))
        in_sh = (_ns(mesh, pspecs),
                 NamedSharding(mesh, logical_to_spec(("batch", None), rules)))
        mf = mf_tok * B * dims["doc_len"]
        return Cell(entry.arch_id, shape.shape_id, "serve", fn, args, in_sh,
                    None, rules, mf)

    if shape.shape_id == "prune_index":
        nd, m = dims["docs_per_block"], dims["doc_len"]
        N, dim = dims["n_samples"], dims["out_dim"]
        from repro.core import voronoi

        def fn(d_embs, d_masks, samples):     # the platform's pruning scan
            with shlib.axis_rules(rules):
                return voronoi.pruning_order_batch(d_embs, d_masks, samples)

        args = (_sds((nd, m, dim), F32), _sds((nd, m), jnp.bool_),
                _sds((N, dim), F32))
        bsp = logical_to_spec(("batch", None, None), rules)
        in_sh = (NamedSharding(mesh, bsp),
                 NamedSharding(mesh, logical_to_spec(("batch", None), rules)),
                 NamedSharding(mesh, P()))
        mf = 2.0 * nd * N * m * dim  # one full score pass (amortized bound)
        return Cell(entry.arch_id, shape.shape_id, "serve", fn, args, in_sh,
                    None, rules, mf)

    # rerank: n_queries=128 < 256 devices -> batch shards over data(+pod),
    # candidates shard over model (the rerank fan-out axis).
    nq, nc = dims["n_queries"], dims["n_candidates"]
    lq, m = dims["query_len"], dims["doc_len"]
    dim = cfg.out_dim
    rules = dict(rules) | {
        "batch": (("pod", "data") if multi_pod else ("data",)),
        "candidates": ("model",)}

    def fn(q_embs, d_embs, d_masks):
        with shlib.axis_rules(rules):
            s = jnp.einsum("qld,qnmd->qnlm", q_embs, d_embs)
            s = jnp.where(d_masks[:, :, None, :], s, -1e30)
            out = s.max(-1).sum(-1)
            return shlib.constrain(out, "batch", "candidates")

    args = (_sds((nq, lq, dim), F32), _sds((nq, nc, m, dim), F32),
            _sds((nq, nc, m), jnp.bool_))
    in_sh = (NamedSharding(mesh, logical_to_spec(("batch", None, None), rules)),
             NamedSharding(mesh, logical_to_spec(
                 ("batch", "candidates", None, None), rules)),
             NamedSharding(mesh, logical_to_spec(
                 ("batch", "candidates", None), rules)))
    mf = 2.0 * nq * nc * lq * m * dim
    return Cell(entry.arch_id, shape.shape_id, "serve", fn, args, in_sh,
                None, rules, mf)


# ---------------------------------------------------------------------------

def build_cell(arch_id: str, shape_id: str, mesh, *, multi_pod: bool = False,
               variant: str = "baseline") -> Cell:
    entry = cfgbase.get(arch_id)
    shape = entry.shapes[shape_id]
    if shape.skip:
        return Cell(arch_id, shape_id, shape.kind, None, (), (), None, {},
                    0.0, skip=shape.skip)
    if entry.family == "lm":
        return _lm_cell(entry, shape, mesh, multi_pod, variant)
    if entry.family == "gnn":
        return _gnn_cell(entry, shape, mesh, multi_pod, variant)
    if entry.family == "recsys":
        return _recsys_cell(entry, shape, mesh, multi_pod, variant)
    if entry.family == "retrieval":
        return _colbert_cell(entry, shape, mesh, multi_pod, variant)
    raise ValueError(f"unknown family {entry.family}")
