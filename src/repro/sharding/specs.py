"""Logical-axis sharding rules (MaxText-style) decoupling models from meshes.

Models annotate tensors with *logical* axis names ("batch", "embed",
"heads", "expert", "table_rows", ...).  A launcher activates a rule set
mapping logical names -> mesh axis names; `constrain` then applies
`with_sharding_constraint` with the resulting PartitionSpec.  With no
active rules (unit tests on CPU) every annotation is a no-op, so model
code never needs a mesh to run.

Rule values may be a mesh axis name, a tuple of mesh axes (e.g.
("pod", "data") for the flattened DP axis in the multi-pod mesh), or
None (replicated).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def current_rules() -> dict | None:
    return getattr(_state, "rules", None)


@contextmanager
def axis_rules(rules: dict[str, str | tuple | None]):
    """Activate logical->mesh axis rules for the enclosed region."""
    prev = current_rules()
    _state.rules = dict(rules)
    try:
        yield
    finally:
        _state.rules = prev


def logical_to_spec(logical_axes: tuple[str | None, ...],
                    rules: dict | None = None) -> P:
    rules = rules if rules is not None else (current_rules() or {})
    resolved = []
    used: set[str] = set()
    for name in logical_axes:
        axes = rules.get(name) if name is not None else None
        # A mesh axis may appear at most once in a PartitionSpec; later
        # logical axes that map onto an already-used mesh axis replicate.
        if axes is None:
            resolved.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        free = tuple(a for a in axes if a not in used)
        used.update(free)
        resolved.append(free if len(free) > 1 else (free[0] if free else None))
    return P(*resolved)


def spec_for(*logical_axes: str | None) -> P:
    return logical_to_spec(tuple(logical_axes))


def _outside_mesh_context(err: Exception) -> bool:
    """True when a ``with_sharding_constraint`` failure happened because
    no mesh context is active (the benign case ``constrain`` no-ops).
    Both must hold: JAX's public context probe sees no mesh, and the
    error is JAX's own "requires a non-empty mesh" refusal — so any
    other error, or one raised under a mesh context, still surfaces."""
    return (jax.sharding.get_abstract_mesh().empty
            and "non-empty mesh" in str(err))


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Apply a sharding constraint by logical axis names (no-op w/o rules)."""
    rules = current_rules()
    if not rules:
        return x
    spec = logical_to_spec(tuple(logical_axes), rules)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except RuntimeError as e:
        # Outside a mesh context (e.g. pure CPU eval) constraints are moot
        # — but ONLY that case may be swallowed.  Genuine sharding errors
        # (wrong-rank specs, divisibility failures) used to vanish into a
        # blanket ``except Exception`` here; they re-raise now.
        if _outside_mesh_context(e):
            return x
        raise


def mesh_axes_for(logical: str, rules: dict | None = None):
    """Resolve one logical axis to ``(mesh, mesh_axes, n_shards)`` under
    the active rules.

    ``with_sharding_constraint`` only needs a *spec*; explicit SPMD code
    (``shard_map`` callers like the streaming top-k merge) needs the
    concrete mesh too, which rule sets carry under the ``"__mesh__"``
    key (the convention the a2a embedding exchange established).
    Returns ``(None, (), 1)`` when no mesh is carried or the logical
    axis is replicated; mesh axes missing from the mesh are dropped.
    """
    rules = rules if rules is not None else (current_rules() or {})
    mesh = rules.get("__mesh__")
    if mesh is None:
        return None, (), 1
    axes = rules.get(logical)
    if axes is None:
        return None, (), 1
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(a for a in axes if a in getattr(mesh, "axis_names", ()))
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if not axes or n <= 1:
        return None, (), 1
    return mesh, axes, n


# Canonical rule sets -------------------------------------------------------
#
# Baseline posture (DESIGN.md §8): training batches shard over every
# available device (ZeRO-3-like), params FSDP over `data` on the embed
# axis + tensor-parallel over `model` on heads/ffn/vocab/expert axes;
# XLA overlaps the per-scanned-layer weight all-gathers with compute.

_LM_COMMON = {
    "fsdp": ("data",),
    "embed": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "expert": None,            # TP-MoE baseline; EP variant flips this
    "vocab": ("model",),
    "kv_len": None,
    "table_axis": None,
    "table_rows": None,
    "candidates": ("model",),
}


def lm_train_rules(multi_pod: bool) -> dict:
    r = dict(_LM_COMMON)
    if multi_pod:
        # global batch (256) < devices (512): DP over (pod, data), stored
        # activations sequence-sharded over `model` (Megatron-SP style).
        r |= {"batch": ("pod", "data"), "seq": ("model",)}
    else:
        r |= {"batch": ("data", "model"), "seq": None}
    return r


def lm_prefill_rules(multi_pod: bool) -> dict:
    dp = ("pod", "data") if multi_pod else ("data",)
    return dict(_LM_COMMON) | {"batch": dp, "seq": None}


def lm_decode_rules(multi_pod: bool, *, batch: int = 0) -> dict:
    dp = ("pod", "data") if multi_pod else ("data",)
    # kv_heads (8) does not divide the 16-way model axis -> the KV cache
    # shards its LENGTH over `model` instead (32768/16 or window/16).
    r = dict(_LM_COMMON) | {"batch": dp, "seq": None,
                            "kv_heads": None, "kv_len": ("model",)}
    if batch == 1:
        # long_500k: nothing to shard on batch; shard the ring cache length
        # over both axes (window is a multiple of 256).
        r |= {"batch": None,
              "kv_len": ("data", "model") if not multi_pod
              else ("pod", "data", "model")}
    return r


def lm_rules_ep_moe(rules: dict) -> dict:
    """Hillclimb variant: experts sharded over `model` (all-to-all MoE)."""
    return rules | {"expert": ("model",), "ffn": None}


def gnn_rules(multi_pod: bool) -> dict:
    dp = ("pod", "data", "model") if multi_pod else ("data", "model")
    return {
        "edges": dp,                # edge list fully sharded
        "nodes": None,              # node features replicated (psum combine)
        "feat": None,
        "batch": dp,
        "hidden": None,
    }


def recsys_rules(multi_pod: bool) -> dict:
    dp = ("pod", "data", "model") if multi_pod else ("data", "model")
    return {
        "batch": dp,
        "table_axis": ("model",),   # table-wise sharding (baseline)
        "table_rows": None,         # hillclimb variant: row-wise sharding
        "embed": None,
        "mlp_in": None,
        "mlp_out": ("model",),
        "heads": ("model",),
        "ffn": ("model",),
        "seq": None,
        "candidates": ("model",),
        "vocab": ("model",),
        "fsdp": ("data",),
        "expert": None,
        "kv_heads": ("model",),
        "kv_len": None,
    }


def recsys_rules_rowsharded(multi_pod: bool) -> dict:
    """Hillclimb variant: row-wise table sharding (EP-style lookups)."""
    r = recsys_rules(multi_pod)
    r["table_axis"] = None
    r["table_rows"] = ("model",)
    return r


def serve_rules(mesh=None, placement=None) -> dict:
    """Retrieval-serving rule set (sharded-bucket serving).

    Queries are replicated (every shard scores its local docs against
    the whole query batch); the corpus doc axis — logical "candidates",
    which both the dense index and every packed capacity bucket carry as
    their leading axis — shards over the mesh's candidate-parallel axis:
    ``model`` on the flat host mesh (``launch.mesh.make_serve_mesh()``,
    every local device on one axis), ``candidates`` on the 2-D
    ``hosts x candidates`` grid (``make_serve_mesh(hosts=...)``), where
    each capacity bucket spans the candidates axis *within* the host
    group a :class:`repro.sharding.placement.PlacementPlan` pins it to.

    Passing ``mesh`` embeds it under ``"__mesh__"`` so explicit-SPMD
    consumers (the streaming top-k merge's ``shard_map``, the sharded
    ``global_keep_masks`` merge) can reach the concrete mesh; without it
    the rules still drive ``constrain`` specs but the streaming merge
    stays single-device.  ``placement`` rides under ``"__placement__"``
    (grid meshes only; ``topk_search`` derives the deterministic
    bytes-balanced default when absent).
    """
    grid = "hosts" in getattr(mesh, "axis_names", ())
    r = {
        "batch": None,
        "candidates": ("candidates",) if grid else ("model",),
        "embed": None,
        "seq": None,
    }
    if mesh is not None:
        r["__mesh__"] = mesh
    if placement is not None:
        r["__placement__"] = placement
    return r


def data_mesh_for(sharded: bool | None, *, who: str):
    """Resolve the ``data``-axis mesh explicit-SPMD pruning consumers
    shard over — the one auto/force/off policy shared by
    ``voronoi.global_keep_masks`` and
    ``pruning_pipeline.pruning_order_bucketed`` (they promise to
    distribute "the same way"; a single resolver keeps that true).

    ``None`` auto-enables when the active rules carry a ``"__mesh__"``
    whose ``data`` axis is wider than 1; ``True`` requires one (the
    error names ``who``, the caller); ``False`` never shards.
    """
    if sharded is False:
        return None
    mesh = (current_rules() or {}).get("__mesh__")
    ok = (mesh is not None
          and "data" in getattr(mesh, "axis_names", ())
          and mesh.shape["data"] > 1)
    if sharded and not ok:
        raise ValueError(
            f"{who}(sharded=True) needs active sharding rules carrying "
            "a '__mesh__' with a data axis wider than 1 (see "
            "sharding.axis_rules)")
    return mesh if ok else None


def grid_axes_for(rules: dict | None = None):
    """Resolve the active rules' multi-host serving grid.

    Returns ``(mesh, n_groups, n_cand, placement)`` when the rules carry
    a ``"__mesh__"`` that is a 2-D ``hosts x candidates`` grid with more
    than one host group (``launch.mesh.make_serve_mesh(hosts=...)``);
    ``placement`` is the rules' ``"__placement__"`` plan or None.
    Returns ``(None, 1, 1, None)`` otherwise — flat meshes keep the
    single-tier sharded merge, and a 1-group grid degenerates to it.
    """
    rules = rules if rules is not None else (current_rules() or {})
    mesh = rules.get("__mesh__")
    names = getattr(mesh, "axis_names", ())
    if mesh is None or "hosts" not in names or "candidates" not in names:
        return None, 1, 1, None
    n_groups = mesh.shape["hosts"]
    if n_groups <= 1:
        return None, 1, 1, None
    return mesh, n_groups, mesh.shape["candidates"], rules.get("__placement__")
