"""Sharded-execution tests: these run jitted code on a multi-device host
mesh (via a subprocess that sets the fake device count before jax
initializes) and verify that the distribution layer computes the same
numbers as the single-device reference.

Also covers: cell-builder integrity for every (arch x shape) pair (spec
trees match arg trees; skips are marked), and the a2a embedding exchange
forward+gradient parity.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

from repro import configs
from repro.configs import base as cfgbase

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_a2a_lookup_matches_dense_fwd_and_grad():
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.recsys import alltoall_lookup
from repro.launch.mesh import auto_mesh
from repro.sharding.specs import axis_rules
mesh = auto_mesh((2, 4), ("data", "model"))
F, V, D, B = 3, 32, 8, 16
tables = jax.random.normal(jax.random.PRNGKey(0), (F, V, D))
ids = jax.random.randint(jax.random.PRNGKey(1), (B, F), 0, V)
ref = jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
               in_axes=(0, 1), out_axes=1)(tables, ids)
rules = {"__mesh__": mesh, "__lookup__": "a2a",
         "__lookup_axes__": ("data", "model")}
def fwd(t, i):
    with axis_rules(rules):
        return alltoall_lookup(t, i, capacity_factor=8.0)
with mesh:
    out = jax.jit(fwd, in_shardings=(
        NamedSharding(mesh, P(None, ("data", "model"), None)),
        NamedSharding(mesh, P(("data", "model"), None))))(tables, ids)
assert jnp.allclose(out, ref, atol=1e-5), "fwd mismatch"
def loss(t):
    with axis_rules(rules):
        return (alltoall_lookup(t, ids, capacity_factor=8.0) ** 2).sum()
with mesh:
    g = jax.jit(jax.grad(loss), in_shardings=(
        NamedSharding(mesh, P(None, ("data", "model"), None)),))(tables)
g_ref = jax.grad(lambda t: (jax.vmap(
    lambda tt, i: jnp.take(tt, i, axis=0), in_axes=(0, 1),
    out_axes=1)(t, ids) ** 2).sum())(tables)
assert jnp.allclose(g, g_ref, atol=1e-4), "grad mismatch"
print("A2A_OK")
"""
    assert "A2A_OK" in _run_subprocess(code)


def test_sharded_lm_train_step_matches_single_device():
    """A smoke-size LM train step produces the same loss on a 2x4 mesh
    with FSDP-sharded params as on one device."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import sharding as shlib
from repro.models import transformer as tfm
from repro.launch.mesh import auto_mesh
from repro.train import optimizer, train_step
cfg = tfm.LMConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                   n_kv_heads=2, d_ff=64, vocab=64,
                   param_dtype=jnp.float32, compute_dtype=jnp.float32,
                   remat=False)
opt = optimizer.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
state = train_step.make_train_state(
    jax.random.PRNGKey(0), lambda k: tfm.init_params(k, cfg), opt)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
step = train_step.lm_train_step(cfg, opt)
_, m_ref = jax.jit(step)(state, {"tokens": tokens})

mesh = auto_mesh((2, 4), ("data", "model"))
rules = shlib.lm_train_rules(False)
def fn(s, b):
    with shlib.axis_rules(rules):
        return step(s, b)
pspec = jax.tree_util.tree_map(lambda x: P(), state)
with mesh:
    _, m_sh = jax.jit(fn, in_shardings=(
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspec,
                               is_leaf=lambda x: isinstance(x, P)),
        {"tokens": NamedSharding(mesh, P(("data", "model"), None))}))(
        state, {"tokens": tokens})
d = abs(float(m_ref["loss"]) - float(m_sh["loss"]))
assert d < 1e-4, f"loss diverged: {d}"
print("LM_SHARD_OK")
"""
    assert "LM_SHARD_OK" in _run_subprocess(code)


@pytest.mark.parametrize("arch_id", configs.ASSIGNED + ["colbert"])
def test_cell_builders_integrity(arch_id):
    """Every (arch x shape) builds: spec trees match arg trees leaf-for-
    leaf and all shardings are divisibility-legal on the production mesh
    (verified abstractly — no compile)."""
    from repro.launch import steps

    class FakeMesh:
        pass

    # use a real production-shaped mesh object only for NamedSharding
    # construction; no computation happens.
    import numpy as np
    from jax.sharding import NamedSharding

    mesh = jax.sharding.Mesh(
        np.array(jax.devices() * 256)[:256].reshape(16, 16),
        ("data", "model"))
    entry = configs.get(arch_id)
    for shape_id in entry.shapes:
        cell = steps.build_cell(arch_id, shape_id, mesh, multi_pod=False)
        if cell.skip:
            continue
        assert cell.fn is not None
        flat_args = jax.tree_util.tree_leaves(cell.args)
        flat_sh = jax.tree_util.tree_leaves(
            cell.in_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        assert len(flat_args) == len(flat_sh), (
            arch_id, shape_id, len(flat_args), len(flat_sh))
        for a, s in zip(flat_args, flat_sh):
            assert isinstance(s, NamedSharding), (arch_id, shape_id)
            spec = s.spec
            # divisibility check per sharded dim
            for dim, part in enumerate(spec):
                if part is None:
                    continue
                axes = (part,) if isinstance(part, str) else part
                n = 1
                for ax in axes:
                    n *= dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
                assert a.shape[dim] % n == 0, (
                    arch_id, shape_id, a.shape, spec)


def test_skips_documented():
    skipped = []
    for arch in configs.ASSIGNED:
        for sid, sh in configs.get(arch).shapes.items():
            if sh.skip:
                skipped.append((arch, sid))
                assert "attention" in sh.skip or "sub-quadratic" in sh.skip
    assert sorted(skipped) == [("minitron-4b", "long_500k"),
                               ("qwen2.5-32b", "long_500k"),
                               ("stablelm-3b", "long_500k")]


def test_dryrun_records_complete():
    """If the dry-run sweep has been run, every assigned cell must be ok
    or a documented skip on BOTH meshes."""
    dr = os.path.join(ROOT, "EXPERIMENTS", "dryrun")
    if not os.path.isdir(dr) or not os.listdir(dr):
        pytest.skip("dry-run sweep not executed in this checkout")
    for mesh_name in ("pod16x16", "pod2x16x16"):
        for arch in configs.ASSIGNED:
            for sid in configs.get(arch).shapes:
                path = os.path.join(
                    dr, f"{arch}__{sid}__{mesh_name}__baseline.json")
                if not os.path.exists(path):
                    pytest.skip(f"sweep incomplete: {path} missing")
                with open(path) as f:
                    rec = json.load(f)
                assert rec["status"] in ("ok", "skipped"), (arch, sid,
                                                            mesh_name)
