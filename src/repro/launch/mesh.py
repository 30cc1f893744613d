"""Production mesh construction (multi-pod dry-run target).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run driver sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; everything else sees the real device count).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding layer
    places arrays by ``with_sharding_constraint`` and ``shard_map``
    specs, which is Auto-axis semantics (``make_mesh`` now defaults to
    Explicit axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever the current host offers, as a (data, model) mesh — used by
    smoke tests and CPU examples (usually 1x1)."""
    n = len(jax.devices())
    data = max(1, n // 1)
    return auto_mesh((data, 1), ("data", "model"))


def make_serve_mesh(hosts: int = 1):
    """Serving mesh.

    ``hosts=1`` (default): the flat host mesh — every local device on
    the ``model`` axis, which the serving rule set
    (``sharding.serve_rules``) places the corpus doc axis
    ("candidates") over, so the streaming top-k merge shards each
    capacity bucket across the whole host.

    ``hosts>1``: the multi-host placement grid — a 2-D
    ``hosts x candidates`` mesh where each row of devices is one host
    group.  A ``sharding.PlacementPlan`` pins every packed capacity
    bucket to one group; the bucket's doc axis spans that group's
    ``candidates`` devices, and the streaming merge exchanges one
    ``(n_q, k)`` candidate block per *group* instead of per shard
    (DESIGN_BACKENDS.md §Placement).  The device count must divide
    evenly into rows.
    """
    n = max(1, len(jax.devices()))
    if hosts <= 1:
        return auto_mesh((1, n), ("data", "model"))
    if n % hosts:
        raise ValueError(
            f"make_serve_mesh(hosts={hosts}): {n} devices do not divide "
            f"into {hosts} host groups")
    return auto_mesh((hosts, n // hosts), ("hosts", "candidates"))


def default_serve_hosts() -> int:
    """Auto host-group count for ``--mesh grid``: the largest power of
    two ``h`` with ``h * h <= n_devices`` that divides the device count
    (4 devices -> a 2x2 grid; 1-2 devices -> 1, i.e. the flat mesh)."""
    n = max(1, len(jax.devices()))
    h = 1
    while 2 * h * (2 * h) <= n and n % (2 * h) == 0:
        h *= 2
    return h
