"""Production mesh construction (functions only — importing this module must
never touch jax device state).

Every mesh uses ``Auto`` axes: shardings are propagated by GSPMD from the
jit in/out shardings and the party engine's ``shard_map`` specs.
"""
from __future__ import annotations

import jax


def _auto_kwargs(n):
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 = 256 chips/pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_kwargs(len(axes)))


def make_party_mesh(n: int | None = None, axis: str = "party"):
    """1-D mesh laying the EASTER party dimension over devices.

    Used by the sharded party engine (core/party_engine.py): party groups
    whose size is a multiple of the axis run K-parallel across devices.
    Any other group — every group, on a single device — runs replicated:
    the whole group is computed on every device. ``n=None`` takes every
    local device.
    """
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (axis,), **_auto_kwargs(1))


def require_party_layout(mesh, n_passive: int) -> None:
    """Raise unless a stack of ``n_passive`` parties lays out over the
    mesh's party axis. An entry point asked for the sharded engine calls
    this, so that a stack the engine would run replicated on every device
    is refused instead of reported as sharded."""
    from repro.sharding import party_axis_size, party_shardable
    if not party_shardable(mesh, n_passive):
        size = party_axis_size(mesh)
        raise ValueError(
            f"--engine sharded: {n_passive} passive parties cannot lay out "
            f"over a {size}-device party axis; it needs more than one "
            f"device and a passive count that is a multiple of the axis "
            f"(e.g. --num-passive {max(size, 2)})")


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small host mesh for CPU integration tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         **_auto_kwargs(2))

