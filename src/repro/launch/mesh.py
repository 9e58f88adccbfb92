"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — critical because smoke tests must see 1 device
while the dry-run forces 512 host devices via XLA_FLAGS before any import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, **kw):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    place arrays with ``NamedSharding`` and let the compiler propagate the
    rest, which ``Explicit`` axes (the default since JAX 0.7) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """1x1 mesh over whatever the host has — smoke tests / examples."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))


def make_tp_mesh(tp: int):
    """1-D tensor-parallel mesh for the sharded serving megastep
    (DESIGN.md §13). Raises ValueError (not a jax internal error) when the
    host doesn't have ``tp`` devices, so launchers can surface it as a CLI
    error. On CPU, force virtual devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax
    initialises."""
    n = len(jax.devices())
    if tp < 1:
        raise ValueError(f"tp={tp} must be >= 1")
    if tp > n:
        raise ValueError(
            f"tp={tp} exceeds the {n} visible device(s); on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count to fake more")
    return _auto_mesh((tp,), ("tp",), devices=jax.devices()[:tp])
