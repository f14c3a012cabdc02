"""Production mesh construction (deliverable e).

A FUNCTION, not a module constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests see the real single CPU device).

Target: TPU v5e pods — 256 chips/pod as a (16, 16) (data, model) mesh;
multi-pod prepends a "pod" axis: (2, 16, 16). The chip's peaks live with
the chip benchmark, keyed by device kind (`bench/devices.json`).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """`jax.make_mesh` with Auto axis types (its default is Explicit,
    which the pjit-style shardings in this repo do not use)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(tuple(axes)),
                         devices=devices)


def current_mesh():
    """The abstract mesh installed by ``jax.sharding.set_mesh``, or None
    when no mesh context is active (callers fall back to unsharded
    paths)."""
    m = jax.sharding.get_abstract_mesh()
    return m if m is not None and m.axis_names else None


# --------------------------------------------------------------- factories
def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
