"""Production meshes.

Single pod: 16 x 16 = 256 chips (data x model).
Multi-pod:  2 x 16 x 16 = 512 chips (pod x data x model) — the "pod" axis is
data-parallel across ICI-connected pods (DCN at real scale); the sharding
rules map logical "batch" to ("pod", "data") so the same model code serves
both meshes.

Every mesh in the repo is built by :func:`make_mesh`, which gives each axis
``AxisType.Auto``: the model code places arrays with
``with_sharding_constraint`` (``runtime/sharding.py``), which JAX accepts
only on Auto axes (``jax.make_mesh`` defaults to Explicit axes).

The constructors are *functions* (never module-level constants) so
importing this module touches no jax device state.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 2, *, devices: Optional[Sequence] = None):
    """``(data, model)`` mesh over ``devices`` (default: all local ones)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    model_axis = min(model_axis, n)
    return make_mesh((n // model_axis, model_axis), ("data", "model"),
                     devices=devices)
