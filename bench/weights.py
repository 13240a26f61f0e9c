"""Weights from the seed: the draw every architecture shares.

An architecture (``archs/<model_type>.py``) names its weights, their shapes
and their scales; ``draw`` makes them from the two words of a threefry key,
the weight that comes ``i``-th in sorted name order from ``fold_in(key,
i)``. The architecture draws once in a plain layout for its reference and
once, inside its own jitted call, in the program's parameter tree, so the
reference never takes a weight that the program made.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


def seed_key_data(seed: int) -> np.ndarray:
    """Any whole-number seed (more than 32 bits too) as the two uint32
    words of a threefry key, so one compiled program serves every seed."""
    seed = int(seed) % (1 << 64)
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def draw(shapes: Dict[str, tuple], scale: Callable[[str], float],
         key_data) -> Dict[str, jnp.ndarray]:
    """``scale(name)`` times a standard normal of each shape, float32.
    Call it inside a jitted function, with ``key_data`` from
    ``seed_key_data``."""
    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    return {name: scale(name) * jax.random.normal(
                jax.random.fold_in(key, i), shapes[name], jnp.float32)
            for i, name in enumerate(sorted(shapes))}
