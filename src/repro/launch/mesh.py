"""Device meshes.

``make_mesh`` builds a ``data × model`` (or ``pod × data × model``) mesh over
the visible devices. A function, not a module constant, so importing this
module never touches JAX device state.

Mesh axes are ``Auto``: the model places intermediates with
``with_sharding_constraint`` by logical axis (``distributed.sharding``) and
leaves the rest to the compiler, which ``jax.make_mesh``'s default
``Explicit`` axes refuse.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary (data, model[, pod]) mesh for tests and small runs."""
    if axes is None:
        axes = ("data", "model")[: len(shape)] if len(shape) <= 2 else ("pod", "data", "model")
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(shape))
