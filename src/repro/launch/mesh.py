"""Production meshes.

``make_production_mesh`` builds the target deployment mesh: one v5e pod of
16×16 = 256 chips (axes ``data × model``), or two pods = 512 chips with a
leading ``pod`` axis. Functions (not module constants) so importing this
module never touches JAX device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first use.

Mesh axes are ``Auto``: the model places intermediates with
``with_sharding_constraint`` by logical axis (``distributed.sharding``) and
leaves the rest to the compiler, which ``jax.make_mesh``'s default
``Explicit`` axes refuse.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None):
    """Arbitrary (data, model[, pod]) mesh for tests and small runs."""
    if axes is None:
        axes = ("data", "model")[: len(shape)] if len(shape) <= 2 else ("pod", "data", "model")
    return _auto_mesh(tuple(shape), tuple(axes))


HW = {
    # TPU v5e, per chip
    "peak_flops_bf16": 197e12,  # FLOP/s
    "hbm_bandwidth": 819e9,  # B/s
    "hbm_bytes": 16 * 1024**3,
    "ici_link_bandwidth": 50e9,  # B/s per link (one direction)
}
