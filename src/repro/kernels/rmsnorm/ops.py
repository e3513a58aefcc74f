"""Jit-able wrapper: any [..., d] input, VMEM-aware row blocking."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import rmsnorm_2d

#: one fp32 row-block tile. The kernel holds about six such tiles in VMEM
#: (input and output, each double-buffered, plus fp32 temporaries), which
#: must stay under the 16 MiB scoped-VMEM limit of a v5e core.
_VMEM_BUDGET = 2 * 1024 * 1024


def rms_norm_fused(x: jax.Array, scale: jax.Array, eps: float = 1e-5, interpret: bool = False):
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    x2 = x.reshape(rows, d)
    # block_rows: tile ≤ VMEM budget at fp32, multiple of 8, ≤ rows
    block = max(min(_VMEM_BUDGET // (d * 4), rows), 1)
    block = max((block // 8) * 8, 1)
    pad = (-rows) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_2d(x2, scale, eps=eps, block_rows=block, interpret=interpret)
    if pad:
        out = out[:rows]
    return out.reshape(*lead, d)
