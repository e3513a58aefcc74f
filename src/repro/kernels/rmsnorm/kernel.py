"""Fused RMSNorm Pallas kernel.

Every assigned architecture normalizes twice per layer; unfused XLA emits a
square → mean → rsqrt → mul chain with multiple HBM round-trips of the
[tokens, d_model] activation. The kernel computes the whole chain in one VMEM
pass per (block_rows × d) tile: read x once, write y once.

Grid: one step per row-block; the full feature dim stays resident (d ≤ 16k
at fp32 = 64 KB/row-block-row — with block_rows=256 and d=12288 the tile is
12 MB fp32 → block_rows is chosen by ``ops`` to fit ~2 MB in VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_2d(x: jax.Array, scale: jax.Array, eps: float = 1e-5, block_rows: int = 128, interpret: bool = False):
    rows, d = x.shape
    assert rows % block_rows == 0, "caller pads rows to a block multiple"
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, scale)
