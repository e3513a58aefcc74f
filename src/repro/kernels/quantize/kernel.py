"""Int8 block-quantization Pallas kernels.

The device-side twin of PAIO's data-transformation enforcement object
(paper §3.1): used by the compressed all-reduce (gradient compression with
error feedback) and by quantized checkpoint shards.

Each (block_r × block_c) tile gets one fp32 scale = absmax/127 — tiles are
(128, 128) by default so rows/lanes align with the VPU/MXU layout and one
tile plus its scale comfortably fits VMEM.

A TPU block's trailing two dims must be multiples of (8, 128) or span the
whole array, so a per-tile ``(1, 1)`` scale block is illegal there. The tile
kernels therefore move each scale broadcast over an ``(8, block_c)`` fp32
tile (4 KiB per 128×128 tile); the wrappers take and return the compact
``[R/block_r, C/block_c]`` scale array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: sublanes of a scale tile: the fewest a layout-legal fp32 block may have
_SCALE_ROWS = 8


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.max(jnp.abs(x), axis=1, keepdims=True), axis=0, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0  # [1, 1]
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, x_ref):
    # every lane of the scale tile holds the scale: broadcast one row over
    # sublanes (Mosaic cannot broadcast a [1, 1] slice over both axes)
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[0:1, :]).astype(x_ref.dtype)


def quantize_2d(x: jax.Array, block_r: int = 128, block_c: int = 128, interpret: bool = False):
    """x [R, C] (R % block_r == 0, C % block_c == 0) → (int8 [R,C], fp32
    scales [R/block_r, C/block_c])."""
    r, c = x.shape
    gr, gc = r // block_r, c // block_c
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(gr, gc),
        in_specs=[pl.BlockSpec((block_r, block_c), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((_SCALE_ROWS, block_c), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((gr * _SCALE_ROWS, c), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s[::_SCALE_ROWS, ::block_c]


def _quant_rows_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # one scale per row
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def quantize_rows_2d(x: jax.Array, row_block: int = 32, interpret: bool = False):
    """x [M, C] (M % row_block == 0) → (int8 [M, C], fp32 scales [M, 1]).

    Row-granular twin of :func:`quantize_2d`, used by the batched enforcement
    path: each row is one request block, so a whole enforcement batch becomes a
    single fused kernel launch. ``row_block`` = 32 satisfies the int8 sublane
    minimum so input and output tiles are layout-legal on TPU.
    """
    m, c = x.shape
    grid = (m // row_block,)
    return pl.pallas_call(
        _quant_rows_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((row_block, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((row_block, c), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, c), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def dequantize_2d(q: jax.Array, s: jax.Array, out_dtype=jnp.float32, block_r: int = 128, block_c: int = 128, interpret: bool = False):
    """(int8 [R, C], fp32 scales [R/block_r, C/block_c]) → out_dtype [R, C]."""
    r, c = q.shape
    grid = (r // block_r, c // block_c)
    s_tiles = jnp.repeat(jnp.repeat(s.astype(jnp.float32), _SCALE_ROWS, 0), block_c, 1)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((_SCALE_ROWS, block_c), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        interpret=interpret,
    )(q, s_tiles)
