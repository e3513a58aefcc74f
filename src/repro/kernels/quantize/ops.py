"""Jit-able wrappers: flatten/pad any-rank arrays into aligned 2D tiles.

The kernels compile for the TPU unless a caller passes ``interpret=True``
(the Pallas interpreter, for validation off the chip)."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .kernel import dequantize_2d, quantize_2d, quantize_rows_2d


def _to_2d(x: jax.Array, block_r: int, block_c: int) -> Tuple[jax.Array, int]:
    flat = x.reshape(-1)
    cols = block_c
    rows = math.ceil(flat.size / cols)
    rows_pad = (-rows) % block_r
    pad = rows * cols - flat.size + rows_pad * cols
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows + rows_pad, cols), pad


def quantize_int8(x: jax.Array, block_r: int = 128, block_c: int = 128, interpret: bool = False):
    """Any-shape → (q int8 [R,C], scales [R/br, C/bc], meta)."""
    x2, pad = _to_2d(x, block_r, block_c)
    q, s = quantize_2d(x2, block_r, block_c, interpret=interpret)
    return q, s, {"shape": x.shape, "dtype": x.dtype, "pad": pad}


def quantize_rows_int8(x, row_block: int = 32, interpret: bool = False):
    """[M, C] → (int8 [M, C], fp32 scales [M, 1]), one scale per row.

    Backs the batched ``QuantizeInt8`` enforcement object: the whole batch's
    blocks are packed row-wise and quantized in ONE kernel launch. Rows are
    padded to ``row_block`` (TPU sublane alignment) and sliced back, so any
    batch size is accepted. Accepts numpy or jax arrays.
    """
    x = jnp.asarray(x, jnp.float32)
    m, c = x.shape
    pad = (-m) % row_block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    q, s = quantize_rows_2d(x, row_block=row_block, interpret=interpret)
    return q[:m], s[:m]


def dequantize_int8(q: jax.Array, s: jax.Array, meta, block_r: int = 128, block_c: int = 128, interpret: bool = False):
    x2 = dequantize_2d(q, s, jnp.float32, block_r, block_c, interpret=interpret)
    flat = x2.reshape(-1)
    if meta["pad"]:
        flat = flat[: flat.size - meta["pad"]]
    return flat.reshape(meta["shape"]).astype(meta["dtype"])
