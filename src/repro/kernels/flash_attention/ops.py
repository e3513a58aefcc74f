"""Jit-able wrapper around the flash-attention Pallas kernel.

Handles layout ([B,S,H,d] ⇄ [B·H,S,d]), padding to block multiples and GQA
head grouping. The kernel compiles for the TPU unless a caller passes
``interpret=True`` (CPU validation). The model calls this through
``attn_core(backend="pallas")``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bhsd


def flash_attention(
    q: jax.Array,  # [B, Sq, H, d]
    k: jax.Array,  # [B, Sk, K, d]
    v: jax.Array,  # [B, Sk, K, d]
    mask=None,  # models.attention.MaskSpec (aligned-positions fast path)
    scale: Optional[float] = None,
    causal: Optional[bool] = None,
    sliding_window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """TPU flash attention; q/k/v may have different head counts (GQA).

    The kernel derives masking from absolute indices, so it serves the
    aligned-positions cases (training, full prefill). Ring-buffer decode
    stays on the XLA core (one-token queries don't benefit from a kernel).
    """
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    group = h // kh
    scale = d**-0.5 if scale is None else scale
    if causal is None:
        causal = mask.causal if mask is not None else True
    if mask is not None and sliding_window == 0:
        sliding_window = mask.sliding_window

    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(sk, 8))
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v

    # [B, S, H, d] → [B·H, S, d] (head-major so GQA index-maps are contiguous)
    qb = qp.transpose(0, 2, 1, 3).reshape(b * h, sq + pad_q, d)
    kb = kp.transpose(0, 2, 1, 3).reshape(b * kh, sk + pad_k, d)
    vb = vp.transpose(0, 2, 1, 3).reshape(b * kh, sk + pad_k, d)

    out = flash_attention_bhsd(
        qb,
        kb,
        vb,
        group=group,
        scale=scale,
        causal=causal,
        sliding_window=sliding_window,
        kv_len=sk,
        block_q=bq,
        block_k=bk,
        interpret=interpret,
    )
    out = out.reshape(b, h, sq + pad_q, d).transpose(0, 2, 1, 3)
    return out[:, :sq] if pad_q else out
