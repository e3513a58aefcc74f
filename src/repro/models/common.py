"""Common building blocks: initializers, norms, RoPE, activations.

Everything is functional: params are plain dicts of ``jnp`` arrays, layers are
``init_*``/``apply`` function pairs. Per-layer parameters are *stacked* along a
leading layer axis so the model can ``lax.scan`` over layers (small HLO, fast
compiles, natural remat boundary).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lsc

Array = jax.Array


# --------------------------------------------------------------------------- #
# init                                                                         #
# --------------------------------------------------------------------------- #
def dense_init(
    key: Array, shape: Sequence[int], in_axis: int = -2, dtype=jnp.float32, std: Optional[float] = None
) -> Array:
    """Truncated-normal fan-in init (LeCun-style, the MaxText default); a
    given ``std`` replaces 1/sqrt(fan-in)."""
    if std is None:
        std = 1.0 / math.sqrt(shape[in_axis])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key: Array, shape: Sequence[int], dtype=jnp.float32, std: float = 0.02) -> Array:
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# --------------------------------------------------------------------------- #
# norms                                                                        #
# --------------------------------------------------------------------------- #
def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: Array, scale: Array, bias: Optional[Array] = None, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


# --------------------------------------------------------------------------- #
# rotary position embeddings                                                   #
# --------------------------------------------------------------------------- #
class Yarn(NamedTuple):
    """YaRN rotary scaling as DeepSeek-V2 defines it (its ``rope_scaling``)."""

    factor: float
    original_max_pos: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(yarn: Yarn, d_rot: int, theta: float) -> Tuple[int, int]:
    """The rotary dims between which YaRN's ramp runs: those that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_max_pos`` positions."""

    def dim(rotations: float) -> float:
        return d_rot * math.log(yarn.original_max_pos / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    return max(math.floor(dim(yarn.beta_fast)), 0), min(math.ceil(dim(yarn.beta_slow)), d_rot - 1)


def rope_frequencies(d_rot: int, theta: float = 10000.0, yarn: Optional[Yarn] = None) -> Array:
    freqs = 1.0 / (theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot))
    if yarn is None:
        return freqs
    # below the ramp the frequencies stay, above it they are divided by the
    # factor, and in between they blend linearly
    low, high = yarn_correction_range(yarn, d_rot, theta)
    ramp = jnp.clip((jnp.arange(d_rot // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(
    x: Array,
    positions: Array,
    theta: float = 10000.0,
    fraction: float = 1.0,
    yarn: Optional[Yarn] = None,
) -> Array:
    """Apply RoPE to the last dim of ``x`` [..., seq, heads, d_head].

    ``fraction`` < 1 rotates only the first ``fraction·d_head`` dims (ChatGLM's
    2D/partial RoPE); the remainder passes through unrotated. ``yarn`` blends
    the frequencies and scales cos and sin by YaRN's mscale ratio.
    """
    d_head = x.shape[-1]
    d_rot = int(d_head * fraction)
    d_rot -= d_rot % 2
    if d_rot == 0:
        return x
    x_rot, x_pass = x[..., :d_rot], x[..., d_rot:]
    freqs = rope_frequencies(d_rot, theta, yarn)  # [d_rot/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, d_rot/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        ratio = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if ratio != 1.0:
            cos, sin = cos * ratio, sin * ratio
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1) if d_rot < d_head else rotated.astype(x.dtype)
    return out


# --------------------------------------------------------------------------- #
# activations / FFN                                                            #
# --------------------------------------------------------------------------- #
def swiglu(gate: Array, up: Array) -> Array:
    return jax.nn.silu(gate) * up


def init_ffn(
    key: Array, n_layers: int, d_model: int, d_ff: int, dtype=jnp.float32, std: Optional[float] = None
) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (n_layers, d_model, d_ff), in_axis=-2, dtype=dtype, std=std),
        "w_up": dense_init(k2, (n_layers, d_model, d_ff), in_axis=-2, dtype=dtype, std=std),
        "w_down": dense_init(k3, (n_layers, d_ff, d_model), in_axis=-2, dtype=dtype, std=std),
    }


def apply_ffn(p: dict, x: Array) -> Array:
    """SwiGLU FFN. ``p`` holds per-layer (unstacked) weights."""
    gate = lsc(jnp.einsum("bsd,df->bsf", x, p["w_gate"]), ("batch", "seq", "ff"))
    up = lsc(jnp.einsum("bsd,df->bsf", x, p["w_up"]), ("batch", "seq", "ff"))
    hidden = swiglu(gate, up)
    out = jnp.einsum("bsf,fd->bsd", hidden, p["w_down"])
    return lsc(out, ("batch", "seq", "embed"))


def ffn_logical_axes() -> dict:
    # d_model is the FSDP axis, as in attention, MoE and SSM weights: the FFN
    # holds most of a dense layer's parameters
    return {
        "w_gate": ("layers", "fsdp", "ff"),
        "w_up": ("layers", "fsdp", "ff"),
        "w_down": ("layers", "ff", "fsdp"),
    }


# --------------------------------------------------------------------------- #
# misc                                                                         #
# --------------------------------------------------------------------------- #
def take_layer(params, i: int):
    """Slice layer ``i`` out of a stacked param tree."""
    return jax.tree_util.tree_map(lambda a: a[i], params)


def cross_entropy_loss(logits: Array, labels: Array, mask: Optional[Array] = None) -> Array:
    """Token-mean softmax cross entropy, fp32 accumulation."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
