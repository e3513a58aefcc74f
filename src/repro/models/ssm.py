"""Mamba-style selective state-space layer (S6).

Training/prefill uses a parallel associative scan over the diagonal SSM
recurrence (log-depth, TPU-friendly); decode is the O(1)-per-token recurrent
step over carried (conv_state, ssm_state) — the sub-quadratic long-context
path.

Recurrence (per channel c, state n):
    h_t = exp(Δ_t·A)·h_{t-1} + Δ_t·B_t·x_t
    y_t = C_t·h_t + D·x_t
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lsc

from .common import dense_init

Array = jax.Array


class SSMState(NamedTuple):
    conv: Array  # [B, conv_w - 1, d_inner] — rolling conv window
    ssm: Array  # [B, d_inner, n_state]


def init_ssm(
    key,
    n_layers: int,
    d_model: int,
    d_inner: int,
    n_state: int = 16,
    conv_w: int = 4,
    dt_rank: Optional[int] = None,
    dtype=jnp.float32,
) -> dict:
    dt_rank = dt_rank or max(d_model // 16, 1)
    ks = jax.random.split(key, 6)
    # S4D-real initialization for A
    a_init = jnp.broadcast_to(jnp.arange(1, n_state + 1, dtype=jnp.float32), (n_layers, d_inner, n_state))
    return {
        "in_proj": dense_init(ks[0], (n_layers, d_model, 2 * d_inner), in_axis=1, dtype=dtype),
        "conv_w": dense_init(ks[1], (n_layers, conv_w, d_inner), in_axis=1, dtype=dtype),
        "conv_b": jnp.zeros((n_layers, d_inner), dtype),
        "x_proj": dense_init(ks[2], (n_layers, d_inner, dt_rank + 2 * n_state), in_axis=1, dtype=dtype),
        "dt_proj": dense_init(ks[3], (n_layers, dt_rank, d_inner), in_axis=1, dtype=dtype),
        "dt_bias": jnp.zeros((n_layers, d_inner), dtype),
        "A_log": jnp.log(a_init).astype(jnp.float32),
        "D": jnp.ones((n_layers, d_inner), dtype),
        "out_proj": dense_init(ks[4], (n_layers, d_inner, d_model), in_axis=1, dtype=dtype),
    }


def ssm_logical_axes() -> dict:
    return {
        "in_proj": ("layers", "fsdp", "ff"),
        "conv_w": ("layers", None, "ff"),
        "conv_b": ("layers", "ff"),
        "x_proj": ("layers", "ff", None),
        "dt_proj": ("layers", None, "ff"),
        "dt_bias": ("layers", "ff"),
        "A_log": ("layers", "ff", None),
        "D": ("layers", "ff"),
        "out_proj": ("layers", "ff", "fsdp"),
    }


def _ssm_combine(left, right):
    a_l, b_l = left
    a_r, b_r = right
    return a_l * a_r, b_l * a_r + b_r


def _ssm_chunk(h_prev: Array, u, dt, a, b, c) -> Tuple[Array, Array]:
    """One chunk of the diagonal SSM recurrence via associative scan.

    h_prev [B,D,N]; u/dt [B,L,D]; a [D,N]; b/c [B,L,N] → (h_last, y [B,L,D]).
    """
    neg_dta = dt[..., None] * (-a)  # log decay [B,L,D,N]
    da = jnp.exp(neg_dta)
    db = dt[..., None] * b[:, :, None, :] * u[..., None]
    _, h_intra = jax.lax.associative_scan(_ssm_combine, (da, db), axis=1)
    # carry contribution: h_t += (∏_{τ≤t} da_τ) · h_prev
    da_cum = jnp.exp(jnp.cumsum(neg_dta, axis=1))
    h = h_intra + da_cum * h_prev[:, None]
    y = jnp.einsum("bsdn,bsn->bsd", h, c)
    return h[:, -1], y


def _ssm_scan_parallel(
    u: Array, dt: Array, a: Array, b: Array, c: Array, chunk: int = 2048
) -> Tuple[Array, Array]:
    """Chunked parallel scan: associative scan within chunks (log-depth,
    MXU-friendly), exact state carry across chunks — bounds the [B,L,D,N]
    working set to the chunk length. Returns (y [B,S,D], h_last [B,D,N])."""
    bsz, s, d = u.shape
    n = a.shape[-1]
    h0 = jnp.zeros((bsz, d, n), u.dtype)
    if s <= chunk:
        h_last, y = _ssm_chunk(h0, u, dt, a, b, c)
        return y, h_last
    pad = (-s) % chunk
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // chunk

    def split(t):
        return t.reshape(bsz, nc, chunk, *t.shape[2:]).transpose(1, 0, 2, *range(3, t.ndim + 1))

    xs = (split(u), split(dt), split(b), split(c))

    def step(h, x):
        h_new, y_i = _ssm_chunk(h, x[0], x[1], a, x[2], x[3])
        return h_new, y_i

    h, ys = jax.lax.scan(step, h0, xs)
    y = ys.transpose(1, 0, 2, 3).reshape(bsz, s + pad, d)
    return y[:, :s], h


def apply_ssm(
    p: dict,
    x: Array,  # [B, S, d_model]
    *,
    n_state: int,
    conv_w: int = 4,
    chunk: int = 2048,
    state: Optional[SSMState] = None,
    update_state: bool = False,
) -> Tuple[Array, Optional[SSMState]]:
    """Mamba block. ``state`` given & S==1 → recurrent decode step."""
    b, s, _ = x.shape
    d_inner = p["dt_bias"].shape[-1]
    dt_rank = p["dt_proj"].shape[0]

    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xz = lsc(xz, ("batch", "seq", "ff"))
    xi, z = jnp.split(xz, 2, axis=-1)  # [B,S,d_inner] each

    is_decode = state is not None and s == 1
    new_state = None

    if is_decode:
        window = jnp.concatenate([state.conv.astype(jnp.float32), xi.astype(jnp.float32)], axis=1)
        conv_out = jnp.einsum("bwd,wd->bd", window, p["conv_w"].astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
        xc = jax.nn.silu(conv_out)[:, None, :].astype(xi.dtype)  # [B,1,D]
        new_conv = window[:, 1:, :].astype(state.conv.dtype)
    else:
        pad = jnp.zeros((b, conv_w - 1, d_inner), xi.dtype)
        xp = jnp.concatenate([pad, xi], axis=1)  # causal depthwise conv
        idx = jnp.arange(s)[:, None] + jnp.arange(conv_w)[None, :]  # [S, W]
        windows = xp[:, idx, :]  # [B, S, W, D]
        xc = jax.nn.silu(jnp.einsum("bswd,wd->bsd", windows, p["conv_w"]) + p["conv_b"])
        new_conv = xp[:, s : s + conv_w - 1, :] if s >= conv_w - 1 else xp[:, -(conv_w - 1) :, :]

    proj = jnp.einsum("bsd,dk->bsk", xc, p["x_proj"])
    dt_in, bc = proj[..., :dt_rank], proj[..., dt_rank:]
    b_mat, c_mat = bc[..., :n_state], bc[..., n_state:]
    dt = jax.nn.softplus(
        jnp.einsum("bsk,kd->bsd", dt_in, p["dt_proj"]).astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )
    a = jnp.exp(p["A_log"].astype(jnp.float32))  # [D, N], positive

    if is_decode:
        da = jnp.exp(dt[:, 0, :, None] * (-a))  # [B,D,N]
        db = dt[:, 0, :, None] * b_mat[:, 0, None, :].astype(jnp.float32) * xc[:, 0, :, None].astype(jnp.float32)
        h = state.ssm.astype(jnp.float32) * da + db
        y = jnp.einsum("bdn,bn->bd", h, c_mat[:, 0].astype(jnp.float32))[:, None, :].astype(x.dtype)
        h = h.astype(state.ssm.dtype)
        if update_state:
            new_state = SSMState(conv=new_conv, ssm=h)
        else:
            new_state = state
    else:
        y32, h_last = _ssm_scan_parallel(
            xc.astype(jnp.float32),
            dt.astype(jnp.float32),
            a,
            b_mat.astype(jnp.float32),
            c_mat.astype(jnp.float32),
            chunk=chunk,
        )
        y = y32.astype(x.dtype)
        if update_state and state is not None:
            new_state = SSMState(conv=new_conv, ssm=h_last.astype(state.ssm.dtype))

    y = y + xc * p["D"]
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return lsc(out, ("batch", "seq", "embed")), new_state


def init_ssm_state(batch: int, d_inner: int, n_state: int, conv_w: int = 4, dtype=jnp.float32) -> SSMState:
    return SSMState(
        conv=jnp.zeros((batch, conv_w - 1, d_inner), dtype),
        ssm=jnp.zeros((batch, d_inner, n_state), dtype),
    )


def ssm_state_logical_axes() -> SSMState:
    return SSMState(conv=("batch", None, "ff"), ssm=("batch", "ff", None))
