"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory).

mLSTM uses the *chunkwise-parallel* formulation: the sequence is processed in
chunks; within a chunk the quadratic parallel form runs (MXU-friendly), and an
exactly-stabilized (C, n, m) state is carried across chunks — so training,
32k prefill and O(1)-state decode all share one code path. This is the TPU
adaptation of the paper's CUDA kernels: chunk size is chosen so the intra-chunk
score matrix tiles into VMEM.

sLSTM keeps true sequential recurrence (per-head block-diagonal recurrent
mixing) via ``lax.scan`` — it is inherently serial by design.

Recurrences (per head):
  mLSTM: C_t = f_t·C_{t-1} + i_t·v_t k_tᵀ,  n_t = f_t·n_{t-1} + i_t·k_t,
         h_t = C_tᵀ q_t / max(|n_tᵀ q_t|, 1)        (exp-gating, stabilized by m)
  sLSTM: c_t = f_t·c_{t-1} + i_t·z_t,  n_t = f_t·n_{t-1} + i_t,
         h_t = o_t · c_t / n_t
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lsc

from .common import dense_init, rms_norm

Array = jax.Array


# --------------------------------------------------------------------------- #
# mLSTM                                                                        #
# --------------------------------------------------------------------------- #
class MLSTMState(NamedTuple):
    c: Array  # [B, H, dh, dh] stabilized matrix memory
    n: Array  # [B, H, dh]
    m: Array  # [B, H] log-stabilizer


def init_mlstm(key, n_layers, d_model, d_inner, n_heads, conv_w: int = 4, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    return {
        "up_proj": dense_init(ks[0], (n_layers, d_model, 2 * d_inner), in_axis=1, dtype=dtype),
        "conv_w": dense_init(ks[1], (n_layers, conv_w, d_inner), in_axis=1, dtype=dtype),
        "conv_b": jnp.zeros((n_layers, d_inner), dtype),
        "wq": dense_init(ks[2], (n_layers, d_inner, d_inner), in_axis=1, dtype=dtype),
        "wk": dense_init(ks[3], (n_layers, d_inner, d_inner), in_axis=1, dtype=dtype),
        "wv": dense_init(ks[4], (n_layers, d_inner, d_inner), in_axis=1, dtype=dtype),
        "w_if": dense_init(ks[5], (n_layers, d_inner, 2), in_axis=1, dtype=jnp.float32),
        "b_if": jnp.zeros((n_layers, 2), jnp.float32),
        "gn_scale": jnp.ones((n_layers, d_inner), dtype),
        "down_proj": dense_init(ks[6], (n_layers, d_inner, d_model), in_axis=1, dtype=dtype),
    }


def mlstm_logical_axes() -> dict:
    return {
        "up_proj": ("layers", "fsdp", "ff"),
        "conv_w": ("layers", None, "ff"),
        "conv_b": ("layers", "ff"),
        "wq": ("layers", "ff", None),
        "wk": ("layers", "ff", None),
        "wv": ("layers", "ff", None),
        "w_if": ("layers", "ff", None),
        "b_if": ("layers", None),
        "gn_scale": ("layers", "ff"),
        "down_proj": ("layers", "ff", "fsdp"),
    }


def _mlstm_chunk(carry, inp, scale):
    """Process one chunk. carry=(C,n,m); inp q,k,v [B,H,L,dh], li/lf [B,H,L]."""
    c_prev, n_prev, m_prev = carry
    q, k, v, li, lf = inp
    b, h, l, dh = q.shape
    lf_cum = jnp.cumsum(lf, axis=-1)  # inclusive: decay 0..i

    # intra-chunk log decay matrix: D[i,j] = lf_cum[i] - lf_cum[j] + li[j], j<=i
    d_log = lf_cum[..., :, None] - lf_cum[..., None, :] + li[..., None, :]
    causal = jnp.tril(jnp.ones((l, l), bool))
    d_log = jnp.where(causal, d_log, -jnp.inf)

    # stabilizer per query: max(inter-state decay, intra max)
    m_inter = lf_cum + m_prev[..., None]  # [B,H,L]
    m_i = jnp.maximum(m_inter, jnp.max(d_log, axis=-1))
    m_i = jnp.maximum(m_i, 0.0)  # keep denominator's exp(-m) ≤ 1

    d_mat = jnp.exp(d_log - m_i[..., None])  # [B,H,L,L]
    s_intra = jnp.einsum("bhld,bhmd->bhlm", q, k) * scale * d_mat

    w_inter = jnp.exp(m_inter - m_i)  # [B,H,L]
    h_inter = jnp.einsum("bhld,bhde->bhle", q, c_prev) * w_inter[..., None] * scale
    num = jnp.einsum("bhlm,bhmd->bhld", s_intra, v) + h_inter

    # n_i^T q_i: inter part via carried n; intra part = Σ_j D_ij (k_j·q_i)·scale
    n_inter = jnp.einsum("bhld,bhd->bhl", q, n_prev) * w_inter * scale
    n_intra = jnp.einsum("bhlm,bhmd,bhld->bhl", d_mat, k, q) * scale
    denom = jnp.maximum(jnp.abs(n_intra + n_inter), jnp.exp(-m_i))
    h_out = num / denom[..., None]

    # end-of-chunk state
    lf_tot = lf_cum[..., -1]  # [B,H]
    m_next = jnp.maximum(m_prev + lf_tot, jnp.max(lf_tot[..., None] - lf_cum + li, axis=-1))
    w_old = jnp.exp(m_prev + lf_tot - m_next)  # [B,H]
    w_new = jnp.exp(lf_tot[..., None] - lf_cum + li - m_next[..., None])  # [B,H,L]
    c_next = c_prev * w_old[..., None, None] + jnp.einsum("bhl,bhld,bhle->bhde", w_new, k, v)
    n_next = n_prev * w_old[..., None] + jnp.einsum("bhl,bhld->bhd", w_new, k)
    return (c_next, n_next, m_next), h_out


def mlstm_core(
    q: Array, k: Array, v: Array, log_i: Array, log_f: Array, state: Optional[MLSTMState], chunk: int = 256
) -> Tuple[Array, MLSTMState]:
    """q,k,v [B,H,S,dh]; log gates [B,H,S]. Returns (h [B,H,S,dh], state)."""
    b, h, s, dh = q.shape
    scale = dh**-0.5
    if state is None:
        state = MLSTMState(
            c=jnp.zeros((b, h, dh, dh), jnp.float32),
            n=jnp.zeros((b, h, dh), jnp.float32),
            m=jnp.zeros((b, h), jnp.float32),
        )
    carry = (state.c, state.n, state.m)
    q, k, v = q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    log_i, log_f = log_i.astype(jnp.float32), log_f.astype(jnp.float32)
    if s <= chunk:
        carry, h_out = _mlstm_chunk(carry, (q, k, v, log_i, log_f), scale)
    else:
        pad = (-s) % chunk
        if pad:  # pad with identity steps: i=-inf (no write), f=0 decay→keep
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            log_i = jnp.pad(log_i, ((0, 0), (0, 0), (0, pad)), constant_values=-1e30)
            log_f = jnp.pad(log_f, ((0, 0), (0, 0), (0, pad)))
        nc = (s + pad) // chunk

        def step(cry, xs):
            return _mlstm_chunk(cry, xs, scale)

        xs = tuple(
            a.reshape(b, h, nc, chunk, *a.shape[3:]).transpose(2, 0, 1, 3, *range(4, a.ndim + 1))
            for a in (q, k, v)
        ) + tuple(a.reshape(b, h, nc, chunk).transpose(2, 0, 1, 3) for a in (log_i, log_f))
        carry, h_chunks = jax.lax.scan(step, carry, xs)
        h_out = h_chunks.transpose(1, 2, 0, 3, 4).reshape(b, h, s + pad, dh)[:, :, :s]
    return h_out, MLSTMState(c=carry[0], n=carry[1], m=carry[2])


def apply_mlstm(
    p: dict,
    x: Array,  # [B,S,d_model]
    *,
    n_heads: int,
    conv_w: int = 4,
    chunk: int = 256,
    state: Optional[MLSTMState] = None,
    update_state: bool = False,
    conv_state: Optional[Array] = None,
) -> Tuple[Array, Optional[MLSTMState], Optional[Array]]:
    b, s, _ = x.shape
    d_inner = p["conv_b"].shape[-1]
    dh = d_inner // n_heads

    xz = jnp.einsum("bsd,de->bse", x, p["up_proj"])
    xz = lsc(xz, ("batch", "seq", "ff"))
    xi, z = jnp.split(xz, 2, axis=-1)

    # causal depthwise conv (q/k path)
    if state is not None and s == 1 and conv_state is not None:
        window = jnp.concatenate([conv_state, xi], axis=1)
        xc = jax.nn.silu(jnp.einsum("bwd,wd->bd", window, p["conv_w"]) + p["conv_b"])[:, None, :]
        new_conv = window[:, 1:, :]
    else:
        padc = jnp.zeros((b, conv_w - 1, d_inner), xi.dtype)
        xp = jnp.concatenate([padc, xi], axis=1)
        idx = jnp.arange(s)[:, None] + jnp.arange(conv_w)[None, :]
        windows = xp[:, idx, :]
        xc = jax.nn.silu(jnp.einsum("bswd,wd->bsd", windows, p["conv_w"]) + p["conv_b"])
        new_conv = xp[:, -(conv_w - 1) :, :] if conv_w > 1 else None

    def heads(t):
        return t.reshape(b, s, n_heads, dh).transpose(0, 2, 1, 3)  # [B,H,S,dh]

    q = heads(jnp.einsum("bsd,de->bse", xc, p["wq"]))
    k = heads(jnp.einsum("bsd,de->bse", xc, p["wk"]))
    v = heads(jnp.einsum("bsd,de->bse", xi, p["wv"]))

    gates = jnp.einsum("bsd,dg->bsg", xc.astype(jnp.float32), p["w_if"]) + p["b_if"]
    log_i = gates[..., 0][:, None, :].repeat(n_heads, axis=1)  # [B,H,S]
    log_f = jax.nn.log_sigmoid(gates[..., 1])[:, None, :].repeat(n_heads, axis=1)

    h_out, new_state = mlstm_core(q, k, v, log_i, log_f, state, chunk=chunk)
    h_out = h_out.transpose(0, 2, 1, 3).reshape(b, s, d_inner).astype(x.dtype)
    h_out = rms_norm(h_out, p["gn_scale"])  # per-channel GN stand-in
    h_out = h_out * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", h_out, p["down_proj"])
    if not update_state:
        new_state, new_conv = state, conv_state
    return lsc(out, ("batch", "seq", "embed")), new_state, new_conv


def init_mlstm_state(batch: int, n_heads: int, dh: int) -> MLSTMState:
    return MLSTMState(
        c=jnp.zeros((batch, n_heads, dh, dh), jnp.float32),
        n=jnp.zeros((batch, n_heads, dh), jnp.float32),
        m=jnp.zeros((batch, n_heads), jnp.float32),
    )


# --------------------------------------------------------------------------- #
# sLSTM                                                                        #
# --------------------------------------------------------------------------- #
class SLSTMState(NamedTuple):
    c: Array  # [B, d]
    n: Array  # [B, d]
    h: Array  # [B, d]
    m: Array  # [B, d]


def init_slstm(key, n_layers, d_model, n_heads, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    dh = d_model // n_heads
    return {
        "w_gates": dense_init(ks[0], (n_layers, d_model, 4 * d_model), in_axis=1, dtype=dtype),
        "r_gates": dense_init(ks[1], (n_layers, n_heads, dh, 4 * dh), in_axis=2, dtype=dtype),
        "b_gates": jnp.zeros((n_layers, 4 * d_model), dtype),
        "gn_scale": jnp.ones((n_layers, d_model), dtype),
        "out_proj": dense_init(ks[2], (n_layers, d_model, d_model), in_axis=1, dtype=dtype),
    }


def slstm_logical_axes() -> dict:
    # REPLICATED weights (§Perf xlstm iteration 1): the time recurrence reads
    # its weights every timestep; FSDP-sharded storage would all-gather ~20 MB
    # × S × layers per step (~175 GB/layer measured) for a ~5M-param/layer
    # saving. Replication removes the gathers entirely.
    return {
        "w_gates": ("layers", None, None),
        "r_gates": ("layers", None, None, None),
        "b_gates": ("layers", None),
        "gn_scale": ("layers", None),
        "out_proj": ("layers", None, None),
    }


def _slstm_step(p, n_heads, state: SLSTMState, x_t: Array) -> Tuple[SLSTMState, Array]:
    """One timestep. x_t [B, d]."""
    b, d = x_t.shape
    dh = d // n_heads
    wx = jnp.einsum("bd,dg->bg", x_t.astype(jnp.float32), p["w_gates"].astype(jnp.float32))
    h_heads = state.h.reshape(b, n_heads, dh)
    rh = jnp.einsum("bhd,hdg->bhg", h_heads, p["r_gates"].astype(jnp.float32)).reshape(b, 4 * d)
    pre = wx + rh + p["b_gates"].astype(jnp.float32)
    z_pre, i_pre, f_pre, o_pre = jnp.split(pre, 4, axis=-1)
    z = jnp.tanh(z_pre)
    o = jax.nn.sigmoid(o_pre)
    log_f = jax.nn.log_sigmoid(f_pre)
    m_new = jnp.maximum(log_f + state.m, i_pre)
    i_g = jnp.exp(i_pre - m_new)
    f_g = jnp.exp(log_f + state.m - m_new)
    c = f_g * state.c + i_g * z
    n = jnp.maximum(f_g * state.n + i_g, 1e-6)
    h = o * (c / n)
    return SLSTMState(c=c, n=n, h=h, m=m_new), h


def apply_slstm(
    p: dict,
    x: Array,  # [B,S,d]
    *,
    n_heads: int,
    state: Optional[SLSTMState] = None,
    update_state: bool = False,
) -> Tuple[Array, Optional[SLSTMState]]:
    b, s, d = x.shape
    if state is None:
        state = init_slstm_state(b, d)
    st32 = SLSTMState(*(a.astype(jnp.float32) for a in state))

    if s == 1:
        new_state, h = _slstm_step(p, n_heads, st32, x[:, 0])
        hs = h[:, None, :]
    else:

        def step(carry, x_t):
            return _slstm_step(p, n_heads, carry, x_t)

        new_state, hs = jax.lax.scan(step, st32, x.transpose(1, 0, 2))
        hs = hs.transpose(1, 0, 2)

    hs = rms_norm(hs.astype(x.dtype), p["gn_scale"])
    out = jnp.einsum("bsd,de->bse", hs, p["out_proj"])
    if not update_state:
        new_state = state
    return lsc(out, ("batch", "seq", "embed")), new_state


def init_slstm_state(batch: int, d: int) -> SLSTMState:
    z = jnp.zeros((batch, d), jnp.float32)
    return SLSTMState(c=z, n=z, h=z, m=z)
