"""Attention: GQA (+qk-norm, partial RoPE, sliding window) and MLA.

Three execution backends for the softmax-attention core:

* ``xla``     — naive einsum attention (reference; smoke tests),
* ``chunked`` — online-softmax over KV chunks via ``lax.scan`` (flash-attention
  recurrence in pure JAX; bounded memory, used for long-context training and
  prefill),
* ``pallas``  — the Pallas TPU kernel in ``repro.kernels.flash_attention``
  (TPU target; validated in interpret mode on CPU).

GQA is computed by broadcasting KV heads to the full query-head count inside
the core (fused by XLA) so every einsum stays sharded over the ``heads``
logical axis regardless of ``n_kv_heads`` divisibility; the KV *cache* stores
only the ``n_kv_heads`` heads (the memory win GQA exists for).

MLA (DeepSeek-V2) implements both the expanded prefill/train form and the
*absorbed* decode form that attends directly over the cached 512-d latent —
the paper-faithful KV-cache reduction.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lsc

from .common import Yarn, apply_rope, dense_init, rms_norm, yarn_mscale

Array = jax.Array

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# masking                                                                      #
# --------------------------------------------------------------------------- #
def make_bias(
    q_pos: Array,  # [B, Sq]
    k_pos: Array,  # [B, Sk]
    causal: bool,
    sliding_window: int = 0,
    k_valid: Optional[Array] = None,  # [B, Sk] bool
) -> Array:
    """Additive attention bias [B, 1, Sq, Sk]."""
    diff = q_pos[:, :, None] - k_pos[:, None, :]  # [B, Sq, Sk]
    ok = jnp.ones_like(diff, dtype=bool)
    if causal:
        ok &= diff >= 0
    if sliding_window > 0:
        ok &= diff < sliding_window
    if k_valid is not None:
        ok &= k_valid[:, None, :]
    return jnp.where(ok, 0.0, NEG_INF)[:, None, :, :].astype(jnp.float32)


# --------------------------------------------------------------------------- #
# softmax-attention cores                                                      #
# --------------------------------------------------------------------------- #
def attn_core_xla(q: Array, k: Array, v: Array, bias: Array, scale: float) -> Array:
    """q [B,Sq,H,dq], k [B,Sk,H,dq], v [B,Sk,H,dv], bias [B,1,Sq,Sk]."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attn_core_chunked(
    q: Array,
    k: Array,
    v: Array,
    mask: "MaskSpec",
    scale: float,
    chunk: int = 1024,
) -> Array:
    """Online-softmax (flash) recurrence over KV chunks; O(Sq·chunk) scores.

    The mask/bias is derived *inside* each chunk step from positions (never
    materializing the [Sq, Sk] bias) — same trick a flash kernel uses.
    """
    b, sq, h, dq = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]
    k_pos = mask.k_pos
    k_valid = mask.k_valid if mask.k_valid is not None else jnp.ones((b, sk), bool)
    if sk % chunk != 0:  # pad KV to a chunk multiple with invalid slots
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)))
        k_valid = jnp.pad(k_valid, ((0, 0), (0, pad)))
        sk += pad
    n_chunks = sk // chunk
    kc = k.reshape(b, n_chunks, chunk, h, dq).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, dv).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    kvc = k_valid.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    q32 = q.astype(jnp.float32)
    q_pos = mask.q_pos

    def step(carry, xs):
        m, l, acc = carry
        k_i, v_i, kp_i, kv_i = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k_i.astype(jnp.float32)) * scale
        diff = q_pos[:, :, None] - kp_i[:, None, :]  # [B, Sq, chunk]
        ok = kv_i[:, None, :]
        if mask.causal:
            ok = ok & (diff >= 0)
        if mask.sliding_window > 0:
            ok = ok & (diff < mask.sliding_window)
        s = jnp.where(ok[:, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_i.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kc, vc, kpc, kvc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(v.dtype)  # [B,Sq,H,dv]


class MaskSpec:
    """Positional mask description (built lazily per chunk / kernel block)."""

    __slots__ = ("q_pos", "k_pos", "causal", "sliding_window", "k_valid")

    def __init__(self, q_pos, k_pos, causal, sliding_window=0, k_valid=None):
        self.q_pos = q_pos
        self.k_pos = k_pos
        self.causal = causal
        self.sliding_window = sliding_window
        self.k_valid = k_valid

    def bias(self) -> Array:
        return make_bias(self.q_pos, self.k_pos, self.causal, self.sliding_window, self.k_valid)


def attn_core(q, k, v, mask: MaskSpec, scale, backend: str = "xla", chunk: int = 1024) -> Array:
    if backend == "chunked":
        return attn_core_chunked(q, k, v, mask, scale, chunk=chunk)
    if backend == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(q, k, v, mask=mask, scale=scale)
    return attn_core_xla(q, k, v, mask.bias(), scale)


def repeat_kv(x: Array, n_rep: int) -> Array:
    """[B,S,K,dh] -> [B,S,K*n_rep,dh] via broadcast (fused by XLA)."""
    if n_rep == 1:
        return x
    b, s, k, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, k, n_rep, d)).reshape(b, s, k * n_rep, d)


# --------------------------------------------------------------------------- #
# GQA attention layer                                                          #
# --------------------------------------------------------------------------- #
class KVCache(NamedTuple):
    k: Array  # [B, Smax, K, dh]  (pre-RoPE'd keys at absolute positions)
    v: Array  # [B, Smax, K, dh]
    pos: Array  # [B, Smax] absolute position of each slot (-1 = empty)
    idx: Array  # [] int32, number of tokens written (ring pointer for SWA)


def init_attention(
    key, n_layers, d_model, n_heads, n_kv_heads, d_head, qk_norm=False, dtype=jnp.float32, std=None
):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (n_layers, d_model, n_heads, d_head), in_axis=1, dtype=dtype, std=std),
        "wk": dense_init(ks[1], (n_layers, d_model, n_kv_heads, d_head), in_axis=1, dtype=dtype, std=std),
        "wv": dense_init(ks[2], (n_layers, d_model, n_kv_heads, d_head), in_axis=1, dtype=dtype, std=std),
        "wo": dense_init(ks[3], (n_layers, n_heads, d_head, d_model), in_axis=1, dtype=dtype, std=std),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((n_layers, d_head), dtype)
        p["k_norm"] = jnp.ones((n_layers, d_head), dtype)
    return p


def attention_logical_axes(qk_norm=False):
    axes = {
        "wq": ("layers", "fsdp", "heads", None),
        "wk": ("layers", "fsdp", "kv_heads", None),
        "wv": ("layers", "fsdp", "kv_heads", None),
        "wo": ("layers", "heads", None, "fsdp"),
    }
    if qk_norm:
        axes["q_norm"] = ("layers", None)
        axes["k_norm"] = ("layers", None)
    return axes


def apply_attention(
    p: dict,
    x: Array,  # [B, S, d]
    positions: Array,  # [B, S]
    *,
    causal: bool = True,
    sliding_window: int = 0,
    rope_theta: float = 10000.0,
    rope_fraction: float = 1.0,
    qk_norm: bool = False,
    norm_eps: float = 1e-5,
    backend: str = "xla",
    chunk: int = 1024,
    cache: Optional[KVCache] = None,
    update_cache: bool = False,
) -> Tuple[Array, Optional[KVCache]]:
    """One attention layer (params already sliced to this layer).

    * train/encoder: ``cache=None, update_cache=False`` — full-sequence attn.
    * prefill: ``cache=empty, update_cache=True`` — full seq, fills cache.
    * decode: ``cache=filled, update_cache=True`` — S==1 step against cache.
    """
    n_heads = p["wq"].shape[-2]
    n_kv = p["wk"].shape[-2]
    d_head = p["wq"].shape[-1]
    scale = d_head**-0.5

    q = lsc(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), ("batch", "seq", "heads", None))
    k = lsc(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), ("batch", "seq", "kv_heads", None))
    v = lsc(jnp.einsum("bsd,dhk->bshk", x, p["wv"]), ("batch", "seq", "kv_heads", None))

    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)

    q = apply_rope(q, positions, rope_theta, rope_fraction)
    k = apply_rope(k, positions, rope_theta, rope_fraction)

    new_cache = None
    if cache is not None:
        s_max = cache.k.shape[1]
        s_in = k.shape[1]
        if update_cache:
            if s_in >= s_max:
                # SWA prefill longer than the window: keep the last s_max
                # tokens, rolled so token t sits at ring slot t % s_max.
                start = cache.idx + s_in - s_max
                shift = jnp.mod(start, s_max)
                ck = jnp.roll(k[:, -s_max:].astype(cache.k.dtype), shift, axis=1)
                cv = jnp.roll(v[:, -s_max:].astype(cache.v.dtype), shift, axis=1)
                cpos = jnp.roll(positions[:, -s_max:], shift, axis=1)
            else:
                # ring-buffer write (slot = idx mod s_max → SWA-safe). Prefill
                # (idx=0) writes at offset 0; decode writes one slot.
                slot = jnp.mod(cache.idx, s_max)
                ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
                cpos = jax.lax.dynamic_update_slice(cache.pos, positions, (0, slot))
            idx = cache.idx + s_in
            new_cache = KVCache(k=ck, v=cv, pos=cpos, idx=idx)
        else:
            new_cache = cache
        if s_in == 1:
            # decode: attend over the cache (ring contents, position-masked)
            k_att, v_att, k_pos = new_cache.k, new_cache.v, new_cache.pos
            mask = MaskSpec(positions, k_pos, causal, sliding_window, k_valid=k_pos >= 0)
        else:
            # prefill: attend over the full in-scope keys (the cache may hold
            # only the trailing window for SWA; early queries need all keys)
            k_att, v_att = k, v
            mask = MaskSpec(positions, positions, causal, sliding_window)
    else:
        k_att, v_att = k, v
        mask = MaskSpec(positions, positions, causal, sliding_window)

    k_full = repeat_kv(k_att, n_heads // n_kv)
    v_full = repeat_kv(v_att, n_heads // n_kv)
    out = attn_core(q, k_full, v_full, mask, scale, backend=backend, chunk=chunk)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return lsc(out, ("batch", "seq", "embed")), new_cache


def init_kv_cache(batch: int, s_max: int, n_kv: int, d_head: int, dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, s_max, n_kv, d_head), dtype),
        v=jnp.zeros((batch, s_max, n_kv, d_head), dtype),
        pos=jnp.full((batch, s_max), -1, jnp.int32),
        idx=jnp.asarray(0, jnp.int32),
    )


def kv_cache_logical_axes() -> KVCache:
    return KVCache(
        k=("batch", "kv_seq", "kv_heads", None),
        v=("batch", "kv_seq", "kv_heads", None),
        pos=("batch", "kv_seq"),
        idx=(),
    )


# --------------------------------------------------------------------------- #
# MLA — multi-head latent attention (DeepSeek-V2)                              #
# --------------------------------------------------------------------------- #
class MLACache(NamedTuple):
    c_kv: Array  # [B, Smax, kv_lora]   — compressed latent
    k_rope: Array  # [B, Smax, rope_dim] — shared rotary key
    pos: Array  # [B, Smax]
    idx: Array


def init_mla(
    key,
    n_layers,
    d_model,
    n_heads,
    kv_lora_rank,
    qk_nope_dim,
    qk_rope_dim,
    v_head_dim,
    dtype=jnp.float32,
    std=None,
):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(
            ks[0], (n_layers, d_model, n_heads, qk_nope_dim + qk_rope_dim), in_axis=1, dtype=dtype, std=std
        ),
        "w_kv_a": dense_init(ks[1], (n_layers, d_model, kv_lora_rank + qk_rope_dim), in_axis=1, dtype=dtype, std=std),
        "kv_norm": jnp.ones((n_layers, kv_lora_rank), dtype),
        "w_kv_b": dense_init(
            ks[2], (n_layers, kv_lora_rank, n_heads, qk_nope_dim + v_head_dim), in_axis=1, dtype=dtype, std=std
        ),
        "wo": dense_init(ks[3], (n_layers, n_heads, v_head_dim, d_model), in_axis=1, dtype=dtype, std=std),
    }


def mla_logical_axes():
    return {
        "wq": ("layers", "fsdp", "heads", None),
        "w_kv_a": ("layers", "fsdp", None),
        "kv_norm": ("layers", None),
        "w_kv_b": ("layers", None, "heads", None),
        "wo": ("layers", "heads", None, "fsdp"),
    }


def mla_softmax_scale(d_qk: int, yarn: Optional[Yarn] = None) -> float:
    """``d_qk**-0.5``, times mscale(factor, mscale_all_dim)² under YaRN."""
    scale = d_qk**-0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def apply_mla(
    p: dict,
    x: Array,
    positions: Array,
    *,
    qk_nope_dim: int,
    qk_rope_dim: int,
    v_head_dim: int,
    rope_theta: float = 10000.0,
    norm_eps: float = 1e-5,
    backend: str = "xla",
    chunk: int = 1024,
    cache: Optional[MLACache] = None,
    update_cache: bool = False,
    yarn: Optional[Yarn] = None,
) -> Tuple[Array, Optional[MLACache]]:
    n_heads = p["wq"].shape[-2]
    kv_lora = p["w_kv_b"].shape[0]  # per-layer slice: [kv_lora, H, nope+v]
    scale = mla_softmax_scale(qk_nope_dim + qk_rope_dim, yarn)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])  # [B,S,H,nope+rope]
    q_nope, q_rope = q[..., :qk_nope_dim], q[..., qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, rope_theta, yarn=yarn)

    kv_a = jnp.einsum("bsd,dk->bsk", x, p["w_kv_a"])  # [B,S,lora+rope]
    c_kv = rms_norm(kv_a[..., :kv_lora], p["kv_norm"], norm_eps)  # [B,S,lora]
    k_rope = apply_rope(kv_a[..., kv_lora:][:, :, None, :], positions, rope_theta, yarn=yarn)[:, :, 0, :]

    is_decode = cache is not None and x.shape[1] == 1

    new_cache = None
    if cache is not None and update_cache:
        # write into the allocated cache at the current offset (prefill writes
        # the whole prefix at slot 0, decode writes one slot)
        slot = jnp.mod(cache.idx, cache.c_kv.shape[1])
        new_cache = MLACache(
            c_kv=jax.lax.dynamic_update_slice(cache.c_kv, c_kv.astype(cache.c_kv.dtype), (0, slot, 0)),
            k_rope=jax.lax.dynamic_update_slice(cache.k_rope, k_rope.astype(cache.k_rope.dtype), (0, slot, 0)),
            pos=jax.lax.dynamic_update_slice(cache.pos, positions, (0, slot)),
            idx=cache.idx + x.shape[1],
        )
    elif cache is not None:
        new_cache = cache

    w_kb = p["w_kv_b"][..., :qk_nope_dim]  # [lora, H, nope]
    w_vb = p["w_kv_b"][..., qk_nope_dim:]  # [lora, H, vdim]

    if is_decode:
        # absorbed decode: attend over the latent cache directly (paper-faithful
        # MLA memory saving — never materialize per-head K/V for the full seq)
        q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, w_kb)  # [B,1,H,lora]
        cc, kr, kpos = new_cache.c_kv, new_cache.k_rope, new_cache.pos
        k_valid = kpos >= 0
        bias = make_bias(positions, kpos, True, 0, k_valid)
        s_lat = jnp.einsum("bshl,bkl->bhsk", q_lat.astype(jnp.float32), cc.astype(jnp.float32))
        s_rope = jnp.einsum("bshr,bkr->bhsk", q_rope.astype(jnp.float32), kr.astype(jnp.float32))
        scores = (s_lat + s_rope) * scale + bias
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhsk,bkl->bshl", probs, cc.astype(jnp.float32))  # [B,1,H,lora]
        out_h = jnp.einsum("bshl,lhv->bshv", ctx, w_vb.astype(jnp.float32)).astype(x.dtype)
    else:
        # expanded train/prefill form
        k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, w_kb)
        value = jnp.einsum("bsl,lhv->bshv", c_kv, w_vb)
        k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (*k_rope.shape[:2], n_heads, qk_rope_dim))
        k_all = jnp.concatenate([k_nope, k_rope_h], axis=-1)
        q_all = jnp.concatenate([q_nope, q_rope], axis=-1)
        q_all = lsc(q_all, ("batch", "seq", "heads", None))
        k_all = lsc(k_all, ("batch", "seq", "heads", None))
        mask = MaskSpec(positions, positions, True, 0)
        out_h = attn_core(q_all, k_all, value, mask, scale, backend=backend, chunk=chunk)

    out = jnp.einsum("bshv,hvd->bsd", out_h, p["wo"])
    return lsc(out, ("batch", "seq", "embed")), new_cache


def init_mla_cache(batch: int, s_max: int, kv_lora: int, rope_dim: int, dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((batch, s_max, kv_lora), dtype),
        k_rope=jnp.zeros((batch, s_max, rope_dim), dtype),
        pos=jnp.full((batch, s_max), -1, jnp.int32),
        idx=jnp.asarray(0, jnp.int32),
    )


def mla_cache_logical_axes() -> MLACache:
    return MLACache(
        c_kv=("batch", "kv_seq", None),
        k_rope=("batch", "kv_seq", None),
        pos=("batch", "kv_seq"),
        idx=(),
    )
