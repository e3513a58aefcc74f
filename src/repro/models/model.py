"""Unified model: every assigned architecture is an ``ArchConfig`` instance.

One functional Model covers six families (dense / moe / audio / hybrid / ssm /
vlm) by composing blocks into homogeneous *segments* that are scanned with
``lax.scan`` (stacked per-layer params → small HLO, fast compiles,
natural remat boundary):

* ``dense``  — pre-norm attention (GQA/MLA variants) + SwiGLU FFN
* ``moe``    — attention + top-k MoE FFN (+ shared experts)
* ``hymba``  — parallel attention & Mamba heads fused per block + FFN
* ``mlstm``/``slstm`` — xLSTM blocks (no separate FFN; d_ff = 0)

Modality frontends are stubs per the assignment: audio provides precomputed
frame embeddings, VLM provides precomputed patch embeddings spliced ahead of
the token sequence.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import lsc

from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .common import Yarn, cross_entropy_loss, dense_init, embed_init, init_ffn, apply_ffn, ffn_logical_axes, rms_norm

Array = jax.Array


# --------------------------------------------------------------------------- #
# configuration                                                                #
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | audio | hybrid | ssm | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0  # 0 → d_model // n_heads
    # attention
    attn_type: str = "gqa"  # gqa | mla
    qk_norm: bool = False
    rope_fraction: float = 1.0
    rope_theta: float = 10000.0
    # YaRN rotary scaling of MLA (DeepSeek-V2's ``rope_scaling``); factor 1: none
    rope_scaling_factor: float = 1.0
    rope_original_max_pos: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    causal: bool = True
    sliding_window: int = 0  # 0 = full attention
    # MLA (DeepSeek-V2)
    kv_lora_rank: int = 0
    qk_rope_dim: int = 64
    qk_nope_dim: int = 128
    v_head_dim: int = 128
    # MoE
    n_experts: int = 0  # the router's width: experts routed over
    n_experts_held: int = 0  # experts whose weights are here (the first ones); 0 = all
    norm_topk_prob: bool = True  # renormalise the top-k weights to sum to one
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    first_k_dense: int = 0
    capacity_factor: float = 1.25
    moe_group_size: int = 256
    aux_loss_coef: float = 0.01
    # SSM (mamba / hymba)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_chunk: int = 2048
    # xLSTM
    slstm_every: int = 0  # every k-th block is sLSTM (0 = none)
    mlstm_expand: int = 2
    # frontend stubs
    frontend: str = "none"  # none | audio_stub | vision_stub
    frontend_dim: int = 0
    n_vision_tokens: int = 0
    # numerics / execution
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    attn_backend: str = "xla"  # xla | chunked | pallas
    attn_chunk: int = 1024
    mlstm_chunk: int = 256
    remat: bool = False
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # random init: 0 draws each matrix at 1/sqrt(fan-in); > 0 draws every
    # matrix but the router, and the embedding, at this std (a checkpoint's
    # ``initializer_range``)
    init_std: float = 0.0

    def __post_init__(self):
        if self.rope_scaling_factor != 1.0 and self.attn_type != "mla":
            raise ValueError("rope_scaling_factor (YaRN) is implemented for MLA only")
        if self.init_std and any(kind not in ("dense", "moe") for kind, _ in self.segments()):
            raise ValueError("init_std covers dense and MoE layers only")

    # -- derived ----------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def yarn(self) -> Optional[Yarn]:
        if self.rope_scaling_factor == 1.0:
            return None
        return Yarn(
            self.rope_scaling_factor, self.rope_original_max_pos, self.rope_beta_fast,
            self.rope_beta_slow, self.rope_mscale, self.rope_mscale_all_dim,
        )

    @property
    def vocab_padded(self) -> int:
        return int(math.ceil(self.vocab / 128) * 128)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def mlstm_inner(self) -> int:
        return self.mlstm_expand * self.d_model

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def segments(self) -> List[Tuple[str, int]]:
        """Homogeneous (block_kind, n_layers) runs, scanned independently."""
        if self.family in ("dense", "audio", "vlm"):
            return [("dense", self.n_layers)]
        if self.family == "moe":
            segs = []
            if self.first_k_dense > 0:
                segs.append(("dense", self.first_k_dense))
            segs.append(("moe", self.n_layers - self.first_k_dense))
            return segs
        if self.family == "hybrid":
            return [("hymba", self.n_layers)]
        if self.family == "ssm":
            if self.slstm_every <= 0:
                return [("mlstm", self.n_layers)]
            segs: List[Tuple[str, int]] = []
            run = 0
            for i in range(self.n_layers):
                if (i + 1) % self.slstm_every == 0:
                    if run:
                        segs.append(("mlstm", run))
                        run = 0
                    segs.append(("slstm", 1))
                else:
                    run += 1
            if run:
                segs.append(("mlstm", run))
            return segs
        raise ValueError(f"unknown family {self.family!r}")

    def active_params_per_layer(self) -> float:
        """Parameter count touched per token per layer (MoE counts top-k)."""
        d, dh = self.d_model, self.head_dim
        attn = d * dh * (self.n_heads * 2 + self.n_kv_heads * 2)
        if self.attn_type == "mla":
            d_qk = self.qk_nope_dim + self.qk_rope_dim
            attn = (
                d * self.n_heads * d_qk
                + d * (self.kv_lora_rank + self.qk_rope_dim)
                + self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                + self.n_heads * self.v_head_dim * d
            )
        if self.family == "moe":
            ff = 3 * d * self.d_ff_expert * (self.top_k + self.n_shared_experts)
        elif self.family == "ssm":
            di = self.mlstm_inner
            return d * 2 * di + 3 * di * di + di * d  # mLSTM block approx
        else:
            ff = 3 * d * self.d_ff
        if self.family == "hybrid":
            di = self.d_inner
            ff += 2 * d * di + di * d  # mamba branch
        return attn + ff

    def total_params(self) -> float:
        """Approximate total parameter count (embedding included)."""
        d = self.d_model
        per_layer = 0.0
        for kind, count in self.segments():
            if kind == "dense":
                dh = self.head_dim
                attn = d * dh * (self.n_heads * 2 + self.n_kv_heads * 2)
                per_layer += count * (attn + 3 * d * self.d_ff)
            elif kind == "moe":
                dh = self.head_dim
                attn = d * dh * (self.n_heads * 2 + self.n_kv_heads * 2)
                if self.attn_type == "mla":
                    attn = (
                        d * self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
                        + d * (self.kv_lora_rank + self.qk_rope_dim)
                        + self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
                        + self.n_heads * self.v_head_dim * d
                    )
                ff = 3 * d * self.d_ff_expert * ((self.n_experts_held or self.n_experts) + self.n_shared_experts)
                per_layer += count * (attn + ff + d * self.n_experts)
            elif kind == "hymba":
                dh = self.head_dim
                attn = d * dh * (self.n_heads * 2 + self.n_kv_heads * 2)
                di = self.d_inner
                mamba = 2 * d * di + di * d + di * (d // 16 + 2 * self.ssm_state)
                per_layer += count * (attn + mamba + 3 * d * self.d_ff)
            elif kind == "mlstm":
                di = self.mlstm_inner
                per_layer += count * (2 * d * di + 3 * di * di + di * d)
            elif kind == "slstm":
                per_layer += count * (4 * d * d + 4 * d * (d // self.n_heads) + d * d)
        embed = self.vocab_padded * d * (1 if self.tie_embeddings else 2)
        return per_layer + embed


# --------------------------------------------------------------------------- #
# per-segment parameter init                                                   #
# --------------------------------------------------------------------------- #
def _init_segment(cfg: ArchConfig, kind: str, count: int, key) -> dict:
    d, dtype, std = cfg.d_model, cfg.param_dtype, cfg.init_std or None
    ks = jax.random.split(key, 6)
    p: dict = {"norm1": jnp.ones((count, d), dtype)}
    if kind in ("dense", "moe", "hymba"):
        if cfg.attn_type == "mla":
            p["attn"] = attn_mod.init_mla(
                ks[0], count, d, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                dtype, std,
            )
        else:
            p["attn"] = attn_mod.init_attention(
                ks[0], count, d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.qk_norm, dtype, std
            )
        p["norm2"] = jnp.ones((count, d), dtype)
    if kind == "dense":
        p["ffn"] = init_ffn(ks[1], count, d, cfg.d_ff, dtype, std)
    elif kind == "moe":
        p["moe"] = moe_mod.init_moe(
            ks[1], count, d, cfg.n_experts, cfg.d_ff_expert, cfg.n_shared_experts, dtype, cfg.n_experts_held, std
        )
    elif kind == "hymba":
        p["ssm"] = ssm_mod.init_ssm(ks[2], count, d, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv, dtype=dtype)
        p["ffn"] = init_ffn(ks[1], count, d, cfg.d_ff, dtype)
        p["attn_out_norm"] = jnp.ones((count, d), dtype)
        p["ssm_out_norm"] = jnp.ones((count, d), dtype)
    elif kind == "mlstm":
        p["mlstm"] = xlstm_mod.init_mlstm(ks[3], count, d, cfg.mlstm_inner, cfg.n_heads, dtype=dtype)
    elif kind == "slstm":
        p["slstm"] = xlstm_mod.init_slstm(ks[4], count, d, cfg.n_heads, dtype)
    return p


def _segment_logical_axes(cfg: ArchConfig, kind: str) -> dict:
    axes: dict = {"norm1": ("layers", None)}
    if kind in ("dense", "moe", "hymba"):
        axes["attn"] = attn_mod.mla_logical_axes() if cfg.attn_type == "mla" else attn_mod.attention_logical_axes(cfg.qk_norm)
        axes["norm2"] = ("layers", None)
    if kind == "dense":
        axes["ffn"] = ffn_logical_axes()
    elif kind == "moe":
        axes["moe"] = moe_mod.moe_logical_axes(cfg.n_shared_experts)
    elif kind == "hymba":
        axes["ssm"] = ssm_mod.ssm_logical_axes()
        axes["ffn"] = ffn_logical_axes()
        axes["attn_out_norm"] = ("layers", None)
        axes["ssm_out_norm"] = ("layers", None)
    elif kind == "mlstm":
        axes["mlstm"] = xlstm_mod.mlstm_logical_axes()
    elif kind == "slstm":
        axes["slstm"] = xlstm_mod.slstm_logical_axes()
    return axes


def init_params(cfg: ArchConfig, key) -> dict:
    keys = jax.random.split(key, len(cfg.segments()) + 3)
    params: dict = {
        "embed": embed_init(keys[0], (cfg.vocab_padded, cfg.d_model), cfg.param_dtype, cfg.init_std or 0.02),
        "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "segments": [
            _init_segment(cfg, kind, count, keys[i + 1]) for i, (kind, count) in enumerate(cfg.segments())
        ],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[-2], (cfg.d_model, cfg.vocab_padded), dtype=cfg.param_dtype, std=cfg.init_std or None
        )
    if cfg.frontend == "audio_stub":
        params["frontend_proj"] = dense_init(
            keys[-1], (cfg.frontend_dim, cfg.d_model), dtype=cfg.param_dtype, std=cfg.init_std or None
        )
    return params


def param_logical_axes(cfg: ArchConfig) -> dict:
    axes: dict = {
        "embed": ("vocab", "fsdp"),
        "final_norm": (None,),
        "segments": [_segment_logical_axes(cfg, kind) for kind, _ in cfg.segments()],
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("fsdp", "vocab")
    if cfg.frontend == "audio_stub":
        axes["frontend_proj"] = (None, "fsdp")
    return axes


# --------------------------------------------------------------------------- #
# block application                                                            #
# --------------------------------------------------------------------------- #
def _apply_attention(cfg: ArchConfig, p_attn: dict, x, positions, cache, update_cache):
    if cfg.attn_type == "mla":
        return attn_mod.apply_mla(
            p_attn,
            x,
            positions,
            qk_nope_dim=cfg.qk_nope_dim,
            qk_rope_dim=cfg.qk_rope_dim,
            v_head_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps,
            backend=cfg.attn_backend,
            chunk=cfg.attn_chunk,
            cache=cache,
            update_cache=update_cache,
            yarn=cfg.yarn,
        )
    return attn_mod.apply_attention(
        p_attn,
        x,
        positions,
        causal=cfg.causal,
        sliding_window=cfg.sliding_window,
        rope_theta=cfg.rope_theta,
        rope_fraction=cfg.rope_fraction,
        qk_norm=cfg.qk_norm,
        norm_eps=cfg.norm_eps,
        backend=cfg.attn_backend,
        chunk=cfg.attn_chunk,
        cache=cache,
        update_cache=update_cache,
    )


def _apply_block(cfg: ArchConfig, kind: str, p: dict, x, positions, cache, update_cache, layer=None):
    """One layer. Returns (x, aux_loss, counts, new_cache); ``counts`` is
    (dropped, ran): the expert choices that the capacity bound dropped and the
    held experts whose FFN ran (both 0 outside MoE). ``layer``: as
    ``moe.apply_moe_counted`` takes it."""
    aux = jnp.zeros((), jnp.float32)
    counts = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    if kind in ("dense", "moe", "hymba"):
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if kind == "hymba":
            attn_cache = cache[0] if cache is not None else None
            a_out, new_attn_cache = _apply_attention(cfg, p["attn"], h, positions, attn_cache, update_cache)
            s_out, new_ssm_state = ssm_mod.apply_ssm(
                p["ssm"],
                h,
                n_state=cfg.ssm_state,
                conv_w=cfg.ssm_conv,
                chunk=cfg.ssm_chunk,
                state=cache[1] if cache is not None else None,
                update_state=update_cache,
            )
            fused = 0.5 * (rms_norm(a_out, p["attn_out_norm"], cfg.norm_eps) + rms_norm(s_out, p["ssm_out_norm"], cfg.norm_eps))
            x = x + fused
            new_cache = (new_attn_cache, new_ssm_state) if cache is not None else None
            h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
            x = x + apply_ffn(p["ffn"], h2)
            return x, aux, counts, new_cache
        a_out, new_cache = _apply_attention(cfg, p["attn"], h, positions, cache, update_cache)
        x = x + a_out
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if kind == "dense":
            x = x + apply_ffn(p["ffn"], h2)
        else:
            m_out, aux, dropped, ran = moe_mod.apply_moe_counted(
                p["moe"],
                h2,
                cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                group_size=cfg.moe_group_size,
                renormalize=cfg.norm_topk_prob,
                layer=layer,
            )
            counts = (dropped, ran)
            x = x + m_out
        return x, aux, counts, new_cache
    if kind == "mlstm":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        state, conv_state = cache if cache is not None else (None, None)
        out, new_state, new_conv = xlstm_mod.apply_mlstm(
            p["mlstm"],
            h,
            n_heads=cfg.n_heads,
            chunk=cfg.mlstm_chunk,
            state=state,
            update_state=update_cache,
            conv_state=conv_state,
        )
        new_cache = (new_state, new_conv) if cache is not None else None
        return x + out, aux, counts, new_cache
    if kind == "slstm":
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        out, new_state = _apply_slstm_maybe_sharded(cfg, p["slstm"], h, cache, update_cache)
        return x + out, aux, counts, new_state if cache is not None else None
    raise ValueError(kind)


def _apply_slstm_maybe_sharded(cfg: ArchConfig, p_slstm: dict, h, cache, update_cache):
    """sLSTM cell, batch-local under ``shard_map`` when a mesh is active.

    §Perf xlstm iteration X1b: the time recurrence's backward pass reduces
    partial weight gradients across the data axis *every timestep* under
    plain pjit (measured ~39 MB/token/layer of all-reduce). Running the cell
    inside ``shard_map`` over the batch axes makes each device's recurrence
    fully local; shard_map AD then inserts ONE gradient psum per layer at the
    boundary — a ~S× reduction of the collective term.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import active_mesh, batch_axes

    mesh = active_mesh()
    axes = batch_axes()

    def run(pp, hh, st):
        return xlstm_mod.apply_slstm(pp, hh, n_heads=cfg.n_heads, state=st, update_state=update_cache)

    if mesh is None or not axes or h.shape[0] % int(np.prod([mesh.shape[a] for a in axes])) != 0:
        return run(p_slstm, h, cache)

    bspec = P(tuple(axes) if len(axes) > 1 else axes[0])
    state_specs = jax.tree_util.tree_map(lambda _: bspec, cache)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), bspec, state_specs),
        out_specs=(bspec, state_specs if cache is not None else None),
        check_vma=False,
    )
    def sharded(pp, hh, st):
        from repro.distributed.sharding import manual_region

        with manual_region():
            out, new_state = run(pp, hh, st)
        return (out, new_state) if cache is not None else (out, None)

    return sharded(p_slstm, h, cache)


#: segment leaves that stay float32 when the rest go to the compute dtype
#: (their modules upcast internally)
_KEEP_F32 = frozenset({"A_log", "w_if", "b_if", "router"})
#: top-level leaves that ``forward`` reads only through a cast to the compute
#: dtype (``final_norm`` is not one: ``rms_norm`` scales in float32)
_CAST_TOP = ("embed", "lm_head", "frontend_proj")


def _cast_segment(cfg: ArchConfig, p_seg: dict) -> dict:
    """Every float32 leaf of a segment not in ``_KEEP_F32`` in the compute
    dtype; a leaf that arrives in another dtype is left as it is."""

    def cast_leaf(path, a):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if a.dtype == jnp.float32 and name not in _KEEP_F32:
            return a.astype(cfg.compute_dtype)
        return a

    return jax.tree_util.tree_map_with_path(cast_leaf, p_seg)


def cast_for_compute(cfg: ArchConfig, params: dict) -> dict:
    """``params`` with exactly the casts that ``forward`` applies made ahead of
    it: ``forward`` on the result computes the same bits and casts nothing
    more. A leaf that needs no cast is the caller's own array, uncopied.

    For weights that stay fixed across many calls (serving), so that each call
    reads the compute-dtype copy instead of converting the float32 tree again.
    """
    out = dict(params)
    out["segments"] = [_cast_segment(cfg, p_seg) for p_seg in params["segments"]]
    for name in _CAST_TOP:
        if name in out and out[name].dtype == jnp.float32:
            out[name] = out[name].astype(cfg.compute_dtype)
    return out


def _scan_segment(cfg: ArchConfig, kind: str, p_seg: dict, x, positions, cache_seg, update_cache):
    """Scan a homogeneous segment of layers; caches are stacked on axis 0.
    Returns (x, aux_loss, counts, new_caches), aux and counts summed."""

    # Cast params to compute dtype BEFORE the layer scan (§Perf: the per-layer
    # FSDP all-gather then moves bf16, not fp32 — halves weight-gather traffic).
    # A tree from ``cast_for_compute`` arrives cast, and this casts nothing.
    p_seg = _cast_segment(cfg, p_seg)
    stacks = None
    if kind == "moe" and moe_mod.decode_sized(x.shape[0] * x.shape[1]):
        # the experts' stacks stay whole, out of the scan, and the expert loop
        # reads a chosen expert at (layer, expert): handed a layer's slice, it
        # would copy every held expert of the layer first
        moe_p = dict(p_seg["moe"])
        stacks = {n: moe_p.pop(n) for n in moe_mod.EXPERT_WEIGHTS}
        p_seg = {**p_seg, "moe": moe_p}

    def body(carry, xs):
        x_in, aux_in, counts_in, layer = carry
        p_layer, cache_layer = xs
        if stacks is not None:
            p_layer = {**p_layer, "moe": {**p_layer["moe"], **stacks}}
        x_out, aux, counts, new_cache = _apply_block(
            cfg, kind, p_layer, x_in, positions, cache_layer, update_cache, layer if stacks is not None else None
        )
        counts = tuple(a + b for a, b in zip(counts_in, counts))
        return (x_out, aux_in + aux, counts, layer + 1), new_cache

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)

    zero = jnp.zeros((), jnp.int32)
    carry = (x, jnp.zeros((), jnp.float32), (zero, zero), zero)
    carry, new_caches = jax.lax.scan(body, carry, (p_seg, cache_seg))
    return (*carry[:3], new_caches)


# --------------------------------------------------------------------------- #
# forward / loss                                                               #
# --------------------------------------------------------------------------- #
def embed_inputs(cfg: ArchConfig, params: dict, batch: Dict[str, Array]) -> Tuple[Array, Array]:
    """Returns (x [B,S,d], positions [B,S])."""
    if cfg.frontend == "audio_stub":
        frames = batch["frames"].astype(cfg.compute_dtype)  # [B,T,frontend_dim]
        x = jnp.einsum("btf,fd->btd", frames, params["frontend_proj"].astype(cfg.compute_dtype))
        b, s, _ = x.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        return lsc(x, ("batch", "seq", "embed")), positions
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.frontend == "vision_stub" and "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(cfg.compute_dtype)  # [B,Nv,d]
        x = jnp.concatenate([vis, x], axis=1)
    b, s, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return lsc(x, ("batch", "seq", "embed")), positions


def forward_layers(
    cfg: ArchConfig,
    params: dict,
    batch: Dict[str, Array],
    caches: Optional[List[Any]] = None,
    update_cache: bool = False,
) -> Tuple[Array, Array, Array, Optional[List[Any]]]:
    """Every layer, without the final norm and head. Returns (x [B,S,d],
    aux_loss, (dropped expert choices, held experts run), new_caches)."""
    x, positions = embed_inputs(cfg, params, batch)
    aux_total = jnp.zeros((), jnp.float32)
    counts_total = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    new_caches: List[Any] = []
    for i, (kind, _count) in enumerate(cfg.segments()):
        cache_seg = caches[i] if caches is not None else None
        x, aux, counts, new_cache_seg = _scan_segment(
            cfg, kind, params["segments"][i], x, positions, cache_seg, update_cache
        )
        aux_total = aux_total + aux
        counts_total = tuple(a + b for a, b in zip(counts_total, counts))
        new_caches.append(new_cache_seg)
    return x, aux_total, counts_total, (new_caches if caches is not None else None)


def apply_head(cfg: ArchConfig, params: dict, x: Array) -> Array:
    """The final norm and the output head: logits [B,S,V_pad] of x [B,S,d]."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    return lsc(logits, ("batch", "seq", "vocab"))


def forward(
    cfg: ArchConfig,
    params: dict,
    batch: Dict[str, Array],
    caches: Optional[List[Any]] = None,
    update_cache: bool = False,
) -> Tuple[Array, Array, Optional[List[Any]]]:
    """Returns (logits [B,S,V_pad], aux_loss, new_caches)."""
    x, aux, _, new_caches = forward_layers(cfg, params, batch, caches, update_cache)
    return apply_head(cfg, params, x), aux, new_caches


def mask_padded_vocab(cfg: ArchConfig, logits: Array) -> Array:
    """Exclude padded vocab slots from the softmax (additive -inf mask)."""
    if cfg.vocab_padded == cfg.vocab:
        return logits
    neg = jnp.full((cfg.vocab_padded - cfg.vocab,), -1e30, logits.dtype)
    return logits + jnp.concatenate([jnp.zeros((cfg.vocab,), logits.dtype), neg])


def loss_fn(cfg: ArchConfig, params: dict, batch: Dict[str, Array]) -> Tuple[Array, Dict[str, Array]]:
    logits, aux, _ = forward(cfg, params, batch)
    logits = mask_padded_vocab(cfg, logits)
    if cfg.family == "audio":
        labels = batch["labels"]  # [B,T] frame targets
        mask = batch.get("loss_mask")
        ce = cross_entropy_loss(logits, labels, mask)
    else:
        tokens = batch["tokens"]
        labels = batch.get("labels")
        if labels is None:  # causal LM: next-token prediction
            labels = tokens[:, 1:]
            logits_shift = logits[:, :-1]
        else:
            logits_shift = logits
        if cfg.frontend == "vision_stub" and "vision_embeds" in batch:
            # logits cover [vision; text]; predict text tokens only
            nv = batch["vision_embeds"].shape[1]
            logits_shift = logits[:, nv - 1 : -1]
            labels = tokens
        mask = batch.get("loss_mask")
        ce = cross_entropy_loss(logits_shift, labels, mask)
    loss = ce + cfg.aux_loss_coef * aux
    return loss, {"ce": ce, "aux": aux, "loss": loss}


# --------------------------------------------------------------------------- #
# caches                                                                       #
# --------------------------------------------------------------------------- #
def _stack_cache(make_one, count: int):
    one = make_one()
    return jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (count, *a.shape)).copy(), one)


def init_caches(cfg: ArchConfig, batch_size: int, max_seq: int, dtype=jnp.bfloat16) -> List[Any]:
    """Per-segment stacked decode caches sized for ``max_seq``."""
    caches: List[Any] = []
    window = min(max_seq, cfg.sliding_window) if cfg.sliding_window > 0 else max_seq
    for kind, count in cfg.segments():
        if kind in ("dense", "moe"):
            if cfg.attn_type == "mla":
                mk = lambda: attn_mod.init_mla_cache(batch_size, max_seq, cfg.kv_lora_rank, cfg.qk_rope_dim, dtype)
            else:
                mk = lambda: attn_mod.init_kv_cache(batch_size, window, cfg.n_kv_heads, cfg.head_dim, dtype)
            caches.append(_stack_cache(mk, count))
        elif kind == "hymba":
            def mk():
                return (
                    attn_mod.init_kv_cache(batch_size, window, cfg.n_kv_heads, cfg.head_dim, dtype),
                    ssm_mod.init_ssm_state(batch_size, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv, jnp.float32),
                )

            caches.append(_stack_cache(mk, count))
        elif kind == "mlstm":
            def mk():
                return (
                    xlstm_mod.init_mlstm_state(batch_size, cfg.n_heads, cfg.mlstm_inner // cfg.n_heads),
                    jnp.zeros((batch_size, 3, cfg.mlstm_inner), jnp.float32),  # conv state (w-1=3)
                )

            caches.append(_stack_cache(mk, count))
        elif kind == "slstm":
            caches.append(_stack_cache(lambda: xlstm_mod.init_slstm_state(batch_size, cfg.d_model), count))
    return caches


def cache_logical_axes(cfg: ArchConfig) -> List[Any]:
    axes: List[Any] = []

    def stackd(tree):
        return jax.tree_util.tree_map(
            lambda ax: ("layers", *ax), tree, is_leaf=lambda v: isinstance(v, tuple) and all(isinstance(e, (str, type(None))) for e in v)
        )

    for kind, _count in cfg.segments():
        if kind in ("dense", "moe"):
            tree = attn_mod.mla_cache_logical_axes() if cfg.attn_type == "mla" else attn_mod.kv_cache_logical_axes()
            axes.append(stackd(tree))
        elif kind == "hymba":
            axes.append(stackd((attn_mod.kv_cache_logical_axes(), ssm_mod.ssm_state_logical_axes())))
        elif kind == "mlstm":
            axes.append(
                stackd(
                    (
                        xlstm_mod.MLSTMState(c=("batch", None, "ff", None), n=("batch", None, "ff"), m=("batch", None)),
                        ("batch", None, "ff"),
                    )
                )
            )
        elif kind == "slstm":
            axes.append(stackd(xlstm_mod.SLSTMState(c=("batch", None), n=("batch", None), h=("batch", None), m=("batch", None))))
    return axes
