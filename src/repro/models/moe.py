"""Mixture-of-Experts FFN: top-k routing, GShard-style grouped dispatch.

Tokens are split into groups of ``group_size``; per group, top-k routing
assigns each token to up to ``top_k`` experts with a per-(group, expert)
capacity ``C = ceil(top_k · group_size · capacity_factor / n_experts)``.
Dispatch/combine are einsums over a [G, S', E, C] mask — the classic GShard
formulation, chosen because it shards cleanly on TPU meshes: groups over the
``data``(+``pod``) axes, experts over the ``model`` axis, with XLA inserting
the expert-parallel all-to-alls. ``group_size`` bounds the dispatch tensor to
``tokens × top_k × capacity_factor × group_size`` elements.

Shared experts (DeepSeek-MoE) are a dense FFN added to the routed output.
An auxiliary load-balancing loss (Switch-style) is returned for training.

An expert share (expert parallelism): the layer may hold the weights of only
the first of the experts it routes over. Routing, the top-k weights and each
expert's capacity are computed over all of them, as for the whole layer; the
layer then dispatches to and combines from the experts it holds, and a choice
of an absent expert adds nothing here.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import lsc

from .common import dense_init, swiglu

Array = jax.Array


def init_moe(
    key,
    n_layers: int,
    d_model: int,
    n_experts: int,
    d_ff_expert: int,
    n_shared: int = 0,
    dtype=jnp.float32,
    n_held: int = 0,
    std=None,
) -> dict:
    """``n_held`` (0: all) experts' weights, with a router over all
    ``n_experts``; ``std`` (None: fan-in) is the experts' draw, not the
    router's."""
    n_held = n_held or n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (n_layers, d_model, n_experts), in_axis=1, dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (n_layers, n_held, d_model, d_ff_expert), in_axis=2, dtype=dtype, std=std),
        "w_up": dense_init(ks[2], (n_layers, n_held, d_model, d_ff_expert), in_axis=2, dtype=dtype, std=std),
        "w_down": dense_init(ks[3], (n_layers, n_held, d_ff_expert, d_model), in_axis=2, dtype=dtype, std=std),
    }
    if n_shared > 0:
        kg, ku, kd = jax.random.split(ks[4], 3)
        d_sh = n_shared * d_ff_expert
        p["shared_gate"] = dense_init(kg, (n_layers, d_model, d_sh), in_axis=1, dtype=dtype, std=std)
        p["shared_up"] = dense_init(ku, (n_layers, d_model, d_sh), in_axis=1, dtype=dtype, std=std)
        p["shared_down"] = dense_init(kd, (n_layers, d_sh, d_model), in_axis=1, dtype=dtype, std=std)
    return p


def moe_logical_axes(n_shared: int = 0) -> dict:
    axes = {
        "router": ("layers", "fsdp", None),
        "w_gate": ("layers", "experts", "fsdp", None),
        "w_up": ("layers", "experts", "fsdp", None),
        "w_down": ("layers", "experts", None, "fsdp"),
    }
    if n_shared > 0:
        axes["shared_gate"] = ("layers", "fsdp", "ff")
        axes["shared_up"] = ("layers", "fsdp", "ff")
        axes["shared_down"] = ("layers", "ff", "fsdp")
    return axes


def apply_moe(
    p: dict,
    x: Array,  # [B, S, d]
    top_k: int,
    *,
    capacity_factor: float = 1.25,
    group_size: int = 256,
    router_noise: float = 0.0,
    renormalize: bool = True,
) -> Tuple[Array, Array, Array]:
    """Returns (output [B,S,d], aux_loss scalar, dropped): ``dropped`` counts
    the (token, expert) choices of held experts that the capacity bound
    dropped. The held experts are the first ones, as many as ``p["w_gate"]``
    has; ``renormalize`` rescales the top-k weights to sum to one (else they
    stay the softmax's over all experts)."""
    b, s, d = x.shape
    e = p["router"].shape[-1]
    held = p["w_gate"].shape[0]
    tokens = b * s
    g = max(tokens // group_size, 1)
    sp = tokens // g  # tokens per group
    xg = x.reshape(g, sp, d)
    xg = lsc(xg, ("expert_group", None, "embed"))

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])  # fp32 routing
    probs = jax.nn.softmax(logits, axis=-1)

    top_p, top_idx = jax.lax.top_k(probs, top_k)  # [g, sp, k]
    if renormalize:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    capacity = int(math.ceil(top_k * sp * capacity_factor / e))
    capacity = max(capacity, 4)

    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.int32)  # [g, sp, k, e]
    flat = onehot.reshape(g, sp * top_k, e)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat  # exclusive cumsum
    pos = jnp.sum(pos_in_expert * flat, axis=-1).reshape(g, sp, top_k)
    fits = pos < capacity

    # the choices of held experts (an absent expert's index one-hots to zeros)
    mine = onehot.astype(jnp.float32) if held == e else jax.nn.one_hot(top_idx, held, dtype=jnp.float32)
    dropped = jnp.sum(~fits & (top_idx < held), dtype=jnp.int32)

    # combine weights [g, sp, held, capacity]; dispatch mask is its support
    combine = jnp.einsum(
        "gske,gskc->gsec",
        (jnp.where(fits, top_p, 0.0))[..., None] * mine,
        jax.nn.one_hot(jnp.where(fits, pos, capacity), capacity, dtype=jnp.float32),
    )
    combine = lsc(combine, ("expert_group", None, "experts", None))
    dispatch = (combine > 0.0).astype(xg.dtype)

    # dispatch → expert FFN → combine
    expert_in = jnp.einsum("gsec,gsd->egcd", dispatch, xg)
    expert_in = lsc(expert_in, ("experts", "expert_group", None, "embed"))
    gate = jnp.einsum("egcd,edf->egcf", expert_in, p["w_gate"])
    up = jnp.einsum("egcd,edf->egcf", expert_in, p["w_up"])
    hidden = swiglu(gate, up)
    expert_out = jnp.einsum("egcf,efd->egcd", hidden, p["w_down"])
    expert_out = lsc(expert_out, ("experts", "expert_group", None, "embed"))
    out = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), expert_out)

    # Switch-style load-balance auxiliary loss
    density = jnp.mean(jnp.sum(onehot, axis=2).astype(jnp.float32), axis=1)  # [g, e] fraction routed
    router_prob = jnp.mean(probs, axis=1)  # [g, e]
    aux = jnp.mean(jnp.sum(density * router_prob, axis=-1)) * (e / top_k)

    out = out.reshape(b, s, d)
    if "shared_gate" in p:
        sg = lsc(jnp.einsum("bsd,df->bsf", x, p["shared_gate"]), ("batch", "seq", "ff"))
        su = lsc(jnp.einsum("bsd,df->bsf", x, p["shared_up"]), ("batch", "seq", "ff"))
        out = out + jnp.einsum("bsf,fd->bsd", swiglu(sg, su), p["shared_down"])
    return lsc(out, ("batch", "seq", "embed")), aux, dropped
