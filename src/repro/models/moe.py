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

The decode path: a call over at most ``MIN_CAPACITY`` tokens (a static shape,
as in a decode step of one or a few sequences) skips the dispatch. A device
loop runs once per held expert that one of its tokens chose, reads that
expert's three matrices, and adds each token's top-k weight times the
expert's FFN; an expert no token chose is not read. The capacity is never
below ``MIN_CAPACITY`` and a token chooses an expert at most once, so at that
size no group can give an expert more choices than it has slots: no choice
is dropped, and the result is the dispatch path's. Under the model's layer
scan the loop reads the segment's whole stacks at (layer, expert), since a
layer's ``[held, ...]`` slice handed to the loop would be copied whole first.
Every longer call, prefill and training among them, takes the dispatch path.
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


#: the least capacity of an expert in a group, in slots
MIN_CAPACITY = 4
#: the held experts' weights, stacked ``[(layers,) held, ...]``
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def decode_sized(tokens: int) -> bool:
    """Whether a call over ``tokens`` tokens takes the decode path: at most the
    least capacity, so that no group can give an expert more choices than it
    has slots."""
    return tokens <= MIN_CAPACITY


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
    out, aux, dropped, _ = apply_moe_counted(
        p, x, top_k, capacity_factor=capacity_factor, group_size=group_size, renormalize=renormalize
    )
    return out, aux, dropped


def apply_moe_counted(
    p: dict,
    x: Array,  # [B, S, d]
    top_k: int,
    *,
    capacity_factor: float = 1.25,
    group_size: int = 256,
    renormalize: bool = True,
    layer=None,
) -> Tuple[Array, Array, Array, Array]:
    """``apply_moe``'s (output, aux_loss, dropped), and the number of held
    experts whose FFN ran: on the decode path the experts chosen, else every
    held expert. ``layer`` (a traced index; a decode-sized call only): ``p``'s
    expert weights are a segment's stacks ``[layers, held, ...]``, and this
    call is that layer of them."""
    b, s, d = x.shape
    e = p["router"].shape[-1]
    tokens = b * s
    g = max(tokens // group_size, 1)
    xg = lsc(x.reshape(g, tokens // g, d), ("expert_group", None, "embed"))
    top_p, top_idx, aux = route(p["router"], xg, top_k, renormalize)

    w = {n: p[n] for n in EXPERT_WEIGHTS}
    if decode_sized(tokens):
        flat = (x.reshape(tokens, d), top_p.reshape(tokens, top_k), top_idx.reshape(tokens, top_k))
        out, ran = chosen_experts(w, *flat, layer)
        dropped = jnp.zeros((), jnp.int32)
    else:
        out, dropped = dispatched_experts(w, xg, top_p, top_idx, e, capacity_factor)
        ran = jnp.asarray(w["w_gate"].shape[0], jnp.int32)
    out = out.reshape(b, s, d)

    if "shared_gate" in p:
        sg = lsc(jnp.einsum("bsd,df->bsf", x, p["shared_gate"]), ("batch", "seq", "ff"))
        su = lsc(jnp.einsum("bsd,df->bsf", x, p["shared_up"]), ("batch", "seq", "ff"))
        out = out + jnp.einsum("bsf,fd->bsd", swiglu(sg, su), p["shared_down"])
    return lsc(out, ("batch", "seq", "embed")), aux, dropped, ran


def route(router: Array, xg: Array, top_k: int, renormalize: bool) -> Tuple[Array, Array, Array]:
    """Routing of token groups ``xg`` [g, sp, d] over every expert, in float32:
    (top-k weights [g, sp, k], their experts [g, sp, k], the Switch-style
    load-balance auxiliary loss)."""
    e = router.shape[-1]
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.int32)  # [g, sp, k, e]
    density = jnp.mean(jnp.sum(onehot, axis=2).astype(jnp.float32), axis=1)  # [g, e] fraction routed
    router_prob = jnp.mean(probs, axis=1)  # [g, e]
    aux = jnp.mean(jnp.sum(density * router_prob, axis=-1)) * (e / top_k)
    return top_p, top_idx, aux


def dispatched_experts(
    w: dict, xg: Array, top_p: Array, top_idx: Array, n_experts: int, capacity_factor: float
) -> Tuple[Array, Array]:
    """The GShard path: every held expert's FFN (``w``: its ``[held, ...]``
    matrices) over its capacity slots, which take each group's choices in
    token order. Returns (routed output [g, sp, d], dropped)."""
    g, sp, _ = xg.shape
    e, top_k = n_experts, top_idx.shape[-1]
    held = w["w_gate"].shape[0]
    capacity = max(int(math.ceil(top_k * sp * capacity_factor / e)), MIN_CAPACITY)

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
    gate = jnp.einsum("egcd,edf->egcf", expert_in, w["w_gate"])
    up = jnp.einsum("egcd,edf->egcf", expert_in, w["w_up"])
    hidden = swiglu(gate, up)
    expert_out = jnp.einsum("egcf,efd->egcd", hidden, w["w_down"])
    expert_out = lsc(expert_out, ("experts", "expert_group", None, "embed"))
    return jnp.einsum("gsec,egcd->gsd", combine.astype(xg.dtype), expert_out), dropped


def chosen_experts(w: dict, xt: Array, top_p: Array, top_idx: Array, layer=None) -> Tuple[Array, Array]:
    """The decode path: a loop over the held experts that some token of ``xt``
    [T, d] chose, each reading its own three matrices once (``w``: ``[held,
    ...]``, or with ``layer`` a segment's ``[layers, held, ...]`` stacks). A
    token's output sums, over its choices of held experts, the top-k weight in
    the compute dtype times that expert's FFN, in float32, as the dispatch
    path's combine does. Returns (routed output [T, d], experts run)."""
    held = w["w_gate"].shape[-3]
    if layer is None:
        w, layer = {n: a[None] for n, a in w.items()}, 0
    chose = jax.nn.one_hot(top_idx, held, dtype=jnp.float32)  # [T, k, held]; an absent expert one-hots to zeros
    weight = jnp.sum(top_p[..., None] * chose, axis=1).astype(xt.dtype)  # [T, held]
    picked = jnp.any(chose > 0.0, axis=(0, 1))
    n_run = jnp.sum(picked, dtype=jnp.int32)
    experts = jnp.nonzero(picked, size=held)[0]  # the chosen ones first, in order

    def matrix(name, ex):
        stack = w[name]
        return jax.lax.dynamic_slice(stack, (layer, ex, 0, 0), (1, 1, *stack.shape[2:]))[0, 0]

    def body(i, acc):
        ex = experts[i]
        hidden = swiglu(xt @ matrix("w_gate", ex), xt @ matrix("w_up", ex))
        y = hidden @ matrix("w_down", ex)
        return acc + weight[:, ex, None].astype(jnp.float32) * y.astype(jnp.float32)

    acc = jax.lax.fori_loop(0, n_run, body, jnp.zeros(xt.shape, jnp.float32))
    return acc.astype(xt.dtype), n_run
