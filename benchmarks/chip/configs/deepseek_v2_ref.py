"""Plain float32 reference of DeepSeek-V2-Lite as its configuration runs it:
one chip's share of an expert-parallel deployment.

It follows DeepSeek-V2 (arXiv:2405.04434; the published ``config.json`` and
modelling code) with the sizes of a configuration's ``arch`` block, in
straightforward ``jax.numpy`` at float32 under ``Precision.HIGHEST``, with no
kernels, cache or batching across requests, and imports nothing of the
program:

* multi-head latent attention in its expanded form: queries from the hidden
  state, keys and values from a normed latent of ``kv_lora_rank``, a rotary
  key shared by every head; YaRN rotary frequencies at every position, and
  the softmax scale multiplied by mscale(factor, mscale_all_dim)²;
* the leading dense layers with a SwiGLU feed-forward;
* then routed experts: softmax over the router's ``n_experts`` outputs, the
  top-k weights as they are unless ``norm_topk_prob``, of which only the
  first ``n_experts_held`` experts are computed here (the share; a choice of
  an absent expert adds nothing), plus the shared experts in full;
* the capacity-bounded dispatch that the configuration states, reproduced.

Rotary pairs are halves of the rope dims, where the checkpoint interleaves
them: a fixed permutation of the rope columns of ``wq`` and ``w_kv_a``,
listed among the configuration's departures.

Weights are drawn from the seed by the same recipe as the program's random
init (split order, truncated-normal draws at 1/sqrt(fan-in) or, where the
configuration gives ``init_std``, at that std for every matrix but the
router and for the embedding; the parameter dtype), so that both start from
identical values. They are held as drawn (bfloat16
for the chip's configuration) and each layer is upcast to float32 as it
runs, so that the whole share and a 16,896-position sequence fit one chip.

``precision="fp8"`` casts both operands of every matrix product to
float8_e4m3fn with a per-tensor scale: the step below the configuration's
bfloat16 compute, used only as the control of the comparison.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# --------------------------------------------------------------------------- #
# weights                                                                      #
# --------------------------------------------------------------------------- #
def vocab_padded(arch: dict) -> int:
    return int(math.ceil(arch["vocab"] / 128) * 128)


def n_held(arch: dict) -> int:
    return arch.get("n_experts_held") or arch["n_experts"]


def _normal(key, shape, fan_in, dtype, std=None):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * (std or 1.0 / math.sqrt(fan_in))
    return w.astype(dtype)


def _ffn(key, L, d, f, dtype, std) -> dict:
    k = jax.random.split(key, 3)
    return {
        "w_gate": _normal(k[0], (L, d, f), d, dtype, std),
        "w_up": _normal(k[1], (L, d, f), d, dtype, std),
        "w_down": _normal(k[2], (L, f, d), f, dtype, std),
    }


def _layers(arch: dict, kind: str, L: int, key, dtype) -> dict:
    d, h, std = arch["d_model"], arch["n_heads"], arch.get("init_std") or None
    lora, nope, rdim, vdim = arch["kv_lora_rank"], arch["qk_nope_dim"], arch["qk_rope_dim"], arch["v_head_dim"]
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 4)
    seg = {
        "norm1": jnp.ones((L, d), dtype),
        "norm2": jnp.ones((L, d), dtype),
        "attn": {
            "wq": _normal(ka[0], (L, d, h, nope + rdim), d, dtype, std),
            "w_kv_a": _normal(ka[1], (L, d, lora + rdim), d, dtype, std),
            "kv_norm": jnp.ones((L, lora), dtype),
            "w_kv_b": _normal(ka[2], (L, lora, h, nope + vdim), lora, dtype, std),
            "wo": _normal(ka[3], (L, h, vdim, d), h, dtype, std),
        },
    }
    if kind == "dense":
        seg["ffn"] = _ffn(ks[1], L, d, arch["d_ff"], dtype, std)
        return seg
    km = jax.random.split(ks[1], 5)
    e, f, held = arch["n_experts"], arch["d_ff_expert"], n_held(arch)
    moe = {
        "router": _normal(km[0], (L, d, e), d, jnp.float32),
        "w_gate": _normal(km[1], (L, held, d, f), d, dtype, std),
        "w_up": _normal(km[2], (L, held, d, f), d, dtype, std),
        "w_down": _normal(km[3], (L, held, f, d), f, dtype, std),
    }
    shared = _ffn(km[4], L, d, arch["n_shared_experts"] * f, dtype, std)
    moe.update({"shared_gate": shared["w_gate"], "shared_up": shared["w_up"], "shared_down": shared["w_down"]})
    seg["moe"] = moe
    return seg


def init_params(arch: dict, key) -> dict:
    """Weights in the program's tree layout (layers stacked on axis 0): the
    dense segment, then the MoE segment."""
    dtype = jnp.dtype(arch.get("param_dtype", "float32"))
    d, dense, std = arch["d_model"], arch["first_k_dense"], arch.get("init_std") or None
    keys = jax.random.split(key, 2 + 3)
    return {
        "embed": (jax.random.normal(keys[0], (vocab_padded(arch), d), jnp.float32) * (std or 0.02)).astype(dtype),
        "final_norm": jnp.ones((d,), dtype),
        "segments": [
            _layers(arch, "dense", dense, keys[1], dtype),
            _layers(arch, "moe", arch["n_layers"] - dense, keys[2], dtype),
        ],
        "lm_head": _normal(keys[-2], (d, vocab_padded(arch)), d, dtype, std),
    }


# --------------------------------------------------------------------------- #
# layers                                                                       #
# --------------------------------------------------------------------------- #
def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def mm(eq: str, a, b, precision: str = "f32"):
    """A matrix product at float32 (or, for the control, float8 inputs)."""
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(arch: dict, dim: int) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies for ``dim`` rotary dims: the plain
    ``base^(-2i/dim)`` below the correction range, divided by ``factor`` above
    it, blended on a linear ramp across it."""
    base, factor = arch["rope_theta"], arch.get("rope_scaling_factor", 1.0)
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1.0:
        return plain
    orig = arch["rope_original_max_pos"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(arch["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(arch["rope_beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def softmax_scale(arch: dict) -> float:
    scale = (arch["qk_nope_dim"] + arch["qk_rope_dim"]) ** -0.5
    factor, m_all = arch.get("rope_scaling_factor", 1.0), arch.get("rope_mscale_all_dim", 0.0)
    return scale * _mscale(factor, m_all) ** 2 if m_all else scale


def rope(arch: dict, x, positions):
    """Rotate-half rotary embedding over the last dim of x [B, S, H, r]."""
    r = x.shape[-1]
    factor = arch.get("rope_scaling_factor", 1.0)
    ratio = _mscale(factor, arch.get("rope_mscale", 1.0)) / _mscale(factor, arch.get("rope_mscale_all_dim", 0.0))
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(arch, r), jnp.float32)
    cos, sin = jnp.cos(ang)[..., None, :] * ratio, jnp.sin(ang)[..., None, :] * ratio
    x1, x2 = x[..., : r // 2], x[..., r // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mla(arch, p, x, positions, precision, q_block):
    """Causal latent attention of x [B, S, d] in its expanded form; the
    softmax is exact per query, computed in blocks of ``q_block`` queries
    against every key."""
    h, nope, lora = arch["n_heads"], arch["qk_nope_dim"], arch["kv_lora_rank"]
    b, s, _ = x.shape
    q = mm("bsd,dhk->bshk", x, p["wq"], precision)
    q = jnp.concatenate([q[..., :nope], rope(arch, q[..., nope:], positions)], axis=-1)
    kv_a = mm("bsd,dk->bsk", x, p["w_kv_a"], precision)
    latent = rms_norm(kv_a[..., :lora], p["kv_norm"], arch["norm_eps"])
    k_rope = rope(arch, kv_a[..., lora:][:, :, None, :], positions)
    kv = mm("bsl,lhk->bshk", latent, p["w_kv_b"], precision)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, k_rope.shape[-1]))], axis=-1)
    v = kv[..., nope:]
    scale = softmax_scale(arch)

    n_blocks = -(-s // q_block)
    pad = n_blocks * q_block - s
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, n_blocks, q_block, h, -1)
    pb = jnp.pad(positions, ((0, 0), (0, pad))).reshape(b, n_blocks, q_block)

    def block(args):
        qi, pi = args
        scores = mm("bqhd,bkhd->bhqk", qi, k, precision) * scale
        allowed = pi[:, :, None] >= positions[:, None, :]
        probs = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", probs, v, precision)

    out = jax.lax.map(block, (qb.transpose(1, 0, 2, 3, 4), pb.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_blocks * q_block, h, -1)[:, :s]
    return mm("bshv,hvd->bsd", out, p["wo"], precision)


def ffn(gate_w, up_w, down_w, x, precision):
    gate = mm("bsd,df->bsf", x, gate_w, precision)
    up = mm("bsd,df->bsf", x, up_w, precision)
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, down_w, precision)


def moe(arch, p, x, group_start, capacity, precision):
    """Routed and shared experts of x [B, S, d].

    The router scores all ``n_experts``; each token takes its top-k by
    softmax probability, weighted by those probabilities as they are (or
    renormalised over the k with ``norm_topk_prob``). Tokens are taken in
    order (row-major, then the k choices of each token); a choice whose
    expert already holds ``capacity[t]`` earlier choices of the same group
    (``group_start[t]`` is the first token of t's group) is dropped. Of the
    choices kept, those of experts 0 ... n_experts_held - 1 are computed here.
    """
    b, s, d = x.shape
    e, k, held = arch["n_experts"], arch["top_k"], n_held(arch)
    xt = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xt, p["router"], precision=HIGHEST)
    top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if arch.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    choice = jax.nn.one_hot(top_i, e, dtype=jnp.int32).reshape(b * s * k, e)
    before = jnp.cumsum(choice, axis=0) - choice  # choices of each expert made earlier
    base = jnp.concatenate([jnp.zeros((1, e), jnp.int32), jnp.cumsum(choice, axis=0)])[group_start * k]
    pos = jnp.sum((before.reshape(b * s, k, e) - base[:, None, :]) * choice.reshape(b * s, k, e), axis=-1)
    w = jnp.where((pos < capacity[:, None]) & (top_i < held), top_p, 0.0)
    gate = mm("td,edf->tef", xt, p["w_gate"], precision)
    up = mm("td,edf->tef", xt, p["w_up"], precision)
    y = mm("tef,efd->ted", jax.nn.silu(gate) * up, p["w_down"], precision)
    picked = jnp.take_along_axis(y, jnp.minimum(top_i, held - 1)[:, :, None], axis=1)  # [T, k, d]
    routed = jnp.sum(picked * w[:, :, None], axis=1).reshape(b, s, d)
    return routed + ffn(p["shared_gate"], p["shared_up"], p["shared_down"], x, precision)


def hidden(arch, params, tokens, *, precision="f32", q_block=512, moe_groups=None):
    """The last layer's output [B, S, d] for tokens [B, S], before the final
    norm; each layer's weights are upcast to float32 as it runs."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["embed"][tokens].astype(jnp.float32)
    eps = arch["norm_eps"]

    def layer(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        x = x + mla(arch, p["attn"], rms_norm(x, p["norm1"], eps), positions, precision, q_block)
        h = rms_norm(x, p["norm2"], eps)
        if "moe" in p:
            return x + moe(arch, p["moe"], h, *moe_groups, precision), None
        return x + ffn(p["ffn"]["w_gate"], p["ffn"]["w_up"], p["ffn"]["w_down"], h, precision), None

    for seg in params["segments"]:
        x, _ = jax.lax.scan(layer, x, seg)
    return x


def head(arch, params, x, precision="f32"):
    """Logits [B, S, V] over the real vocabulary of hidden states x."""
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), arch["norm_eps"])
    return mm("bsd,dv->bsv", x, params["lm_head"][:, : arch["vocab"]].astype(jnp.float32), precision)


def forward(arch, params, tokens, *, precision="f32", q_block=512, moe_groups=None):
    """Logits [B, S, V] over the real vocabulary for tokens [B, S]."""
    return head(arch, params, hidden(arch, params, tokens, precision=precision, q_block=q_block, moe_groups=moe_groups), precision)


def moe_groups_for_batch(arch: dict, n_tokens: int) -> Tuple[np.ndarray, np.ndarray]:
    """The dispatch groups of one forward call over ``n_tokens`` tokens, as the
    configuration states them: ``g = max(n // group_size, 1)`` groups of
    ``n // g`` tokens, capacity ``max(ceil(k · (n // g) · factor / E), 4)``
    with E the router's ``n_experts``, held here or not."""
    g = max(n_tokens // arch["moe_group_size"], 1)
    sp = n_tokens // g
    cap = max(int(math.ceil(arch["top_k"] * sp * arch["capacity_factor"] / arch["n_experts"])), 4)
    starts = (np.arange(n_tokens) // sp) * sp
    return starts.astype(np.int32), np.full(n_tokens, cap, np.int32)


# --------------------------------------------------------------------------- #
# serving                                                                      #
# --------------------------------------------------------------------------- #
def served_logits_fn(arch: dict, length: int, n_served: int, precision="f32"):
    """A jitted function (params, tokens [1, length], prompt_len, group_start,
    capacity) -> the logits [n_served, V] that predicted each served token:
    rows prompt_len - 1 ... prompt_len + n_served - 2 of the sequence prompt +
    served tokens, padded at the end to ``length``. The head runs on those
    rows only."""

    def fn(params, tokens, prompt_len, group_start, capacity):
        x = hidden(arch, params, tokens, precision=precision, moe_groups=(group_start, capacity))
        rows = jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, n_served, axis=1)
        return head(arch, params, rows, precision)[0]

    return jax.jit(fn)


def serving_inputs(arch: dict, prompt: np.ndarray, served: Sequence[int], length: int):
    """Tokens and dispatch groups of one served request as the configuration
    states them: the prompt in one prefill call, then each served token in a
    decode call of its own (a group of one token, which never overflows)."""
    p_len = int(prompt.shape[-1])
    seq = np.concatenate([np.asarray(prompt).reshape(-1), np.asarray(served[:-1], np.int32)])
    tokens = np.zeros((1, length), np.int32)
    tokens[0, : seq.size] = seq
    group_start = np.arange(length, dtype=np.int32)
    capacity = np.full(length, 1 << 30, np.int32)
    gs, cap = moe_groups_for_batch(arch, p_len)
    group_start[:p_len] = gs
    capacity[:p_len] = cap
    return tokens, p_len, group_start, capacity
