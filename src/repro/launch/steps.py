"""Train / serve step builders with logical-axis sharding.

``build_train_step`` returns a jit-able ``(state, batch) → (state, metrics)``
with microbatch gradient accumulation (``lax.scan``), global-norm clipping and
a fused AdamW update. ``build_prefill_step``/``build_decode_step`` return the
serving-side functions operating on stacked per-segment caches.

``make_*_shardings`` translate the model's logical-axis trees into
``NamedSharding`` trees for a given mesh — the glue between model code and
``jax.jit(in_shardings=…)``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import DEFAULT_RULES, logical_to_spec, sharding_rules
from repro.models import model as model_lib
from repro.models.model import ArchConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update

PyTree = Any

#: per-arch logical-rule overrides (divisibility: heads % model_axis etc.)
ARCH_RULES: Dict[str, Dict[str, Any]] = {
    "hymba-1.5b": {"heads": None, "kv_heads": None},  # 25 heads don't split by 16
    "xlstm-350m": {"heads": None, "kv_heads": None},  # 4 heads; inner dim shards via "ff"
}

#: kv heads are replicated under TP by default (Megatron-style) — most assigned
#: archs have n_kv < 16. The decode KV cache shards its *sequence* dim instead.
BASE_RULES = {"kv_heads": None}


def rules_for(cfg: ArchConfig, *, decode: bool = False, batch_size: Optional[int] = None, mesh: Optional[Mesh] = None):
    rules = dict(BASE_RULES)
    rules.update(ARCH_RULES.get(cfg.name, {}))
    if decode:
        rules["kv_seq"] = "model"  # sequence-parallel KV cache (flash-decoding style)
    if batch_size is not None and mesh is not None:
        dp = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp *= mesh.shape[ax]
        if batch_size % dp != 0:  # e.g. batch 1 over a wider data axis
            rules["batch"] = None
            rules["expert_group"] = None
    return rules


def _tree_shardings(mesh: Mesh, axes_tree: PyTree, rules: Dict[str, Any]) -> PyTree:
    def is_axes(v):
        return isinstance(v, tuple) and all(e is None or isinstance(e, str) for e in v)

    with sharding_rules(mesh, rules):
        return jax.tree_util.tree_map(
            lambda ax: NamedSharding(mesh, logical_to_spec(ax)), axes_tree, is_leaf=is_axes
        )


# --------------------------------------------------------------------------- #
# sharding trees                                                               #
# --------------------------------------------------------------------------- #
def make_param_shardings(cfg: ArchConfig, mesh: Mesh, rules: Optional[Dict[str, Any]] = None) -> PyTree:
    return _tree_shardings(mesh, model_lib.param_logical_axes(cfg), rules or rules_for(cfg))


def make_state_shardings(cfg: ArchConfig, mesh: Mesh, rules: Optional[Dict[str, Any]] = None) -> Dict[str, PyTree]:
    p = make_param_shardings(cfg, mesh, rules)
    return {
        "params": p,
        "opt": {"m": p, "v": p, "step": NamedSharding(mesh, P())},
    }


def make_batch_shardings(cfg: ArchConfig, mesh: Mesh, specs: Dict[str, Any], rules: Dict[str, Any]) -> Dict[str, Any]:
    axes = {}
    for name, spec in specs.items():
        if name in ("tokens", "labels", "loss_mask", "positions"):
            axes[name] = ("batch", None)
        elif name == "frames":
            axes[name] = ("batch", None, None)
        elif name == "vision_embeds":
            axes[name] = ("batch", None, None)
        else:
            axes[name] = tuple([None] * len(spec.shape))
    return _tree_shardings(mesh, axes, rules)


def make_cache_shardings(cfg: ArchConfig, mesh: Mesh, rules: Dict[str, Any]) -> PyTree:
    return _tree_shardings(mesh, model_lib.cache_logical_axes(cfg), rules)


# --------------------------------------------------------------------------- #
# train step                                                                   #
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    opt: AdamWConfig = AdamWConfig()
    lr_schedule: Optional[Callable] = None


def init_train_state(cfg: ArchConfig, key) -> Dict[str, PyTree]:
    params = model_lib.init_params(cfg, key)
    return {"params": params, "opt": adamw_init(params)}


def build_train_step(cfg: ArchConfig, tcfg: TrainConfig = TrainConfig()) -> Callable:
    """(state, batch) → (state, metrics). Microbatch accumulation over the
    leading batch axis; grads averaged in fp32."""

    def loss_for(params, mb):
        loss, metrics = model_lib.loss_fn(cfg, params, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def train_step(state: Dict[str, PyTree], batch: Dict[str, jax.Array]):
        params = state["params"]
        n_micro = tcfg.microbatches
        if n_micro <= 1:
            (loss, _), grads = grad_fn(params, batch)
        else:

            def split(x):
                b = x.shape[0]
                return x.reshape(n_micro, b // n_micro, *x.shape[1:])

            micro = jax.tree_util.tree_map(split, batch)

            def acc(carry, mb):
                g_acc, l_acc = carry
                (l, _m), g = grad_fn(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(jnp.float32), g_acc, g
                )
                return (g_acc, l_acc + l), None

            g0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            carry0 = (g0, jnp.zeros((), jnp.float32))
            (grads, loss_sum), _ = jax.lax.scan(acc, carry0, micro)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro

        lr = tcfg.lr_schedule(state["opt"]["step"]) if tcfg.lr_schedule else None
        new_params, new_opt, opt_metrics = adamw_update(params, grads, state["opt"], tcfg.opt, lr)
        out_metrics = {"loss": loss, **opt_metrics}
        if lr is not None:
            out_metrics["lr"] = lr
        return {"params": new_params, "opt": new_opt}, out_metrics

    return train_step


# --------------------------------------------------------------------------- #
# serve steps                                                                  #
# --------------------------------------------------------------------------- #
def _serve_step(cfg: ArchConfig, count: int) -> Callable:
    """(params, caches, batch) → (next_token [B,1], counts[count], caches),
    ``counts`` as ``forward_layers`` returns them; the head runs on the last
    position only."""

    def step(params, caches, batch):
        x, _, counts, new_caches = model_lib.forward_layers(cfg, params, batch, caches=caches, update_cache=True)
        logits = model_lib.mask_padded_vocab(cfg, model_lib.apply_head(cfg, params, x[:, -1:]))
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, counts[count], new_caches

    return step


def build_prefill_step(cfg: ArchConfig) -> Callable:
    """(params, caches, batch) → (next_token [B,1], dropped, caches):
    ``dropped`` is the expert choices that the capacity bound dropped, summed
    over layers (0 without MoE)."""
    return _serve_step(cfg, 0)


def build_decode_step(cfg: ArchConfig) -> Callable:
    """(params, caches, batch) → (next_token [B,1], experts_run, caches):
    ``experts_run`` is the held experts whose FFN ran, summed over layers (0
    without MoE)."""
    return _serve_step(cfg, 1)

