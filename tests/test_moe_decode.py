"""The MoE layer's decode path against its capacity-dispatch path, and the
serving engine's count of the held experts that decode calls ran.

A call over at most ``MIN_CAPACITY`` tokens runs the FFN of each held expert
that one of its tokens chose, and no other; a longer call dispatches to every
held expert's capacity slots. With nothing dropped the two give each token the
same sum of its held choices' top-k weights times their FFNs.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.models import init_params
from repro.models import moe
from repro.models.moe import apply_moe_counted, init_moe
from repro.serve import ServeEngine
from repro.telemetry import MetricRegistry

E, D, F, K = 16, 32, 8, 3
#: route name → (held experts, the experts the router is pushed toward)
ROUTES = {
    "all held": (E, range(0, 0)),
    "every choice absent": (4, range(8, 8 + K)),
    "some choices present": (4, range(2, 6)),
}
#: (float32 to summation order; bfloat16 as the kernels' tests compare it)
TOLERANCES = {jnp.float32: dict(rtol=1e-6, atol=1e-6), jnp.bfloat16: dict(rtol=0.0, atol=2e-2)}


def layer_and_tokens(route, dtype, shape):
    held, favoured = ROUTES[route]
    p = jax.tree_util.tree_map(
        lambda a: a[0], init_moe(jax.random.PRNGKey(0), 1, D, E, F, n_shared=1, dtype=dtype, n_held=held)
    )
    p["router"] = p["router"].at[:, list(favoured)].add(4.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (*shape, D), jnp.float32).astype(dtype) + 0.5
    return p, x, held


def hand_chosen(router, x, top_k, held):
    """The held experts that some token of ``x`` chose: numpy over the
    router's own probabilities."""
    logits = np.asarray(x, np.float64).reshape(-1, x.shape[-1]) @ np.asarray(router, np.float64)
    return {int(e) for row in logits for e in np.argsort(-row, kind="stable")[:top_k] if e < held}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["BS1", "BS4"])
@pytest.mark.parametrize("renormalize", [False, True], ids=["softmax", "renormalized"])
@pytest.mark.parametrize("route", list(ROUTES))
def test_decode_path_equals_the_dispatch_path(route, renormalize, shape, dtype):
    """On one call's routing: the loop over the chosen held experts against
    every held expert's capacity slots (capacity 4, so nothing drops)."""
    p, x, held = layer_and_tokens(route, dtype, shape)
    w = {n: p[n] for n in moe.EXPERT_WEIGHTS}
    xg = x.reshape(1, -1, D)
    with jax.default_matmul_precision("highest"):
        top_p, top_idx, _ = moe.route(p["router"], xg, K, renormalize)
        got, ran = moe.chosen_experts(w, xg[0], top_p[0], top_idx[0])
        want, dropped = moe.dispatched_experts(w, xg, top_p, top_idx, E, 1.25)
        _, _, layer_dropped, layer_ran = apply_moe_counted(p, x, K, renormalize=renormalize)
    assert int(dropped) == int(layer_dropped) == 0
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want[0], np.float32), **TOLERANCES[dtype])
    chosen = hand_chosen(p["router"], x, K, held)
    assert int(ran) == int(layer_ran) == len(chosen)
    if route == "every choice absent":
        assert not chosen and not np.any(np.asarray(got))
    elif route == "some choices present":
        assert 0 < len(chosen) < K * xg.shape[1]


@pytest.mark.parametrize("tokens", [moe.MIN_CAPACITY, moe.MIN_CAPACITY + 1])
def test_only_a_call_within_the_least_capacity_takes_the_decode_path(tokens):
    """The decode path runs the held experts chosen; one token more, and the
    dispatch path runs every held expert's FFN."""
    p, x, held = layer_and_tokens("some choices present", jnp.float32, (1, tokens))
    _, _, _, ran = apply_moe_counted(p, x, K)
    want = len(hand_chosen(p["router"], x, K, held)) if moe.decode_sized(tokens) else held
    assert moe.decode_sized(tokens) == (tokens == moe.MIN_CAPACITY)
    assert int(ran) == want


def moe_cfg(name):
    if name == "deepseek share":  # 2 of 16 experts held, top-3
        return configs.get_reduced("deepseek_v2_lite_16b").replace(n_experts_held=2, compute_dtype=jnp.float32)
    return configs.get_reduced("granite_moe_1b_a400m").replace(compute_dtype=jnp.float32)


@pytest.mark.parametrize("name", ["deepseek share", "granite whole"])
def test_engine_counts_the_held_experts_its_decode_calls_ran(name, monkeypatch):
    """``serve.moe_decode_experts_run`` equals a hand count: every MoE layer's
    input in every decode call, routed again in numpy."""
    cfg = moe_cfg(name)
    records = []
    real = moe.apply_moe_counted

    def spy(p, x, top_k, **kw):
        if moe.decode_sized(x.shape[0] * x.shape[1]):
            jax.debug.callback(lambda r, h: records.append((np.asarray(r), np.asarray(h))), p["router"], x)
        return real(p, x, top_k, **kw)

    monkeypatch.setattr(moe, "apply_moe_counted", spy)
    registry = MetricRegistry()
    engine = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(6)), max_seq=24, registry=registry)
    n_new = 5
    engine.generate(np.arange(3, 11, dtype=np.int32)[None], max_new_tokens=n_new)
    n_moe = sum(count for kind, count in cfg.segments() if kind == "moe")
    assert len(records) == (n_new - 1) * n_moe
    held = cfg.n_experts_held or cfg.n_experts
    want = sum(len(hand_chosen(router, h, cfg.top_k, held)) for router, h in records)
    assert want > 0
    assert registry.sample()["serve.moe_decode_experts_run"] == want
    families = {m.name: m.family for m in registry.collect()}
    assert families["serve.moe_decode_experts_run"] == "paio_serve_moe_decode_experts_run"
