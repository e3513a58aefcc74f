"""The serving engine casts the weights to the compute dtype once: prefill and
decode read that copy, serve the same tokens as the float32 tree does, and no
longer convert the weights in every call."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.launch.steps import build_decode_step, build_prefill_step
from repro.models import forward, init_caches, init_params
from repro.models.model import cast_for_compute
from repro.serve import ServeEngine
from repro.telemetry import MetricRegistry, spans

MAX_SEQ = 32
PROMPTS = np.array([[5, 17, 99, 3, 8], [250, 1, 7, 42, 0]], dtype=np.int32)


@pytest.fixture(scope="module", params=["moe", "dense"])
def model(request):
    if request.param == "moe":
        cfg = configs.get_reduced("granite_moe_1b_a400m")
    else:  # untied, so the head is a leaf of its own
        cfg = configs.get_reduced("llama3_2_1b").replace(tie_embeddings=False)
    assert cfg.param_dtype == jnp.float32 and cfg.compute_dtype == jnp.bfloat16
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def float32_tree_tokens(cfg, params, prompts, n_new):
    """Greedy tokens from the jitted prefill and decode steps on the float32
    tree, positions as the engine gives them."""
    prefill, decode = jax.jit(build_prefill_step(cfg)), jax.jit(build_decode_step(cfg))
    b, s0 = prompts.shape
    caches = init_caches(cfg, b, MAX_SEQ, dtype=cfg.compute_dtype)
    positions = jnp.broadcast_to(jnp.arange(s0, dtype=jnp.int32), (b, s0))
    tok, _, caches = prefill(params, caches, {"tokens": jnp.asarray(prompts), "positions": positions})
    out = [np.asarray(tok)[:, 0]]
    for step in range(1, n_new):
        pos = jnp.full((b, 1), s0 + step - 1, jnp.int32)
        tok, _, caches = decode(params, caches, {"tokens": tok, "positions": pos})
        out.append(np.asarray(tok)[:, 0])
    return np.stack(out, axis=1)


def test_generate_serves_the_float32_trees_tokens(model):
    cfg, params = model
    engine = ServeEngine(cfg, params, max_seq=MAX_SEQ, registry=MetricRegistry())
    got = np.array([r.tokens for r in engine.generate(PROMPTS, max_new_tokens=6)])
    np.testing.assert_array_equal(got, float32_tree_tokens(cfg, params, PROMPTS, 6))


def test_drain_serves_the_float32_trees_tokens(model):
    cfg, params = model
    engine = ServeEngine(cfg, params, max_seq=MAX_SEQ, drain_concurrency=2, registry=MetricRegistry())
    requests = [(PROMPTS[:1], 5), (PROMPTS[1:, :3], 3), (PROMPTS, 4)]
    for prompts, n_new in requests:
        engine.submit(prompts, max_new_tokens=n_new)
    results = iter(engine.drain())
    for prompts, n_new in requests:
        want = float32_tree_tokens(cfg, params, prompts, n_new)
        got = np.array([next(results).tokens for _ in range(prompts.shape[0])])
        np.testing.assert_array_equal(got, want)


def test_forward_on_the_cast_tree_gives_the_same_bits(model):
    cfg, params = model
    batch = {"tokens": jnp.asarray(PROMPTS)}
    fwd = jax.jit(lambda p: forward(cfg, p, batch)[0])
    np.testing.assert_array_equal(np.asarray(fwd(cast_for_compute(cfg, params))), np.asarray(fwd(params)))


def test_cast_for_compute_leaf_by_leaf(model):
    cfg, params = model
    cast = cast_for_compute(cfg, params)
    assert cast["final_norm"] is params["final_norm"]
    head = "embed" if cfg.tie_embeddings else "lm_head"
    assert cast["embed"].dtype == cast[head].dtype == jnp.bfloat16
    seen = set()
    for p_seg, c_seg in zip(params["segments"], cast["segments"]):
        for (path, a), c in zip(
            jax.tree_util.tree_flatten_with_path(p_seg)[0], jax.tree_util.tree_leaves(c_seg)
        ):
            name = path[-1].key
            seen.add(name)
            if name == "router":
                assert c is a and c.dtype == jnp.float32
            else:
                assert a.dtype == jnp.float32 and c.dtype == jnp.bfloat16, name
                np.testing.assert_array_equal(np.asarray(c), np.asarray(a.astype(jnp.bfloat16)))
    assert ("router" in seen) == (cfg.family == "moe")


def weight_casts(jaxpr, params):
    """The float32 → bfloat16 converts in ``jaxpr`` (the jitted decode step's,
    unwrapped) whose operand is a segment weight of ``params``."""
    inner = jaxpr.jaxpr
    if len(inner.eqns) == 1 and "jaxpr" in inner.eqns[0].params:
        inner = inner.eqns[0].params["jaxpr"].jaxpr
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    weights = {v for v, path in zip(inner.invars, paths) if path[0].key == "segments"}
    return [
        e
        for e in inner.eqns
        if e.primitive.name == "convert_element_type"
        and e.invars[0] in weights
        and e.invars[0].aval.dtype == jnp.float32
        and e.params["new_dtype"] == jnp.bfloat16
    ]


def test_decode_graph_reads_the_cast_copy(model):
    cfg, params = model
    engine = ServeEngine(cfg, params, max_seq=MAX_SEQ, registry=MetricRegistry())
    caches = init_caches(cfg, 1, MAX_SEQ, dtype=cfg.compute_dtype)
    batch = {"tokens": jnp.zeros((1, 1), jnp.int32), "positions": jnp.zeros((1, 1), jnp.int32)}
    cast = engine._compute_params
    assert weight_casts(jax.make_jaxpr(engine._decode)(cast, caches, batch), cast) == []
    # the float32 tree, as training passes it, keeps its casts in the graph
    kept = weight_casts(jax.make_jaxpr(engine._decode)(params, caches, batch), params)
    n_cast = sum(a.dtype == jnp.float32 for a in jax.tree_util.tree_leaves(params["segments"]))
    n_router = int(cfg.family == "moe")
    assert len(kept) == n_cast - n_router > 0


def test_assigning_params_recomputes_the_copy(model):
    cfg, params = model
    registry = MetricRegistry()
    engine = ServeEngine(cfg, params, max_seq=MAX_SEQ, registry=registry)
    before = np.array([r.tokens for r in engine.generate(PROMPTS, max_new_tokens=3)])
    cast_bytes = sum(
        c.nbytes
        for c, a in zip(jax.tree_util.tree_leaves(engine._compute_params), jax.tree_util.tree_leaves(params))
        if c.dtype != a.dtype
    )
    assert cast_bytes > 0
    assert registry.sample()["serve.params_precast_bytes"] == cast_bytes
    families = {m.name: m.family for m in registry.collect()}
    assert families["serve.params_precast_bytes"] == "paio_serve_params_precast_bytes"

    swapped = init_params(cfg, jax.random.PRNGKey(4))
    engine.params = swapped
    assert engine.params is swapped
    assert engine._compute_params["segments"][0]["norm1"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(engine._compute_params["embed"]), np.asarray(swapped["embed"].astype(jnp.bfloat16))
    )
    after = np.array([r.tokens for r in engine.generate(PROMPTS, max_new_tokens=3)])
    np.testing.assert_array_equal(after, float32_tree_tokens(cfg, swapped, PROMPTS, 3))
    assert not np.array_equal(before, after)
    assert registry.sample()["serve.params_precast_bytes"] == cast_bytes


def test_a_tree_that_needs_no_cast_is_served_as_given(model):
    cfg, params = model
    cfg = cfg.replace(compute_dtype=jnp.float32)
    registry = MetricRegistry()
    engine = ServeEngine(cfg, params, max_seq=MAX_SEQ, registry=registry)
    for c, a in zip(jax.tree_util.tree_leaves(engine._compute_params), jax.tree_util.tree_leaves(params)):
        assert c is a
    assert registry.sample()["serve.params_precast_bytes"] == 0.0


def test_the_cast_is_one_span(model):
    cfg, params = model
    spans.enable()
    try:
        engine = ServeEngine(cfg, params, max_seq=MAX_SEQ, registry=MetricRegistry())
        engine.generate(PROMPTS, max_new_tokens=3)
        names = [r.name for r in spans.records()]
    finally:
        spans.disable()
    assert names.count("paio.serve.cast_params") == 1
    assert names.index("paio.serve.cast_params") < names.index("paio.serve.prefill")
