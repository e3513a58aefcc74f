"""Per-request times in the serving engine, the summaries it publishes once
per request, and the spans a drain opens."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.core import DifferentiationRule, HousekeepingRule, Stage
from repro.models import init_params
from repro.serve import ServeEngine
from repro.telemetry import MetricRegistry, spans


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.get_reduced("llama3_2_1b").replace(compute_dtype=jnp.float32, n_layers=1)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_each_request_gets_its_own_generate_time(small_model):
    """A drain of two windows: the request in the first window is done before
    the second starts its prefill, so its ``serve.generate_ms`` is smaller
    (the whole drain's duration was once given to both)."""
    cfg, params = small_model
    registry = MetricRegistry()
    engine = ServeEngine(cfg, params, max_seq=32, drain_concurrency=1, registry=registry)
    engine.submit(np.zeros((1, 4), np.int32), max_new_tokens=3)
    engine.submit(np.ones((1, 4), np.int32), max_new_tokens=3)
    first, second = engine.drain()
    a, b = first.times, second.times
    assert first.request_id == 0 and second.request_id == 1
    for t in (a, b):
        assert t.submitted <= t.prefill_start <= t.first_token <= t.done
        assert 0 <= t.queue_wait_s <= t.ttft_s <= t.latency_s
    assert b.prefill_start >= a.done
    assert a.latency_s < b.latency_s
    sample = registry.sample()
    for name, values in (
        ("serve.generate_ms", (a.latency_s, b.latency_s)),
        ("serve.ttft_ms", (a.ttft_s, b.ttft_s)),
        ("serve.queue_wait_ms", (a.queue_wait_s, b.queue_wait_s)),
    ):
        assert sample[f"{name}.count"] == 2
        assert sample[f"{name}.mean"] == pytest.approx(sum(values) / 2 * 1e3)
    families = {m.name: m.family for m in registry.collect()}
    assert families["serve.ttft_ms"] == "paio_serve_ttft_ms"
    assert families["serve.queue_wait_ms"] == "paio_serve_queue_wait_ms"


def test_single_token_request_is_done_at_its_first_token(small_model):
    cfg, params = small_model
    engine = ServeEngine(cfg, params, max_seq=32, registry=MetricRegistry())
    (r,) = engine.generate(np.zeros((1, 4), np.int32), max_new_tokens=1)
    assert r.times.done == r.times.first_token and len(r.tokens) == 1


def test_drain_spans(small_model):
    cfg, params = small_model
    stage = Stage("serve")
    for t in ("a", "b"):
        stage.hsk_rule(HousekeepingRule(op="create_channel", channel=t))
        stage.dif_rule(DifferentiationRule(channel=t, match={"tenant": t}))
    engine = ServeEngine(cfg, params, max_seq=32, stage=stage, drain_concurrency=2, registry=MetricRegistry())
    engine.submit(np.zeros((1, 4), np.int32), max_new_tokens=3, tenant="a")
    engine.submit(np.zeros((1, 4), np.int32), max_new_tokens=2, tenant="b")
    spans.enable()
    try:
        engine.drain()
        recs = spans.records()
    finally:
        spans.disable()
    by = {}
    for r in recs:
        by.setdefault(r.name, []).append(r)
    (admit,) = by["paio.serve.admit"]
    assert admit.ids == {"requests": 2}
    assert [p.ids["request"] for p in by["paio.serve.prefill"]] == [0, 1]
    decode = by["paio.serve.decode"]
    # step 1 decodes both requests, step 2 only the first
    assert [(d.ids["step"], d.ids["live"]) for d in decode] == [(1, 2), (2, 1)]
    steps = {d.span_id: d for d in decode}
    for kind in ("paio.serve.dispatch", "paio.serve.sync"):
        assert [(steps[r.parent_id].ids["step"], r.ids["request"]) for r in by[kind]] == [(1, 0), (1, 1), (2, 0)]
    # enforcement runs inside admission and each decode step
    parents = {r.parent_id for r in by["paio.stage.enforce"]}
    assert parents == {admit.span_id, *steps}
