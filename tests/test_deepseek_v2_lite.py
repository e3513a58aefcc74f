"""DeepSeek-V2-Lite as one chip's expert share, against the plain reference
(``benchmarks/chip/configs/deepseek_v2_ref.py``) at test widths on the CPU.

The program computes in float32 here and the reference in float32 at
``Precision.HIGHEST``; the same weights come from the same key. What is left
between them is summation order (blocked or chunked softmax, einsum
contraction order), a few units in the sixth digit of logits of order 1, so
logits are compared at 2e-4. A renormalised top-k, a dropped YaRN factor or
float8 inputs each move the logits by far more (the tests below show it for
each), so the tolerance separates them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.launch.steps import build_prefill_step
from repro.models import forward, init_caches, init_params
from repro.models.attention import mla_softmax_scale
from repro.models.common import Yarn, rope_frequencies, swiglu, yarn_correction_range, yarn_mscale
from repro.models.moe import apply_moe, init_moe
from repro.serve import ServeEngine
from repro.telemetry import MetricRegistry, spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = dict(atol=2e-4, rtol=2e-4)
#: 16 experts over 8 chips: this share holds experts 0 and 1
HELD = 2


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmarks", "chip", "configs", "deepseek_v2_ref.py")
    spec = importlib.util.spec_from_file_location("deepseek_v2_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def share_cfg(**kw):
    return configs.get_reduced("deepseek_v2_lite_16b").replace(n_experts_held=HELD, **kw)


def arch_of(cfg) -> dict:
    return dataclasses.asdict(cfg)


def tokens_for(cfg, b, s, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, cfg.vocab)


def groups_for(ref, arch, n_tokens):
    gs, cap = ref.moe_groups_for_batch(arch, n_tokens)
    return jnp.asarray(gs), jnp.asarray(cap)


def program_logits(cfg, params, tokens):
    return np.asarray(forward(cfg, params, {"tokens": tokens})[0][..., : cfg.vocab])


def reference_logits(ref, arch, params, tokens, precision="f32"):
    groups = groups_for(ref, arch, tokens.size)
    return np.asarray(ref.forward(arch, params, tokens, precision=precision, q_block=16, moe_groups=groups))


# --------------------------------------------------------------------------- #
# the model against the reference                                              #
# --------------------------------------------------------------------------- #
def test_reference_draws_the_programs_weights(ref):
    for dtype in (jnp.float32, jnp.bfloat16):
        cfg = share_cfg(param_dtype=dtype)
        prog = init_params(cfg, jax.random.PRNGKey(3))
        mine = ref.init_params(arch_of(cfg), jax.random.PRNGKey(3))
        assert jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(mine)
        for a, b in zip(jax.tree_util.tree_leaves(prog), jax.tree_util.tree_leaves(mine)):
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    moe = prog["segments"][1]["moe"]
    assert moe["router"].shape[-1] == cfg.n_experts and moe["w_gate"].shape[1] == HELD


def test_init_std_draws_every_matrix_but_the_router_at_that_std(ref):
    """With ``init_std`` every matrix and the embedding are drawn at that std
    (the truncation at 2 std leaves 0.8796 of it), the router at
    1/sqrt(fan-in); without it every matrix is drawn at 1/sqrt(fan-in), and
    the reference draws the same either way."""
    cfg = share_cfg(d_model=256, d_ff=512, init_std=0.02)  # fan-in 256 puts 1/sqrt(fan-in) at 0.0625
    params = init_params(cfg, jax.random.PRNGKey(11))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if leaf.ndim < 2 or "norm" in name:
            continue
        std = float(jnp.std(leaf.astype(jnp.float32)))
        if "router" in name:
            assert std == pytest.approx(0.8796 / math.sqrt(cfg.d_model), rel=0.05), name
        elif "embed" in name:
            assert std == pytest.approx(0.02, rel=0.05), name
        else:
            assert std == pytest.approx(0.8796 * 0.02, rel=0.05), name
    fan_in = share_cfg(init_std=0.0)
    prog = init_params(fan_in, jax.random.PRNGKey(3))
    mine = ref.init_params(arch_of(fan_in), jax.random.PRNGKey(3))
    for a, b in zip(jax.tree_util.tree_leaves(prog), jax.tree_util.tree_leaves(mine)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    wo = prog["segments"][0]["attn"]["wo"]  # [L, heads, v, d]: fan-in is the heads axis
    assert float(jnp.std(wo)) == pytest.approx(0.8796 / math.sqrt(fan_in.n_heads), rel=0.1)


def test_yarn_and_init_std_are_refused_where_the_program_lacks_them():
    from repro.models.model import ArchConfig

    with pytest.raises(ValueError, match="MLA only"):
        configs.get_reduced("granite_moe_1b_a400m").replace(rope_scaling_factor=40.0)
    with pytest.raises(ValueError, match="dense and MoE"):
        ArchConfig(name="m", family="ssm", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=0, vocab=64,
                   ssm_state=8, init_std=0.02)


@pytest.mark.parametrize("backend", ["xla", "chunked"])
def test_forward_matches_the_reference(ref, backend):
    cfg = share_cfg(compute_dtype=jnp.float32, attn_backend=backend, attn_chunk=16)
    arch = arch_of(cfg)
    params = init_params(cfg, jax.random.PRNGKey(7))
    tokens = tokens_for(cfg, 2, 48, 7)  # 3 groups of 32: the capacity bound (8) drops
    with jax.default_matmul_precision("highest"):
        want = reference_logits(ref, arch, params, tokens)
        got = program_logits(cfg, params, tokens)
    np.testing.assert_allclose(got, want, **TOL)


def test_prefill_then_decode_through_the_latent_cache_matches_the_full_forward(ref):
    """The engine's path (prefill into the latent cache, then one absorbed
    decode call per token) against the reference's full forward over prompt
    and served tokens, compared on the logits that chose each token."""
    cfg = share_cfg(compute_dtype=jnp.float32)
    arch = arch_of(cfg)
    params = init_params(cfg, jax.random.PRNGKey(5))
    prompt = np.asarray(tokens_for(cfg, 1, 40, 5))
    n_new, length = 6, 48
    with jax.default_matmul_precision("highest"):
        caches = init_caches(cfg, 1, length, dtype=jnp.float32)
        logits, _, caches = forward(cfg, params, {"tokens": jnp.asarray(prompt)}, caches=caches, update_cache=True)
        rows = [np.asarray(logits[0, -1, : cfg.vocab])]
        served = [int(np.argmax(rows[-1]))]
        for t in range(1, n_new):
            batch = {"tokens": jnp.asarray([[served[-1]]]), "positions": jnp.asarray([[prompt.shape[1] + t - 1]])}
            logits, _, caches = forward(cfg, params, batch, caches=caches, update_cache=True)
            rows.append(np.asarray(logits[0, -1, : cfg.vocab]))
            served.append(int(np.argmax(rows[-1])))
        toks, p_len, gs, cap = ref.serving_inputs(arch, prompt, served, length)
        fn = ref.served_logits_fn(arch, length, n_new)
        want = np.asarray(fn(params, jnp.asarray(toks), p_len, jnp.asarray(gs), jnp.asarray(cap)))
    np.testing.assert_allclose(np.stack(rows), want, **TOL)


def test_the_tolerance_separates_float8_and_the_departures(ref):
    """Float8 inputs, a renormalised top-k and a missing YaRN each move the
    logits far past the tolerance."""
    cfg = share_cfg(compute_dtype=jnp.float32)
    arch = arch_of(cfg)
    params = init_params(cfg, jax.random.PRNGKey(9))
    tokens = tokens_for(cfg, 1, 40, 9)
    with jax.default_matmul_precision("highest"):
        base = reference_logits(ref, arch, params, tokens)
        variants = {
            "fp8": reference_logits(ref, arch, params, tokens, precision="fp8"),
            "renormalised": program_logits(cfg.replace(norm_topk_prob=True), params, tokens),
            "no yarn": program_logits(cfg.replace(rope_scaling_factor=1.0), params, tokens),
        }
    for name, got in variants.items():
        assert np.max(np.abs(got - base)) > 20 * (TOL["atol"] + TOL["rtol"] * np.max(np.abs(base))), name


def test_the_benchmark_configuration_runs_the_published_model():
    """Every width, the 27 layers, the vocabulary, the router's 64 outputs and
    top-6 as published; only the experts held here are cut, and the block
    the program runs says the same as the file's keys."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "chip", "configs", "deepseek-v2-lite.json")) as f:
        cfg = json.load(f)
    published = {
        "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512, "moe_intermediate_size": 1408,
        "n_routed_experts": 64, "n_shared_experts": 2, "num_attention_heads": 16, "num_experts_per_tok": 6,
        "num_hidden_layers": 27, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "vocab_size": 102400, "rms_norm_eps": 1e-06, "rope_theta": 10000, "norm_topk_prob": False,
        "first_k_dense_replace": 1, "tie_word_embeddings": False,
    }
    for key, value in published.items():
        assert cfg.get("published", {}).get(key, cfg[key]) == value, key
    assert cfg["reduced"] == ["n_routed_experts"] and cfg["n_routed_experts"] == 8
    scaling = cfg["rope_scaling"]
    arch = cfg["arch"]
    assert (arch["d_model"], arch["d_ff"], arch["d_ff_expert"], arch["kv_lora_rank"]) == (2048, 10944, 1408, 512)
    assert (arch["n_heads"], arch["qk_nope_dim"], arch["qk_rope_dim"], arch["v_head_dim"]) == (16, 128, 64, 128)
    assert (arch["n_layers"], arch["vocab"], arch["n_experts"], arch["top_k"]) == (27, 102400, 64, 6)
    assert (arch["n_experts_held"], arch["n_shared_experts"], arch["first_k_dense"]) == (8, 2, 1)
    assert arch["norm_topk_prob"] is False and arch["norm_eps"] == cfg["rms_norm_eps"]
    assert arch["init_std"] == 0.02
    assert (arch["rope_scaling_factor"], arch["rope_original_max_pos"]) == (scaling["factor"], scaling["original_max_position_embeddings"])
    assert (arch["rope_beta_fast"], arch["rope_beta_slow"]) == (scaling["beta_fast"], scaling["beta_slow"])
    assert (arch["rope_mscale"], arch["rope_mscale_all_dim"]) == (scaling["mscale"], scaling["mscale_all_dim"])
    from repro.models.model import ArchConfig

    program = ArchConfig(**arch)
    assert program.yarn == PUBLISHED_YARN and program.param_dtype == "bfloat16"


# --------------------------------------------------------------------------- #
# the expert share                                                             #
# --------------------------------------------------------------------------- #
def test_the_shares_add_up_to_the_uncut_layer(ref):
    """8 chips each holding 2 of 16 experts: their routed parts, plus the
    shared experts (which every chip computes alike) counted once, equal the
    uncut reference layer, drops by the capacity bound included. A chip holds
    the first experts of its router's order, so chip i's router lists the
    experts from 2i on first; a relabelling of experts changes no choice and
    no place in an expert's queue."""
    cfg = configs.get_reduced("deepseek_v2_lite_16b")
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    whole = jax.tree_util.tree_map(
        lambda a: a[0], init_moe(jax.random.PRNGKey(0), 1, d, e, f, cfg.n_shared_experts)
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, d), jnp.float32)
    kw = dict(capacity_factor=cfg.capacity_factor, group_size=cfg.moe_group_size, renormalize=False)
    routed_keys = ("router", "w_gate", "w_up", "w_down")
    total, dropped = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for first in range(0, e, HELD):
            share = {k: whole[k][first : first + HELD] for k in routed_keys[1:]}
            share["router"] = jnp.roll(whole["router"], -first, axis=-1)
            out, _, n = apply_moe(share, x, cfg.top_k, **kw)
            total, dropped = total + out, dropped + int(n)
        shared = swiglu(x @ whole["shared_gate"], x @ whole["shared_up"]) @ whole["shared_down"]
        arch = arch_of(cfg)
        want = ref.moe(arch, whole, x, *groups_for(ref, arch, 96), "f32")
        _, _, dropped_whole = apply_moe(whole, x, cfg.top_k, **kw)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=1e-5, rtol=1e-5)
    assert dropped == int(dropped_whole) > 0


def test_top_k_weights_stay_the_softmax_over_all_experts():
    """``renormalize=False`` weights each chosen expert by its softmax
    probability over all experts; ``True`` rescales the k to sum to one."""
    e, d, f, k = 8, 16, 8, 3
    p = jax.tree_util.tree_map(lambda a: a[0], init_moe(jax.random.PRNGKey(2), 1, d, e, f))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 4, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, p["router"]), axis=-1)[0]
        experts = jnp.stack(
            [jnp.einsum("sf,fd->sd", jax.nn.silu(x[0] @ p["w_gate"][i]) * (x[0] @ p["w_up"][i]), p["w_down"][i]) for i in range(e)]
        )  # [e, s, d]
        top_p, top_i = jax.lax.top_k(probs, k)
        picked = jnp.take_along_axis(experts.transpose(1, 0, 2), top_i[:, :, None], axis=1)  # [s, k, d]
        for renormalize in (False, True):
            w = top_p / jnp.sum(top_p, -1, keepdims=True) if renormalize else top_p
            got = apply_moe(p, x, k, capacity_factor=8.0, renormalize=renormalize)[0][0]
            np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.sum(picked * w[:, :, None], 1)), atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.sum(top_p, -1))) < 0.99  # so the two differ


# --------------------------------------------------------------------------- #
# YaRN                                                                         #
# --------------------------------------------------------------------------- #
PUBLISHED_YARN = Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)


def test_yarn_ramp_runs_between_dims_10_and_23(ref):
    assert yarn_correction_range(PUBLISHED_YARN, 64, 1e4) == (10, 23)
    plain = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    got = np.asarray(rope_frequencies(64, 1e4, PUBLISHED_YARN), np.float64)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)  # ramp 0 at i <= 10
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)  # ramp 1 at i >= 23
    t = (16 - 10) / 13
    np.testing.assert_allclose(got[16], plain[16] * (t / 40 + 1 - t), rtol=1e-6)
    arch = arch_of(configs.get("deepseek_v2_lite_16b"))
    np.testing.assert_allclose(got, ref.yarn_inv_freq(arch, 64), rtol=1e-6)


def test_yarn_softmax_scale_is_0_11472(ref):
    assert yarn_mscale(40.0, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert mla_softmax_scale(192, PUBLISHED_YARN) == pytest.approx(0.11472, abs=1e-5)
    assert mla_softmax_scale(192) == pytest.approx(0.07217, abs=1e-5)
    cfg = configs.get("deepseek_v2_lite_16b")
    assert cfg.yarn == PUBLISHED_YARN and ref.softmax_scale(arch_of(cfg)) == pytest.approx(0.11472, abs=1e-5)


# --------------------------------------------------------------------------- #
# the prefill head                                                             #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["deepseek_v2_lite_16b", "granite_moe_1b_a400m", "llama3_2_1b"])
def test_last_position_head_gives_the_full_heads_token(name):
    cfg = configs.get_reduced(name)
    if name == "deepseek_v2_lite_16b":
        cfg = cfg.replace(n_experts_held=HELD)
    params = init_params(cfg, jax.random.PRNGKey(4))
    tokens = tokens_for(cfg, 2, 24, 4)
    caches = init_caches(cfg, 2, 32, dtype=cfg.compute_dtype)
    tok, _, _ = jax.jit(build_prefill_step(cfg))(params, caches, {"tokens": tokens})
    caches = init_caches(cfg, 2, 32, dtype=cfg.compute_dtype)
    logits = jax.jit(lambda p, c: forward(cfg, p, {"tokens": tokens}, caches=c, update_cache=True)[0])(params, caches)
    want = np.argmax(np.asarray(logits[:, -1, : cfg.vocab]), axis=-1)
    np.testing.assert_array_equal(np.asarray(tok)[:, 0], want)


# --------------------------------------------------------------------------- #
# spans and counters                                                           #
# --------------------------------------------------------------------------- #
def test_cache_init_nests_in_prefill_and_its_bytes_are_published():
    cfg = share_cfg()
    registry = MetricRegistry()
    engine = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)), max_seq=40, registry=registry)
    spans.enable()
    try:
        engine.generate(np.arange(1, 9, dtype=np.int32)[None], max_new_tokens=2)
        records = spans.records()
    finally:
        spans.disable()
    by_id = {r.span_id: r for r in records}
    inits = [r for r in records if r.name == "paio.serve.cache_init"]
    assert len(inits) == 1 and by_id[inits[0].parent_id].name == "paio.serve.prefill"
    # the latent cache: 3 layers x 40 slots x (32 latent + 8 rope) bf16, positions int32, and the offsets
    want = 3 * (40 * (32 + 8) * 2 + 40 * 4 + 4)
    assert registry.sample()["serve.cache_bytes"] == want
    families = {m.name: m.family for m in registry.collect()}
    assert families["serve.cache_bytes"] == "paio_serve_cache_bytes"
    assert families["serve.moe_dropped_choices"] == "paio_serve_moe_dropped_choices"


def hand_count_dropped(p, x, top_k, capacity_factor, group_size, held):
    """Choices of held experts beyond each expert's capacity, group by group,
    tokens in order: numpy over the router's own probabilities."""
    e = p["router"].shape[-1]
    xt = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    g = max(len(xt) // group_size, 1)
    sp = len(xt) // g
    cap = max(math.ceil(top_k * sp * capacity_factor / e), 4)
    logits = xt @ np.asarray(p["router"], np.float64)
    dropped = 0
    for gi in range(g):
        load = np.zeros(e, int)
        for row in logits[gi * sp : (gi + 1) * sp]:
            for expert in np.argsort(-row, kind="stable")[:top_k]:
                load[expert] += 1
                dropped += int(load[expert] > cap and expert < held)
    return dropped


def test_dropped_choices_match_a_hand_count_on_a_forced_overflow():
    e, d, f, k = 16, 32, 8, 3
    p = jax.tree_util.tree_map(lambda a: a[0], init_moe(jax.random.PRNGKey(5), 1, d, e, f, n_held=4))
    # a router that favours experts 2-5, so that the share holding 0-3 overflows
    p["router"] = p["router"].at[:, 2:6].add(2.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, d), jnp.float32) + 1.0
    with jax.default_matmul_precision("highest"):
        _, _, n = apply_moe(p, x, k, capacity_factor=1.0, group_size=32)
    want = hand_count_dropped(p, x, k, 1.0, 32, 4)
    assert int(n) == want > 0
    one = apply_moe(p, x[:, :1], k, capacity_factor=1.0, group_size=32)[2]
    assert int(one) == 0  # one token makes one choice per expert, under the least capacity (4)


def test_engine_counts_the_prefills_drops_and_a_one_token_call_none():
    cfg = share_cfg(capacity_factor=0.25)  # capacity 4 a group of 32: the prefill overflows
    params = init_params(cfg, jax.random.PRNGKey(8))
    prompt = np.asarray(tokens_for(cfg, 1, 64, 8))
    prefill = jax.jit(build_prefill_step(cfg))
    _, want, _ = prefill(params, init_caches(cfg, 1, 72, dtype=cfg.compute_dtype), {"tokens": jnp.asarray(prompt)})
    assert int(want) > 0
    registry = MetricRegistry()
    engine = ServeEngine(cfg, params, max_seq=72, registry=registry)
    engine.generate(prompt, max_new_tokens=4)  # decode calls of one token add nothing
    assert registry.sample()["serve.moe_dropped_choices"] == int(want)
    engine.generate(prompt[:, :1], max_new_tokens=3)
    assert registry.sample()["serve.moe_dropped_choices"] == int(want)
