#!/usr/bin/env python3
"""Bring-up smoke run of the PAIO-fronted training and serving path on TPU.

    python chip_smoke.py [--seed N]    # one chip: kernels, train, serve
    python chip_smoke.py --chips 4     # four chips: sharded training only

Run it from the root of a checkout on a machine with a TPU. Where JAX finds
no TPU it exits non-zero before any other phase; it never runs on the CPU.
One process holds the chip(s) and runs every phase; nothing forks. Weights,
tokens and prompts come from ``--seed``, checkpoints go to a temporary
directory, and the compile cache goes where ``repro.launch.compile_cache``
puts it.

Phases on one chip:

* ``kernels``: each Pallas kernel compiled for the chip at a real width,
  checked against its ``ref.py``.
* ``train``: ``repro.launch.train.train`` on Llama-3.2-1B at full width with
  the depth cut to 4 layers (remat on) at B8 S1024, for 8 steps. The
  ``TrainIOControl`` stage enforces every fetch and every async checkpoint
  write (one every 4 steps). The last checkpoint must restore bit-exact, and
  a quantized save of the params must run the compiled quantize kernel and
  restore within each block's absmax/127.
* ``serve``: ``ServeEngine`` on the full 16-layer Llama-3.2-1B with two
  tenants under ``examples/policies/serve_multitenant.json``. Greedy tokens
  must equal a stage-less engine's, and each tenant's channel must count
  every admitted token.

``--chips 4`` runs only ``sharded``: the 4-layer configuration on a 4x1
(data/FSDP) mesh against one device of the same host, then the full 16-layer
model, which one chip cannot hold, on the 4x1 mesh.

Printed times are smoke timings (compile and run of one pass), not benchmark
numbers. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

SERVE_POLICY = os.path.join(ROOT, "examples", "policies", "serve_multitenant.json")
#: depth cut forced by one chip's 16 GB of HBM: the 4-layer train state and
#: step at B8 S1024 compile to about 13 GB, six layers to about 15 GB
TRAIN_LAYERS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def check(ok, what) -> None:
    """Fail the phase unless ``ok`` (an explicit raise: ``python -O`` keeps it)."""
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def timed(phase: str):
    log(f"[{phase}] start")
    t0 = time.perf_counter()
    yield
    log(f"[{phase}] ok in {time.perf_counter() - t0:.1f} s (smoke timing: compile + one run, not a benchmark number)")


def device_gate(chips: int) -> dict:
    """The device JAX reports; exits before anything else runs unless it is
    a TPU with at least ``chips`` devices."""
    import jax

    devices = jax.devices()
    d = devices[0]
    log(f"device: platform={d.platform} kind={d.device_kind} count={len(devices)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {d.platform!r}; no phase ran")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def llama_1b(n_layers: int = 16):
    """Llama-3.2-1B at its published widths, ``n_layers`` deep, remat on."""
    import repro.configs as configs

    return configs.get("llama3_2_1b").replace(n_layers=n_layers, remat=True)


def log_hbm(what: str) -> None:
    """Peak device memory so far, per device, where the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    if all(p is not None for p in peaks):
        log(f"  peak HBM after {what}: {', '.join(f'{p / 1e9:.2f} GB' for p in peaks)}")


# --------------------------------------------------------------------------- #
# kernels                                                                      #
# --------------------------------------------------------------------------- #
def _compiled(fn, *args, interpret: bool):
    """jit-compile ``fn`` for ``args``; on the chip, require a Mosaic kernel
    in the program (proof it did not run in the interpreter)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if not interpret:
        check("tpu_custom_call" in compiled.as_text(), "no Pallas TPU kernel in the compiled program")
    return compiled


def _assert_codes_match(q, q_ref, what: str) -> None:
    """int8 codes equal the reference's; one step apart is allowed only where
    x/scale sits within float rounding of a .5 boundary (a different
    division order on the chip), so such codes must be rare."""
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
    check(diff.max() <= 1, f"{what}: int8 codes differ by {diff.max()}")
    frac = float((diff > 0).mean())
    check(frac <= 1e-4, f"{what}: {frac:.2e} of int8 codes differ from the reference")


def phase_kernels(
    seed: int,
    rows: int = 8192,
    mat: tuple = (2048, 8192),
    norm_shape: tuple = (8, 1024, 2048),
    attn: tuple = (2, 2048, 32, 8, 64),
    interpret: bool = False,
) -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_reference
    from repro.kernels.quantize.ops import dequantize_int8, quantize_int8, quantize_rows_int8
    from repro.kernels.quantize.ref import dequantize_reference, quantize_reference
    from repro.kernels.rmsnorm.ops import rms_norm_fused
    from repro.kernels.rmsnorm.ref import rmsnorm_reference

    rng = np.random.default_rng(seed)

    # quantize_rows_int8 on [M, 256]: the checkpoint path's kernel
    x = jnp.asarray(rng.normal(size=(rows, 256)) * rng.uniform(0.01, 10.0, size=(rows, 1)), jnp.float32)
    q, s = _compiled(functools.partial(quantize_rows_int8, interpret=interpret), x, interpret=interpret)(x)
    q_ref, s_ref = quantize_reference(x, block_r=1, block_c=256)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref).reshape(-1, 1), rtol=1e-6)
    _assert_codes_match(q, q_ref, "quantize_rows_int8")
    log(f"  quantize_rows_int8 [{rows}, 256] f32 matches quantize_reference")

    # quantize_int8 / dequantize_int8: per-(128, 128)-tile scales
    w = jnp.asarray(rng.normal(size=mat) * 0.02, jnp.float32)

    def roundtrip(a):
        qq, ss, meta = quantize_int8(a, interpret=interpret)
        return qq, ss, dequantize_int8(qq, ss, meta, interpret=interpret)

    q, s, back = _compiled(roundtrip, w, interpret=interpret)(w)
    w2 = w.reshape(-1, 128)  # quantize_int8 lays any shape out as [R, 128] rows
    q_ref, s_ref = quantize_reference(w2)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)
    _assert_codes_match(q, q_ref, "quantize_int8")
    np.testing.assert_allclose(
        np.asarray(back).reshape(-1, 128), np.asarray(dequantize_reference(q, s)), rtol=1e-6, atol=0
    )
    tile_bound = np.repeat(np.asarray(s), 128, axis=0)  # absmax/127 of each row's tile
    check(np.all(np.abs(np.asarray(back).reshape(-1, 128) - np.asarray(w2)) <= tile_bound), "round trip error")
    log(f"  quantize_int8/dequantize_int8 {list(mat)} f32 match quantize/dequantize_reference")

    # rms_norm_fused at d_model 2048
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    h = jax.random.normal(k1, norm_shape, jnp.bfloat16)
    g = (jax.random.normal(k2, norm_shape[-1:], jnp.float32) * 0.1 + 1.0).astype(jnp.bfloat16)
    out = _compiled(functools.partial(rms_norm_fused, interpret=interpret), h, g, interpret=interpret)(h, g)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(rmsnorm_reference(h, g), np.float32), atol=2e-2, rtol=2e-2
    )
    log(f"  rms_norm_fused {list(norm_shape)} bf16 matches the model's rms_norm")

    # flash attention at Llama-3.2-1B's heads: 32 q, 8 kv, head_dim 64
    b, s_len, nh, nkv, dh = attn
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    qa = jax.random.normal(kq, (b, s_len, nh, dh), jnp.bfloat16)
    ka = jax.random.normal(kk, (b, s_len, nkv, dh), jnp.bfloat16)
    va = jax.random.normal(kv, (b, s_len, nkv, dh), jnp.bfloat16)
    fa = functools.partial(flash_attention, causal=True, interpret=interpret)
    out = _compiled(fa, qa, ka, va, interpret=interpret)(qa, ka, va)
    ref = flash_attention_reference(qa, ka, va, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2)
    log(f"  flash_attention B{b} S{s_len} {nh}q/{nkv}kv heads d{dh} bf16 causal matches flash_attention_reference")


# --------------------------------------------------------------------------- #
# training                                                                     #
# --------------------------------------------------------------------------- #
def _check_quantized_restore(orig, restored, block: int = 256) -> None:
    """Each restored leaf is within its quantization block's absmax/127."""
    o = np.asarray(orig, np.float32).reshape(-1)
    r = np.asarray(restored, np.float32).reshape(-1)
    pad = (-o.size) % block
    ob = np.pad(o, (0, pad)).reshape(-1, block)
    rb = np.pad(r, (0, pad)).reshape(-1, block)
    bound = np.abs(ob).max(axis=1, keepdims=True) / 127.0
    check(np.all(np.abs(rb - ob) <= bound), "quantized restore outside absmax/127")


def phase_train(
    seed: int,
    cfg=None,
    steps: int = 8,
    ckpt_every: int = 4,
    batch: int = 8,
    seq: int = 1024,
    interpret: bool = False,
) -> None:
    import jax

    from repro.checkpoint import CheckpointManager, latest_step
    from repro.core.objects import QuantizeInt8
    from repro.launch.train import train

    cfg = cfg if cfg is not None else llama_1b(TRAIN_LAYERS)
    log(
        f"  config: {cfg.name} d_model {cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff},"
        f" vocab {cfg.vocab}; depth cut to {cfg.n_layers} layers (remat={cfg.remat}) to fit one chip's HBM;"
        f" B{batch} S{seq}, {steps} steps, checkpoint every {ckpt_every}"
    )
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        run = train(cfg, steps=steps, batch=batch, seq=seq, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, seed=seed)
        check(len(run.losses) == steps and all(map(math.isfinite, run.losses)), f"losses {run.losses}")
        log(f"  losses: {[round(l, 4) for l in run.losses]}")

        # the stage saw every fetch and every checkpoint write
        n_leaves = len(jax.tree_util.tree_leaves(run.state))
        fetch, ckpt = run.io_stats.per_channel["fetch"], run.io_stats.per_channel["ckpt"]
        check(
            (fetch.cumulative_ops, fetch.cumulative_bytes) == (steps, steps * batch * seq * 4),
            f"fetch channel: {fetch.cumulative_ops} ops, {fetch.cumulative_bytes} B",
        )
        check(ckpt.cumulative_ops == (steps // ckpt_every) * n_leaves, f"ckpt channel: {ckpt.cumulative_ops} ops")
        log(
            f"  stage: fetch {fetch.cumulative_ops} ops {fetch.cumulative_bytes} B,"
            f" ckpt {ckpt.cumulative_ops} ops {ckpt.cumulative_bytes} B"
        )

        # the last checkpoint restores bit-exact, onto the state's shardings
        last = latest_step(ckpt_dir)
        check(last == steps, f"latest checkpoint step {last}")
        shardings = jax.tree_util.tree_map(lambda a: a.sharding, run.state)
        restored = CheckpointManager(ckpt_dir).restore(last, jax.eval_shape(lambda: run.state), shardings)
        for a, b in zip(jax.tree_util.tree_leaves(run.state), jax.tree_util.tree_leaves(restored)):
            check(a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b)), "restore != save")
        del restored
        log(f"  checkpoint step {last}: {n_leaves} arrays restored bit-exact")

        # a quantized save of the params runs the compiled rows kernel
        params = run.state["params"]
        qmgr = CheckpointManager(os.path.join(ckpt_dir, "quantized"), transform="quantize", keep=1)
        if interpret:  # off the chip: the same kernel, in the Pallas interpreter
            qmgr.quantizer = QuantizeInt8(block=256, use_pallas=True, interpret=True)
        qmgr.save(0, params)
        n_quantized = sum(
            e["transform"] == "quantize" for e in qmgr.manifest(0)["tensors"].values()
        )
        check(
            n_quantized > 0 and qmgr.quantizer.kernel_calls == n_quantized,
            f"{n_quantized} arrays quantized, {qmgr.quantizer.kernel_calls} through the Pallas kernel",
        )
        restored = qmgr.restore(0, jax.eval_shape(lambda: params))
        for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(restored)):
            _check_quantized_restore(a, b)
        log(f"  quantized save: {n_quantized} arrays through the Pallas kernel, restore within absmax/127")
    log_hbm("training")


# --------------------------------------------------------------------------- #
# serving                                                                      #
# --------------------------------------------------------------------------- #
def phase_serve(
    seed: int,
    cfg=None,
    requests_per_tenant: int = 2,
    batch: int = 2,
    prompt_len: int = 64,
    new_tokens: int = 16,
) -> None:
    import jax

    from repro.core import ControlPlane, Stage
    from repro.models import init_params
    from repro.serve import ServeEngine

    cfg = cfg if cfg is not None else llama_1b()
    log(f"  config: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, vocab {cfg.vocab}")
    params = jax.jit(lambda key: init_params(cfg, key))(jax.random.PRNGKey(seed))
    tenants = ("tenant_a", "tenant_b")
    rng = np.random.default_rng(seed)
    requests = [
        (tenant, rng.integers(0, cfg.vocab, size=(batch, prompt_len)).astype(np.int32))
        for _ in range(requests_per_tenant)
        for tenant in tenants
    ]
    max_seq = prompt_len + new_tokens

    def serve(stage):
        engine = ServeEngine(cfg, params, max_seq=max_seq, stage=stage)
        for tenant, prompts in requests:
            engine.submit(prompts, max_new_tokens=new_tokens, tenant=tenant)
        return engine.drain()

    stage = Stage("serve")
    cp = ControlPlane()
    cp.register_stage(stage)
    cp.install_policy(SERVE_POLICY)
    cp.start()
    try:
        t0 = time.perf_counter()
        with_stage = serve(stage)
        log(f"  with stage: {len(with_stage)} sequences in {time.perf_counter() - t0:.1f} s (smoke timing)")
    finally:
        cp.close()
    bare = serve(None)

    check([r.tenant for r in with_stage] == [r.tenant for r in bare], "results out of order")
    check(all(len(r.tokens) == new_tokens for r in with_stage), "wrong number of generated tokens")
    check([r.tokens for r in with_stage] == [r.tokens for r in bare], "tokens differ with the stage on")
    log("  greedy tokens identical with and without the stage")

    # every admitted token is on its tenant's channel: prompt tokens at
    # admission, then one token per sequence per decode step
    per_channel = stage.collect().per_channel
    for tenant in tenants:
        st = per_channel[tenant]
        want_ops = requests_per_tenant * new_tokens
        want_tokens = requests_per_tenant * batch * (prompt_len + new_tokens - 1)
        check(
            (st.cumulative_ops, st.cumulative_bytes) == (want_ops, want_tokens),
            f"{tenant}: {st.cumulative_ops} ops, {st.cumulative_bytes} tokens; want {want_ops}, {want_tokens}",
        )
        log(f"  {tenant}: {st.cumulative_ops} ops, {st.cumulative_bytes} tokens admitted")
    log_hbm("serving")


# --------------------------------------------------------------------------- #
# four chips                                                                   #
# --------------------------------------------------------------------------- #
def phase_sharded(
    seed: int,
    cfg_small=None,
    cfg_full=None,
    steps: int = 4,
    full_steps: int = 3,
    batch: int = 8,
    seq: int = 1024,
    data: int = 4,
) -> None:
    import jax

    from repro.launch.train import train

    cfg_small = cfg_small if cfg_small is not None else llama_1b(TRAIN_LAYERS)
    cfg_full = cfg_full if cfg_full is not None else llama_1b()

    one = train(cfg_small, steps=steps, batch=batch, seq=seq, mesh_shape=(1, 1), seed=seed).losses
    run = train(cfg_small, steps=steps, batch=batch, seq=seq, mesh_shape=(data, 1), seed=seed)
    embed = run.state["params"]["embed"]
    check(len(embed.sharding.device_set) == data, f"embedding sharding {embed.sharding}")
    four = run.losses
    del run, embed
    log(f"  {cfg_small.n_layers} layers, 1 device: {one}")
    log(f"  {cfg_small.n_layers} layers, {data}x1 mesh: {four}")
    np.testing.assert_allclose(four, one, rtol=1e-2)  # bf16 compute, different reduction order
    worst = max(abs(a - b) / abs(b) for a, b in zip(four, one))
    log(f"  per-step losses agree within rtol 1e-2 (largest relative difference {worst:.2e})")

    full = train(cfg_full, steps=full_steps, batch=batch, seq=seq, mesh_shape=(data, 1), seed=seed)
    check(all(map(math.isfinite, full.losses)), f"losses {full.losses}")
    per_device: dict = {}
    for leaf in jax.tree_util.tree_leaves(full.state):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = per_device.get(shard.device.id, 0) + shard.data.nbytes
    log(f"  {cfg_full.n_layers} layers on the {data}x1 mesh: losses {full.losses}")
    log(f"  train state per device: {', '.join(f'{b / 1e9:.2f} GB' for b in per_device.values())}")
    log_hbm("sharded training")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    device = device_gate(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        with timed("sharded"):
            phase_sharded(args.seed)
    else:
        log(f"cut: Llama-3.2-1B training depth 16 -> {TRAIN_LAYERS} layers (one chip's HBM); serving at full depth")
        with timed("kernels"):
            phase_kernels(args.seed)
        with timed("train"):
            phase_train(args.seed)
        with timed("serve"):
            phase_serve(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
