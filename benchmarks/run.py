"""Benchmark aggregator: one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Default durations suit CI; ``--full``
approaches the paper's durations.

  fig4_*        stage hot-path scalability (§6.1, Fig 4)
  profile_*     per-op latencies (§6.1 profiling paragraph)
  fig5_7_*      tail-latency control (Figs 5–7, Algorithm 1)
  fig8_*        per-application bandwidth guarantees (Fig 8, Algorithm 2)
  kernel_*      Pallas kernel interpret-mode sanity timings (CPU)
  roofline_*    dry-run derived terms (reads experiments/dryrun JSONs)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


def bench_fig4(seconds: float) -> None:
    from .bench_stage_scalability import profile_ops, run_loopback

    for ch, size in [(1, 0), (1, 131072), (4, 0), (4, 131072)]:
        ops, byts = run_loopback(ch, size, seconds)
        emit(f"fig4_loopback_ch{ch}_{size}B", 1e6 / max(ops, 1e-9), f"{ops/1e3:.1f}kops/s {byts/2**30:.2f}GiB/s")
    for name, ns in profile_ops(n=5000).items():
        emit(f"profile_{name[:-3]}", ns / 1e3, "")


def bench_batch(seconds: float) -> None:
    """Batched vs per-request enforcement (the batched data plane fast path)."""
    from .bench_stage_scalability import run_loopback

    base_ops, _ = run_loopback(1, 4096, seconds, batch_size=1)
    emit("batch_enforce_b1_4KiB", 1e6 / max(base_ops, 1e-9), f"{base_ops/1e3:.1f}kops/s")
    for bs in (64, 256):
        ops, byts = run_loopback(1, 4096, seconds, batch_size=bs)
        emit(
            f"batch_enforce_b{bs}_4KiB",
            1e6 / max(ops, 1e-9),
            f"{ops/1e3:.1f}kops/s {byts/2**30:.2f}GiB/s {ops/max(base_ops,1e-9):.2f}x",
        )


def bench_smoke() -> None:
    """~2 s loopback smoke: one per-request + one batched point, so per-PR CI
    surfaces hot-path perf regressions without the full matrix."""
    bench_batch(seconds=1.0)


def bench_policy() -> None:
    """Policy trigger-to-enforcement reaction latency (see bench_policy_reaction)."""
    from .bench_policy_reaction import measure_reaction

    for interval in (0.05, 0.1):
        r = measure_reaction(trials=10, interval=interval)
        emit(
            f"policy_reaction_i{int(interval*1e3)}ms",
            r["mean_s"] * 1e6,
            f"mean={r['mean_s']*1e3:.1f}ms p95={r['p95_s']*1e3:.1f}ms "
            f"{'under' if r['mean_s'] < interval else 'OVER'}-one-interval",
        )


def bench_fig5_7(seconds: float) -> None:
    from .bench_tail_latency import run_system

    results = {}
    for mode in ("baseline", "paio"):
        r = run_system(mode, "mixture", seconds)
        results[mode] = r
        emit(
            f"fig5_7_{mode}_p99",
            r.percentile(99) * 1e3,
            f"p99={r.percentile(99):.1f}ms tput={r.throughput:.0f}ops/s stalls={r.stall_events}",
        )
    b, p = results["baseline"], results["paio"]
    ratio = b.percentile(99) / max(p.percentile(99), 1e-9)
    emit("fig5_7_p99_improvement", 0.0, f"{ratio:.2f}x (paper: 4x at its 200MiB/s scale)")


def bench_fig8(scale: float) -> None:
    from .bench_bandwidth_fairshare import default_instances, run_setup

    specs = default_instances(scale)
    for mode in ("baseline", "blkio", "paio"):
        res = run_setup(mode, scale)
        phase0 = max(r.t_start for r in res.values())
        phase1 = min(r.t_end for r in res.values())
        met = all(res[s.name].bandwidth_in(phase0, phase1) >= s.demand * 0.9 for s in specs)
        makespan = max(r.t_end for r in res.values())
        emit(f"fig8_{mode}", makespan * 1e6, f"guarantees={'met' if met else 'VIOLATED'} makespan={makespan:.1f}s")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.quantize.ops import dequantize_int8, quantize_int8

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
    t0 = time.perf_counter()
    flash_attention(q, k, v, causal=True, interpret=True).block_until_ready()
    emit("kernel_flash_attention_interpret", (time.perf_counter() - t0) * 1e6, "GQA 128x128 d64")

    x = jax.random.normal(ks[0], (512, 512), jnp.float32)
    t0 = time.perf_counter()
    qq, s, meta = quantize_int8(x, interpret=True)
    dequantize_int8(qq, s, meta, interpret=True).block_until_ready()
    emit("kernel_quantize_roundtrip_interpret", (time.perf_counter() - t0) * 1e6, "512x512 int8")


def bench_roofline() -> None:
    files = sorted(glob.glob("experiments/dryrun/*_pod.json"))
    if not files:
        emit("roofline_missing", 0.0, "run: python -m repro.launch.dryrun --all")
        return
    for f in files:
        r = json.load(open(f))
        rf = r.get("roofline", {})
        name = os.path.basename(f)[:-5]
        step_s = max(rf.get("compute_s", 0), rf.get("memory_s", 0), rf.get("collective_s", 0))
        emit(
            f"roofline_{name}",
            step_s * 1e6,
            f"dominant={rf.get('dominant')} useful={rf.get('useful_flops_ratio', 0):.2f} "
            f"mem/dev={r.get('memory_per_device_gib', 0):.1f}GiB",
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--smoke", action="store_true", help="~2s loopback bench only (per-PR CI perf signal)"
    )
    ap.add_argument(
        "--skip", default="", help="comma list: fig4,batch,policy,fig5_7,fig8,kernels,roofline"
    )
    args = ap.parse_args()
    skip = set(args.skip.split(",")) if args.skip else set()

    print("name,us_per_call,derived")
    if args.smoke:
        bench_smoke()
        return
    if "fig4" not in skip:
        bench_fig4(seconds=2.0 if args.full else 0.5)
    if "batch" not in skip:
        bench_batch(seconds=2.0 if args.full else 0.5)
    if "policy" not in skip:
        bench_policy()
    if "fig5_7" not in skip:
        bench_fig5_7(seconds=20.0 if args.full else 6.0)
    if "fig8" not in skip:
        bench_fig8(scale=0.25 if args.full else 0.1)
    if "kernels" not in skip:
        bench_kernels()
    if "roofline" not in skip:
        bench_roofline()


if __name__ == "__main__":
    main()
