import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract memory / cost / collective analyses.

The two lines above MUST run before any jax import: they give the CPU host
512 placeholder devices so ``jax.make_mesh`` can build the 16×16 (single-pod)
and 2×16×16 (multi-pod) production meshes. ``.lower().compile()`` proves the
sharding config is coherent (no mismatched shardings, no OOM at compile, all
collectives supported); no arrays are ever materialized.

Cost correction: XLA's ``cost_analysis`` counts ``while``-loop bodies ONCE
(verified empirically), so the scan-over-layers/microbatches program
undercounts FLOPs. We therefore compile small *probe* variants — unrolled
loops, one microbatch (global_batch/M), 1 vs 2 layers per segment kind, with
and without the optimizer — and difference them:

  per-layer grad  g_k = G_k − G0          (grad-only probes)
  per-layer opt   o_k = (P_k − P0) − g_k  (full-step probes)
  train total ≈ M·[G0 + Σ_k (L_k−1)·g_k] + (P0−G0) + Σ_k (L_k−1)·o_k
  serve total ≈ P0 + Σ_k (L_k−1)·(P_k−P0)

(sLSTM's time recurrence stays a while loop, so its per-layer diff is scaled
by S analytically — ≲25% overcount on 3/24 xlstm layers, documented.) The
real scanned program is still compiled for ``memory_analysis`` (what must fit
in HBM) and to prove the production sharding lowers.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_2_1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod|both]

Results land in experiments/dryrun/<arch>_<shape>_<mesh>.json.
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, get as get_config
from repro.data.pipeline import make_batch_specs
from repro.distributed.sharding import sharding_rules
from repro.launch import cells as cells_lib
from repro.launch.analysis import parse_collectives, roofline_terms
from repro.launch.mesh import HW, make_production_mesh
from repro.launch.steps import (
    TrainConfig,
    build_decode_step,
    build_encoder_step,
    build_prefill_step,
    build_train_step,
    init_train_state,
    make_batch_shardings,
    make_cache_shardings,
    make_state_shardings,
    rules_for,
)
from repro.models import model as model_lib


def _lower_cell(cfg, cell, mesh, rules, microbatches: int, unroll_micro: bool = False, grad_only: bool = False):
    """Lower one cell variant; returns the lowered computation."""
    with mesh, sharding_rules(mesh, rules):
        batch_specs = make_batch_specs(cfg, cell.global_batch, cell.seq, cell.kind)
        batch_shardings = make_batch_shardings(cfg, mesh, batch_specs, rules)
        abstract_params = jax.eval_shape(lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))

        if cell.kind == "train":
            state_shardings = make_state_shardings(cfg, mesh, rules)
            abstract_state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
            step = build_train_step(
                cfg, TrainConfig(microbatches=microbatches, unroll_micro=unroll_micro, grad_only=grad_only)
            )
            jitted = jax.jit(
                step,
                in_shardings=(state_shardings, batch_shardings),
                out_shardings=(state_shardings, None),
                donate_argnums=0,
            )
            return jitted.lower(abstract_state, batch_specs)

        param_shardings = make_state_shardings(cfg, mesh, rules)["params"]
        if cfg.family == "audio":
            step = build_encoder_step(cfg)
            jitted = jax.jit(step, in_shardings=(param_shardings, batch_shardings))
            return jitted.lower(abstract_params, batch_specs)

        abstract_caches = jax.eval_shape(
            lambda: model_lib.init_caches(cfg, cell.global_batch, cell.seq, dtype=jnp.bfloat16)
        )
        cache_shardings = make_cache_shardings(cfg, mesh, rules)
        step = build_decode_step(cfg) if cell.kind == "decode" else build_prefill_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(param_shardings, cache_shardings, batch_shardings),
            out_shardings=(None, None, cache_shardings),
            donate_argnums=1,
        )
        return jitted.lower(abstract_params, abstract_caches, batch_specs)


def _cost_of(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text(), default_group=2)
    return {
        "flops": float(cost.get("flops", 0.0) or 0.0),
        "bytes": float(cost.get("bytes accessed", 0.0) or 0.0),
        "coll": float(coll.total_moved),
        "_coll_counts": coll.counts,
    }


def _combine(a: Dict[str, float], b: Dict[str, float], fa: float, fb: float) -> Dict[str, float]:
    return {k: fa * a.get(k, 0.0) + fb * b.get(k, 0.0) for k in ("flops", "bytes", "coll")}


def _probe_costs(cfg, cell, mesh, rules, microbatches: int) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """Difference unrolled probes into corrected per-step cost totals."""
    segs = cfg.segments()
    kinds: List[str] = []
    layer_counts: Dict[str, int] = {}
    for kind, count in segs:
        layer_counts[kind] = layer_counts.get(kind, 0) + count
        if kind not in kinds:
            kinds.append(kind)

    def probe_cfg(counts: Tuple[Tuple[str, int], ...]):
        return cfg.replace(segment_override=counts, unroll_layers=True, unroll_scans=True)

    # probes see one microbatch worth of tokens
    if cell.kind == "train" and microbatches > 1:
        probe_cell = cells_lib.ShapeCell(cell.name, cell.seq, cell.global_batch // microbatches, cell.kind)
    else:
        probe_cell = cell

    base_counts = tuple((kk, 1) for kk in kinds)

    def probe(counts, grad_only, cell_override=None):
        lowered = _lower_cell(
            probe_cfg(counts), cell_override or probe_cell, mesh, rules, 1, grad_only=grad_only
        )
        return _cost_of(lowered.compile())

    def slstm_per_layer(grad_only) -> Dict[str, float]:
        """sLSTM is a true time recurrence: its per-layer cost is measured by
        fully time-unrolled mini-probes (seq 64 vs 32, 1 vs 2 layers) — every
        quantity in the block is per-token, so per-layer(S) = diff · S/32."""
        s_tokens = 1 if cell.kind == "decode" else cell.seq
        if cell.kind == "decode":
            # decode is a single step — the plain layer diff is already exact
            return None
        costs = {}
        for n_layers in (1, 2):
            for seq in (32, 64):
                mini = cells_lib.ShapeCell(cell.name, seq, probe_cell.global_batch, cell.kind)
                costs[(n_layers, seq)] = probe((("slstm", n_layers),), grad_only, cell_override=mini)
        marginal = _combine(
            _combine(costs[(2, 64)], costs[(2, 32)], 1.0, -1.0),
            _combine(costs[(1, 64)], costs[(1, 32)], 1.0, -1.0),
            1.0,
            -1.0,
        )
        return {kk: v * (s_tokens / 32.0) for kk, v in marginal.items()}

    p0 = probe(base_counts, grad_only=False)
    per_layer_full: Dict[str, Dict[str, float]] = {}
    fix_once_full: Dict[str, Dict[str, float]] = {}
    for k in kinds:
        counts = tuple((kk, 2 if kk == k else 1) for kk in kinds)
        plain = _combine(probe(counts, False), p0, 1.0, -1.0)
        per_layer_full[k] = plain
        if k == "slstm" and cell.kind != "decode":
            per_layer_full[k] = slstm_per_layer(grad_only=False)
            # P0 embeds one scan-undercounted sLSTM layer: swap its cost
            fix_once_full[k] = _combine(per_layer_full[k], plain, 1.0, -1.0)

    if cell.kind != "train" or microbatches <= 1:
        total = dict(p0)
        for k in kinds:
            total = _combine(total, per_layer_full[k], 1.0, layer_counts[k] - 1)
            if k in fix_once_full:
                total = _combine(total, fix_once_full[k], 1.0, 1.0)
        detail = {
            "p0": {kk: v for kk, v in p0.items() if not kk.startswith("_")},
            "per_layer": per_layer_full,
            "layer_counts": layer_counts,
            "microbatches": 1,
        }
        return total, detail

    # train with microbatching: separate grad cost (×M) from optimizer (×1)
    g0 = probe(base_counts, grad_only=True)
    per_layer_grad: Dict[str, Dict[str, float]] = {}
    fix_once_grad: Dict[str, Dict[str, float]] = {}
    for k in kinds:
        counts = tuple((kk, 2 if kk == k else 1) for kk in kinds)
        plain = _combine(probe(counts, True), g0, 1.0, -1.0)
        per_layer_grad[k] = plain
        if k == "slstm" and cell.kind != "decode":
            per_layer_grad[k] = slstm_per_layer(grad_only=True)
            fix_once_grad[k] = _combine(per_layer_grad[k], plain, 1.0, -1.0)

    grad_total = dict(g0)
    for k in kinds:
        grad_total = _combine(grad_total, per_layer_grad[k], 1.0, layer_counts[k] - 1)
        if k in fix_once_grad:
            grad_total = _combine(grad_total, fix_once_grad[k], 1.0, 1.0)
    opt_total = _combine(p0, g0, 1.0, -1.0)
    for k in kinds:
        o_k = _combine(per_layer_full[k], per_layer_grad[k], 1.0, -1.0)
        opt_total = _combine(opt_total, o_k, 1.0, layer_counts[k] - 1)
    total = _combine(grad_total, opt_total, float(microbatches), 1.0)
    detail = {
        "p0": {kk: v for kk, v in p0.items() if not kk.startswith("_")},
        "g0": {kk: v for kk, v in g0.items() if not kk.startswith("_")},
        "per_layer_grad": per_layer_grad,
        "per_layer_opt": {k: _combine(per_layer_full[k], per_layer_grad[k], 1.0, -1.0) for k in kinds},
        "layer_counts": layer_counts,
        "microbatches": microbatches,
    }
    return total, detail


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str = "experiments/dryrun") -> Dict[str, Any]:
    cell = cells_lib.SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = mesh.size
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)

    cfg = get_config(arch)
    cfg, microbatches = cells_lib.tune_for_cell(cfg, cell, dp)
    rules = rules_for(cfg, decode=(cell.kind == "decode"), batch_size=cell.global_batch, mesh=mesh)

    result: Dict[str, Any] = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "chips": chips,
        "kind": cell.kind,
        "microbatches": microbatches,
        "params": int(cfg.total_params()),
    }

    # 1) the real scanned program: proves sharding + memory fit
    t0 = time.monotonic()
    lowered = _lower_cell(cfg, cell, mesh, rules, microbatches)
    result["lower_s"] = round(time.monotonic() - t0, 1)
    t1 = time.monotonic()
    compiled = lowered.compile()
    result["compile_s"] = round(time.monotonic() - t1, 1)
    mem = compiled.memory_analysis()
    mem_stats = {
        k: int(getattr(mem, k, 0) or 0)
        for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes", "generated_code_size_in_bytes")
    }
    per_device_bytes = mem_stats["argument_size_in_bytes"] + mem_stats["temp_size_in_bytes"]
    raw_cost = _cost_of(compiled)
    result["memory"] = mem_stats
    result["memory_per_device_gib"] = round(per_device_bytes / 2**30, 3)
    result["collective_counts_scanned_hlo"] = raw_cost["_coll_counts"]

    # 2) probe-corrected cost totals
    t2 = time.monotonic()
    total, detail = _probe_costs(cfg, cell, mesh, rules, microbatches)
    result["probe_s"] = round(time.monotonic() - t2, 1)
    result["cost"] = total
    result["cost_detail"] = detail

    model_flops = cells_lib.model_flops_for_cell(cfg, cell)
    from repro.launch.analysis import CollectiveStats

    rf = roofline_terms(
        arch=arch,
        shape=shape,
        mesh_name=mesh_name,
        chips=chips,
        cost={"flops": total["flops"], "bytes accessed": total["bytes"]},
        collectives=CollectiveStats(counts={}, result_bytes={}, moved_bytes={"total": total["coll"]}),
        model_flops_global=model_flops,
        hw=HW,
        memory_per_device=per_device_bytes,
    )
    result["roofline"] = {
        "compute_s": rf.compute_s,
        "memory_s": rf.memory_s,
        "collective_s": rf.collective_s,
        "dominant": rf.dominant,
        "model_flops_per_device": rf.model_flops,
        "useful_flops_ratio": rf.useful_flops_ratio,
    }

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{arch}_{shape}_{mesh_name}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        cell_list = cells_lib.all_cells()
    elif args.arch and args.shape:
        reason = cells_lib.skip_reason(args.arch, args.shape)
        if reason:
            print(f"SKIP {args.arch} × {args.shape}: {reason}")
            return 0
        cell_list = [(args.arch, args.shape)]
    else:
        ap.error("--arch and --shape, or --all")
        return 2

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    failures = 0
    for arch, shape in cell_list:
        for mesh_name in meshes:
            tag = f"{arch} × {shape} × {mesh_name}"
            out_file = os.path.join(args.out, f"{arch}_{shape}_{mesh_name}.json")
            if args.skip_existing and os.path.exists(out_file):
                print(f"SKIP {tag} (exists)")
                continue
            try:
                r = run_cell(arch, shape, mesh_name, args.out)
                rf = r["roofline"]
                print(
                    f"OK   {tag}: mem/dev={r['memory_per_device_gib']:.2f}GiB "
                    f"compute={rf['compute_s']*1e3:.2f}ms memory={rf['memory_s']*1e3:.2f}ms "
                    f"collective={rf['collective_s']*1e3:.2f}ms dominant={rf['dominant']} "
                    f"useful={rf['useful_flops_ratio']:.2f} "
                    f"(compile {r['compile_s']}s probes {r['probe_s']}s)",
                    flush=True,
                )
            except Exception:
                failures += 1
                print(f"FAIL {tag}\n{traceback.format_exc()}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
