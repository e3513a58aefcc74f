"""Production training driver.

Wires together every substrate: arch config → mesh + logical sharding →
pjit train step → PAIO-instrumented data pipeline (foreground flow) and
async checkpointing (background flow, DRL-limited) → TrainIOControl feedback
loop → heartbeat/straggler monitor. Designed so the same entry point runs a
CPU smoke test and a 512-chip pod (mesh shape from flags).

  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_1b --steps 20 \
      --batch 8 --seq 128 --mesh 1x1 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import AsyncCheckpointer, CheckpointManager, latest_step
from repro.core import (
    BG_CHECKPOINT,
    FG_FETCH,
    ControlPlane,
    DifferentiationRule,
    FlowSpec,
    HousekeepingRule,
    Stage,
    TrainIOControl,
)
from repro.data import DataPipeline, SyntheticTokenSource
from repro.distributed.sharding import sharding_rules
from repro.ft import HeartbeatMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import (
    TrainConfig,
    build_train_step,
    init_train_state,
    make_state_shardings,
    rules_for,
)
from repro.models.model import ArchConfig
from repro.optim import AdamWConfig, cosine_schedule
from repro.telemetry import StepTimer
import repro.configs as configs


def build_io_stage(total_bandwidth: float = 512e6) -> tuple[Stage, ControlPlane]:
    """One stage for the job's I/O stack: fg fetches + bg checkpoint writes."""
    stage = Stage("train-io")
    for ch in ("fetch", "ckpt"):
        stage.hsk_rule(HousekeepingRule(op="create_channel", channel=ch))
    stage.hsk_rule(
        HousekeepingRule(
            op="create_object", channel="ckpt", object_id="0", object_kind="drl",
            params={"rate": total_bandwidth * 0.3},
        )
    )
    stage.dif_rule(DifferentiationRule(channel="fetch", match={"request_context": FG_FETCH}))
    stage.dif_rule(DifferentiationRule(channel="ckpt", match={"request_context": BG_CHECKPOINT}))
    algo = TrainIOControl(
        fg=FlowSpec("train-io", "fetch"),
        background=[FlowSpec("train-io", "ckpt")],
        total_bandwidth=total_bandwidth,
        loop_interval=0.2,
    )
    cp = ControlPlane(algo)
    cp.register_stage(stage)
    return stage, cp


@dataclasses.dataclass
class TrainRun:
    """What one ``train`` call leaves behind."""

    losses: list
    #: the final train state, placed with the mesh's state shardings
    state: Any
    #: the I/O stage's statistics (fetch and checkpoint channels) at the end
    io_stats: Any


def train(
    arch: Union[str, ArchConfig],
    steps: int = 20,
    batch: int = 8,
    seq: int = 128,
    mesh_shape: tuple = (1, 1),
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    microbatches: int = 1,
    lr: float = 3e-4,
    resume: bool = False,
    log_every: int = 1,
    reduced: bool = False,
    host: str = "host0",
    seed: int = 0,
) -> TrainRun:
    """Train ``arch`` (a config name, or an ``ArchConfig`` taken as is) for
    ``steps`` steps; ``seed`` draws the initial params and the token stream."""
    if isinstance(arch, ArchConfig):
        cfg = arch
    else:
        cfg = configs.get_reduced(arch) if reduced else configs.get(arch)
    mesh = make_mesh(mesh_shape)
    rules = rules_for(cfg, batch_size=batch, mesh=mesh)

    stage, cp = build_io_stage()
    cp.start()
    monitor = HeartbeatMonitor(dead_after=600.0)
    pipeline = DataPipeline(
        SyntheticTokenSource(vocab=cfg.vocab, batch=batch, seq=seq, seed=seed), stage=stage
    )
    ckpt_mgr = ckpt = None
    if ckpt_dir:
        ckpt_mgr = CheckpointManager(ckpt_dir, stage=stage)
        ckpt = AsyncCheckpointer(ckpt_mgr)

    tcfg = TrainConfig(
        microbatches=microbatches,
        opt=AdamWConfig(lr=lr),
        lr_schedule=cosine_schedule(lr, warmup=max(steps // 10, 1), total=steps),
    )

    with mesh, sharding_rules(mesh, rules):
        state_shardings = make_state_shardings(cfg, mesh, rules)
        step_fn = jax.jit(
            build_train_step(cfg, tcfg),
            in_shardings=(state_shardings, None),
            out_shardings=(state_shardings, None),
            donate_argnums=0,
        )
        # built in place, sharded: the whole state never lands on one device
        init_fn = jax.jit(lambda key: init_train_state(cfg, key), out_shardings=state_shardings)
        state = init_fn(jax.random.PRNGKey(seed))
        start_step = 0
        if resume and ckpt_mgr is not None and (last := latest_step(ckpt_dir)) is not None:
            target = jax.eval_shape(lambda: state)
            del state  # free the fresh state before the restored one lands
            state = ckpt_mgr.restore(last, target, shardings=state_shardings)
            start_step = last
            print(f"resumed from checkpoint step {last}")

        timer = StepTimer()
        losses = []
        for i in range(start_step, steps):
            tokens = pipeline.read_batch(i)
            timer.start()
            state, metrics = step_fn(state, {"tokens": jnp.asarray(tokens)})
            loss = float(metrics["loss"])
            dt = timer.stop()
            monitor.beat(host, dt)
            losses.append(loss)
            if i % log_every == 0:
                print(f"step {i:>5} loss {loss:.4f} grad_norm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
            if ckpt is not None and ckpt_every and (i + 1) % ckpt_every == 0:
                ckpt.save(i + 1, state)
        if ckpt is not None:
            ckpt.wait()

    stats = stage.collect()
    print(
        "io stats:",
        {n: f"{s.cumulative_bytes/2**20:.1f}MiB" for n, s in stats.per_channel.items() if s.cumulative_bytes},
    )
    cp.stop()
    return TrainRun(losses=losses, state=state, io_stats=stats)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1", help="e.g. 1x1, 4x2, 2x16x16")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--reduced", action="store_true", help="use the smoke-scale config")
    args = ap.parse_args()
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    enable_compile_cache()
    train(
        args.arch,
        steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        mesh_shape=mesh_shape,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        microbatches=args.microbatches,
        lr=args.lr,
        resume=args.resume,
        reduced=args.reduced,
    )


if __name__ == "__main__":
    main()
