"""End-to-end driver: train a ~100M-parameter LM with the full stack —
PAIO-instrumented pipeline, async DRL-limited checkpoints, TrainIOControl
feedback loop, cosine LR, resume-from-checkpoint.

Presets:
  --preset cpu   ~10M params, 40 steps  — runs on this CPU container (~min)
  --preset 100m  ~100M params, 300 steps — the assignment's e2e shape; run it
                 on real hardware (or be patient)

Run: PYTHONPATH=src python examples/train_lm_100m.py --preset cpu
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.launch.train import train
import repro.configs.llama3_2_1b as llama


def preset_config(name: str) -> tuple:
    if name == "cpu":
        cfg = llama.config().replace(
            name="lm-10m", n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024, vocab=8192
        )
        return cfg, dict(steps=40, batch=8, seq=128, lr=1e-3, ckpt_every=20)
    if name == "100m":
        cfg = llama.config().replace(
            name="lm-100m", n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048, vocab=32000
        )
        return cfg, dict(steps=300, batch=32, seq=512, lr=6e-4, ckpt_every=100)
    raise SystemExit(f"unknown preset {name}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="cpu", choices=["cpu", "100m"])
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg, run_kw = preset_config(args.preset)
    if args.steps:
        run_kw["steps"] = args.steps

    n_params = cfg.total_params()
    print(f"training {cfg.name}: ~{n_params/1e6:.0f}M params, {run_kw['steps']} steps")
    losses = train(
        cfg,
        ckpt_dir=args.ckpt_dir,
        resume=args.resume,
        log_every=5,
        **run_kw,
    ).losses
    print(f"\nloss {losses[0]:.3f} → {losses[-1]:.3f} over {len(losses)} steps")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
