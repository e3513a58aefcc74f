"""Quickstart: PAIO data plane + a tiny transformer in ~60 lines.

Builds a stage with foreground/background channels, trains a reduced
llama-style model for a few steps with the input pipeline flowing through the
stage, checkpoints through a DRL-limited background channel, and prints the
per-flow I/O statistics the control plane would consume.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

from repro.launch.train import train


def main() -> None:
    with tempfile.TemporaryDirectory() as ckpt_dir:
        losses = train(
            "llama3_2_1b",
            reduced=True,  # smoke-scale config (the full 1.24B needs a pod)
            steps=12,
            batch=8,
            seq=64,
            ckpt_dir=ckpt_dir,
            ckpt_every=5,
        ).losses
    assert losses[-1] < losses[0], "loss should decrease"
    print(f"\nquickstart OK: loss {losses[0]:.3f} → {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
