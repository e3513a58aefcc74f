"""hymba-1.5b [arXiv:2411.13676].

32L d_model=1600 25H (GQA kv=5) d_ff=5504, parallel attention + Mamba heads
per block, ssm_state=16. Attention uses sliding-window (1024) — Hymba keeps
3 global-attention layers; we use the SWA form uniformly (a departure from the
published model), which keeps decode sub-quadratic with a window-bounded KV
cache at any length.
"""
from repro.models.model import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="hymba-1.5b",
        family="hybrid",
        n_layers=32,
        d_model=1600,
        n_heads=25,
        n_kv_heads=5,
        d_ff=5504,
        vocab=32001,
        ssm_state=16,
        ssm_conv=4,
        ssm_expand=2,
        sliding_window=1024,
        tie_embeddings=True,
    )


def reduced() -> ArchConfig:
    return config().replace(
        name="hymba-smoke",
        n_layers=2,
        d_model=80,
        n_heads=5,
        n_kv_heads=1,
        d_ff=128,
        vocab=257,
        ssm_state=8,
        sliding_window=16,
    )
