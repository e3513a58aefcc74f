"""hubert-xlarge [arXiv:2106.07447].

48L d_model=1280 16H d_ff=5120 vocab=504 — bidirectional encoder-only;
the conv waveform frontend is a STUB (``input_specs`` provides precomputed
512-d frame embeddings). No decode or serve path: an encoder has no
autoregressive step.
"""
from repro.models.model import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="hubert-xlarge",
        family="audio",
        n_layers=48,
        d_model=1280,
        n_heads=16,
        n_kv_heads=16,
        d_ff=5120,
        vocab=504,
        causal=False,
        frontend="audio_stub",
        frontend_dim=512,
        tie_embeddings=False,
    )


def reduced() -> ArchConfig:
    return config().replace(
        name="hubert-smoke",
        n_layers=2,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        vocab=64,
        frontend_dim=32,
    )
