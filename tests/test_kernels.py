"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
in interpret mode (CPU executes the kernel body)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # property tests skip cleanly on containers without it
    from _hypothesis_stub import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_reference
from repro.kernels.quantize.kernel import dequantize_2d, quantize_2d
from repro.kernels.quantize.ops import dequantize_int8, quantize_int8
from repro.kernels.quantize.ref import dequantize_reference, quantize_reference


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,sq,sk,h,kh,d,causal,sw",
        [
            (2, 64, 64, 4, 2, 32, True, 0),
            (1, 128, 128, 8, 8, 64, True, 0),
            (2, 96, 96, 4, 1, 16, False, 0),  # MQA, bidirectional, pad blocks
            (1, 256, 256, 2, 2, 64, True, 64),  # sliding window
            (1, 64, 192, 4, 4, 32, False, 0),  # cross lengths
            (2, 40, 72, 2, 1, 8, True, 0),  # non-multiple-of-block shapes
        ],
    )
    def test_matches_reference(self, b, sq, sk, h, kh, d, causal, sw):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, sk, kh, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, sk, kh, d), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, sliding_window=sw, block_q=32, block_k=32, interpret=True)
        ref = flash_attention_reference(q, k, v, causal=causal, sliding_window=sw)
        np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
    def test_dtypes(self, dtype, atol):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (2, 64, 4, 32), dtype)
        k = jax.random.normal(ks[1], (2, 64, 2, 32), dtype)
        v = jax.random.normal(ks[2], (2, 64, 2, 32), dtype)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        ref = flash_attention_reference(q, k, v, causal=True)
        assert out.dtype == dtype
        np.testing.assert_allclose(
            np.array(out, np.float32), np.array(ref, np.float32), atol=atol, rtol=atol
        )

    def test_block_size_invariance(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.float32)
        outs = [
            np.array(flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True))
            for bq, bk in [(16, 16), (32, 64), (128, 128), (64, 32)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], atol=2e-5, rtol=2e-5)

    def test_matches_model_xla_core(self):
        """Kernel == the model's XLA attention core on aligned positions."""
        from repro.models.attention import MaskSpec, attn_core

        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        b, s, h, d = 2, 64, 4, 32
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        mask = MaskSpec(pos, pos, causal=True)
        ref = attn_core(q, k, v, mask, d**-0.5, backend="xla")
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5, rtol=2e-5)


class TestQuantize:
    def test_kernel_matches_reference_exactly(self):
        x = jnp.array(np.random.default_rng(0).normal(size=(256, 384)) * 5, jnp.float32)
        q, s = quantize_2d(x, interpret=True)
        qr, sr = quantize_reference(np.array(x))
        assert np.array_equal(np.array(q), np.array(qr))
        np.testing.assert_allclose(np.array(s), np.array(sr), rtol=1e-6)
        back = dequantize_2d(q, s, interpret=True)
        back_ref = dequantize_reference(qr, sr)
        np.testing.assert_allclose(np.array(back), back_ref, rtol=1e-6)

    @pytest.mark.parametrize("shape", [(1000,), (33, 77), (5, 17, 23), (256, 128), (1, 1)])
    def test_roundtrip_error_bound(self, shape):
        x = jnp.array(np.random.default_rng(1).normal(size=shape), jnp.float32)
        q, s, meta = quantize_int8(x, interpret=True)
        back = dequantize_int8(q, s, meta, interpret=True)
        assert back.shape == x.shape and back.dtype == x.dtype
        # per-block bound: err <= scale/2 + rounding slack; global bound via absmax
        bound = float(np.max(np.abs(np.array(x)))) / 127.0 * 1.01 + 1e-7
        assert float(np.max(np.abs(np.array(back) - np.array(x)))) <= bound

    @given(
        st.integers(min_value=1, max_value=40).map(lambda n: n * 7),
        st.floats(min_value=0.01, max_value=100.0),
    )
    @settings(max_examples=10, deadline=None)
    def test_roundtrip_property(self, n, scale_mag):
        x = jnp.array(np.random.default_rng(n).normal(size=(n,)) * scale_mag, jnp.float32)
        q, s, meta = quantize_int8(x, interpret=True)
        back = dequantize_int8(q, s, meta, interpret=True)
        bound = float(np.max(np.abs(np.array(x)))) / 127.0 * 1.01 + 1e-7
        assert float(np.max(np.abs(np.array(back) - np.array(x)))) <= bound

    def test_bf16_input(self):
        x = jnp.array(np.random.default_rng(2).normal(size=(128, 128)), jnp.bfloat16)
        q, s, meta = quantize_int8(x, interpret=True)
        back = dequantize_int8(q, s, meta, interpret=True)
        assert back.dtype == jnp.bfloat16


class TestRMSNorm:
    @pytest.mark.parametrize("shape", [(8, 64), (2, 16, 64), (3, 5, 32), (130, 48)])
    @pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)])
    def test_matches_model_rmsnorm(self, shape, dtype, atol):
        from repro.kernels.rmsnorm.ops import rms_norm_fused
        from repro.kernels.rmsnorm.ref import rmsnorm_reference

        x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
        scale = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), dtype) * 0.1 + 1.0
        out = rms_norm_fused(x, scale, interpret=True)
        ref = rmsnorm_reference(x, scale)
        assert out.dtype == dtype
        np.testing.assert_allclose(
            np.array(out, np.float32), np.array(ref, np.float32), atol=atol, rtol=atol
        )
