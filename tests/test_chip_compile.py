"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at a real width for one chip of a
described ``v5e:2x2`` topology and compiles it with the TPU compiler, which
refuses what the interpreter accepts (illegal block shapes, too much VMEM).
A compiled kernel shows up as a ``tpu_custom_call`` in the program text.

The topology is described inside a fixture, never at import: only one process
may load the TPU library at a time, and every test worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no described chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_quantize_rows_compiles(one_chip, no_persistent_cache):
    """The checkpoint path's kernel: one scale per 256-element block row."""
    from repro.kernels.quantize.ops import quantize_rows_int8

    compiled = _compile_for_chip(quantize_rows_int8, one_chip, _spec((8192, 256), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(2048, 8192), (128256, 2048)], ids=["ffn", "embed"])
def test_quantize_tile_roundtrip_compiles(one_chip, no_persistent_cache, shape):
    """Per-tile int8 quantize then dequantize at Llama-3.2-1B weight widths."""
    from repro.kernels.quantize.ops import dequantize_int8, quantize_int8

    def roundtrip(x):
        q, s, meta = quantize_int8(x)
        return dequantize_int8(q, s, meta)

    compiled = _compile_for_chip(roundtrip, one_chip, _spec(shape, jnp.float32))
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_quantize_2d_multi_column_tiles_compile(one_chip, no_persistent_cache):
    """The raw tile kernels with several column tiles per row of tiles."""
    from repro.kernels.quantize.kernel import dequantize_2d, quantize_2d

    def roundtrip(x):
        q, s = quantize_2d(x)
        return dequantize_2d(q, s)

    compiled = _compile_for_chip(roundtrip, one_chip, _spec((1024, 1024), jnp.float32))
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm_compiles(one_chip, no_persistent_cache, dtype):
    from repro.kernels.rmsnorm.ops import rms_norm_fused

    compiled = _compile_for_chip(
        rms_norm_fused, one_chip, _spec((8, 1024, 2048), dtype), _spec((2048,), dtype)
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "heads,kv_heads,head_dim", [(32, 8, 64), (32, 8, 128)], ids=["llama3.2-1b", "qwen3-4b"]
)
def test_flash_attention_compiles(one_chip, no_persistent_cache, heads, kv_heads, head_dim):
    from repro.kernels.flash_attention.ops import flash_attention

    b, s = 2, 2048
    compiled = _compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        one_chip,
        _spec((b, s, heads, head_dim), jnp.bfloat16),
        _spec((b, s, kv_heads, head_dim), jnp.bfloat16),
        _spec((b, s, kv_heads, head_dim), jnp.bfloat16),
    )
    assert "tpu_custom_call" in compiled.as_text()
