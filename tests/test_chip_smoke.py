"""chip_smoke.py off the chip, and the compile-cache placement it relies on.

The script must refuse to run without a TPU. Its phases are exercised here at
toy sizes on the CPU, with the kernels passed ``interpret=True``, so a change
that breaks the chip path shows up before any chip time is spent.
"""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_script(path: str, cwd: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the script finds the package itself
    return subprocess.run(
        [sys.executable, path], cwd=cwd, env=env, capture_output=True, text=True, timeout=120
    )


def _tiny_llama():
    import repro.configs as configs

    return configs.get_reduced("llama3_2_1b").replace(remat=True)


class TestDeviceGate:
    def test_exits_nonzero_without_a_tpu(self):
        proc = _run_script(SCRIPT, ROOT)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "platform=cpu" in proc.stdout  # the gate reported what it found
        assert "needs a TPU" in proc.stderr
        assert "[kernels]" not in proc.stdout  # no phase started

    def test_fails_alone_outside_a_checkout(self, tmp_path):
        lone = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, lone)
        proc = _run_script(str(lone), str(tmp_path))
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


class TestPhasesOnCpu:
    """The script's phases at toy sizes: the same checks, interpret-mode kernels."""

    def test_kernels(self):
        _load_chip_smoke().phase_kernels(
            0, rows=64, mat=(128, 256), norm_shape=(2, 16, 128), attn=(1, 128, 4, 2, 32), interpret=True
        )

    def test_train_checkpoint_and_quantized_save(self):
        _load_chip_smoke().phase_train(
            0, cfg=_tiny_llama(), steps=4, ckpt_every=2, batch=4, seq=32, interpret=True
        )

    def test_serve_two_tenants(self):
        _load_chip_smoke().phase_serve(
            0, cfg=_tiny_llama(), requests_per_tenant=2, batch=2, prompt_len=8, new_tokens=4
        )


class TestCompileCache:
    def test_follows_the_environment(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # JAX's own setting left alone

    def test_fixed_path_in_the_checkout_otherwise(self, monkeypatch):
        from repro.launch.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        before = jax.config.jax_compilation_cache_dir
        try:
            assert enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert enable_compile_cache() == want  # the same path every call
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_cache_dir_is_git_ignored(self):
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
