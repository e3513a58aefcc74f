"""Model-layer correctness: attention cores, MLA, MoE, SSM, xLSTM, decode
consistency. Complements the per-arch smoke tests."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import forward, init_caches, init_params
from repro.models.attention import MaskSpec, attn_core
from repro.models.common import apply_rope
from repro.models.ssm import _ssm_scan_parallel
from repro.models.xlstm import mlstm_core


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


class TestAttentionCores:
    def _mask(self, b, s, causal=True, sw=0):
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        return MaskSpec(pos, pos, causal, sw)

    @pytest.mark.parametrize("chunk", [16, 32, 64])
    @pytest.mark.parametrize("causal,sw", [(True, 0), (False, 0), (True, 24)])
    def test_chunked_equals_xla(self, chunk, causal, sw):
        b, s, h, d = 2, 64, 4, 16
        q, k, v = _rand(0, (b, s, h, d)), _rand(1, (b, s, h, d)), _rand(2, (b, s, h, d))
        mask = self._mask(b, s, causal, sw)
        ref = attn_core(q, k, v, mask, d**-0.5, backend="xla")
        out = attn_core(q, k, v, mask, d**-0.5, backend="chunked", chunk=chunk)
        np.testing.assert_allclose(np.array(out), np.array(ref), atol=2e-5, rtol=2e-5)

    def test_rope_preserves_norm_and_relativity(self):
        b, s, h, d = 1, 32, 2, 16
        x = _rand(6, (b, s, h, d))
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        rx = apply_rope(x, pos)
        # rotation preserves norms
        np.testing.assert_allclose(
            np.linalg.norm(np.array(rx), axis=-1), np.linalg.norm(np.array(x), axis=-1), rtol=1e-5
        )
        # inner products depend only on relative offset
        q = apply_rope(x, pos)
        k = apply_rope(x, pos + 5)  # shift both positions
        dots1 = np.einsum("bshd,bshd->bsh", np.array(q), np.array(k))
        q2 = apply_rope(x, pos + 11)
        k2 = apply_rope(x, pos + 16)
        dots2 = np.einsum("bshd,bshd->bsh", np.array(q2), np.array(k2))
        np.testing.assert_allclose(dots1, dots2, rtol=1e-4, atol=1e-4)

    def test_partial_rope_leaves_tail_untouched(self):
        x = _rand(7, (1, 8, 1, 16))
        pos = jnp.arange(8, dtype=jnp.int32)[None]
        rx = apply_rope(x, pos, fraction=0.5)
        np.testing.assert_array_equal(np.array(rx[..., 8:]), np.array(x[..., 8:]))
        assert not np.allclose(np.array(rx[..., :8]), np.array(x[..., :8]))


class TestSSM:
    def test_chunked_scan_matches_sequential(self):
        b, s, d, n = 2, 50, 8, 4
        rng = np.random.default_rng(0)
        u = jnp.array(rng.normal(size=(b, s, d)), jnp.float32)
        dt = jnp.array(np.abs(rng.normal(size=(b, s, d))) * 0.1 + 0.01, jnp.float32)
        a = jnp.array(np.abs(rng.normal(size=(d, n))) + 0.5, jnp.float32)
        bm = jnp.array(rng.normal(size=(b, s, n)), jnp.float32)
        cm = jnp.array(rng.normal(size=(b, s, n)), jnp.float32)

        # sequential reference
        h = np.zeros((b, d, n))
        ys = []
        for t in range(s):
            da = np.exp(np.array(dt[:, t])[..., None] * -np.array(a))
            db = np.array(dt[:, t])[..., None] * np.array(bm[:, t])[:, None, :] * np.array(u[:, t])[..., None]
            h = h * da + db
            ys.append(np.einsum("bdn,bn->bd", h, np.array(cm[:, t])))
        ref = np.stack(ys, axis=1)

        for chunk in (8, 16, 64):
            y, h_last = _ssm_scan_parallel(u, dt, a, bm, cm, chunk=chunk)
            np.testing.assert_allclose(np.array(y), ref, rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.array(h_last), h, rtol=2e-4, atol=2e-4)


class TestMLSTM:
    def test_chunk_invariance_and_state_carry(self):
        b, h, s, dh = 1, 2, 48, 8
        q, k, v = _rand(10, (b, h, s, dh)), _rand(11, (b, h, s, dh)), _rand(12, (b, h, s, dh))
        li = _rand(13, (b, h, s))
        lf = _rand(14, (b, h, s)) + 2.0
        ref, _ = mlstm_core(q, k, v, li, lf, None, chunk=48)
        for chunk in (8, 16, 24):
            out, _ = mlstm_core(q, k, v, li, lf, None, chunk=chunk)
            np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-3, atol=2e-3)
        # split into two halves with carried state == single pass
        out1, st = mlstm_core(q[:, :, :24], k[:, :, :24], v[:, :, :24], li[:, :, :24], lf[:, :, :24], None, chunk=8)
        out2, _ = mlstm_core(q[:, :, 24:], k[:, :, 24:], v[:, :, 24:], li[:, :, 24:], lf[:, :, 24:], st, chunk=8)
        glued = jnp.concatenate([out1, out2], axis=2)
        np.testing.assert_allclose(np.array(glued), np.array(ref), rtol=2e-3, atol=2e-3)


class TestMoE:
    def test_moe_routes_all_tokens_with_high_capacity(self):
        from repro.models.moe import apply_moe, init_moe

        d, e, k = 16, 4, 2
        p = jax.tree_util.tree_map(lambda a: a[0] if False else a, init_moe(jax.random.PRNGKey(0), 1, d, e, 32))
        p1 = jax.tree_util.tree_map(lambda a: a[0], p)  # layer slice
        x = _rand(20, (2, 32, d))
        out, aux, _ = apply_moe(p1, x, k, capacity_factor=8.0, group_size=16)
        assert out.shape == x.shape
        assert np.isfinite(np.array(out)).all() and np.isfinite(float(aux))
        # aux loss lower bound: balanced routing gives e/k * k/e... ≈ 1
        assert float(aux) >= 0.9

    def test_moe_capacity_drops_degrade_gracefully(self):
        from repro.models.moe import apply_moe, init_moe

        d, e, k = 16, 4, 2
        p1 = jax.tree_util.tree_map(lambda a: a[0], init_moe(jax.random.PRNGKey(0), 1, d, e, 32))
        x = _rand(21, (2, 32, d))
        out_hi, _, _ = apply_moe(p1, x, k, capacity_factor=8.0, group_size=16)
        out_lo, _, _ = apply_moe(p1, x, k, capacity_factor=0.5, group_size=16)
        # low capacity drops tokens (outputs differ) but stays finite
        assert np.isfinite(np.array(out_lo)).all()
        assert not np.allclose(np.array(out_hi), np.array(out_lo))


class TestDecodeConsistency:
    @pytest.mark.parametrize(
        "arch", ["llama3_2_1b", "deepseek_v2_lite_16b", "hymba_1_5b", "xlstm_350m", "qwen3_4b"]
    )
    def test_prefill_plus_decode_equals_full(self, arch):
        cfg = configs.get_reduced(arch).replace(compute_dtype=jnp.float32, capacity_factor=8.0)
        params = init_params(cfg, jax.random.PRNGKey(0))
        b, s = 2, 20
        tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
        full, _, _ = forward(cfg, params, {"tokens": tokens})
        s0 = s - 3
        caches = init_caches(cfg, b, s, dtype=jnp.float32)
        pre, _, caches = forward(cfg, params, {"tokens": tokens[:, :s0]}, caches=caches, update_cache=True)
        scale = float(np.max(np.abs(np.array(full)))) + 1e-9
        assert np.max(np.abs(np.array(pre) - np.array(full[:, :s0]))) / scale < 2e-3
        for t in range(s0, s):
            step_batch = {"tokens": tokens[:, t : t + 1], "positions": jnp.full((b, 1), t, jnp.int32)}
            lg, _, caches = forward(cfg, params, step_batch, caches=caches, update_cache=True)
            err = np.max(np.abs(np.array(lg[:, 0]) - np.array(full[:, t]))) / scale
            assert err < 2e-3, f"{arch} step {t}: {err}"


class TestMLAForms:
    def test_absorbed_decode_equals_expanded(self):
        """MLA's absorbed decode form (latent-space attention) must match the
        expanded per-head form on a single decode step."""
        from repro.models.attention import apply_mla, init_mla, init_mla_cache

        d, h, lora, nope, rope, vdim = 32, 2, 16, 8, 4, 8
        p = jax.tree_util.tree_map(lambda a: a[0], init_mla(jax.random.PRNGKey(0), 1, d, h, lora, nope, rope, vdim))
        b, s = 2, 9
        x = _rand(30, (b, s, d))
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        kw = dict(qk_nope_dim=nope, qk_rope_dim=rope, v_head_dim=vdim)

        full, _ = apply_mla(p, x, pos, **kw)  # expanded over all s positions

        cache = init_mla_cache(b, s, lora, rope, jnp.float32)
        _, cache = apply_mla(p, x[:, : s - 1], pos[:, : s - 1], cache=cache, update_cache=True, **kw)
        step, _ = apply_mla(p, x[:, s - 1 :], pos[:, s - 1 :], cache=cache, update_cache=True, **kw)
        np.testing.assert_allclose(np.array(step[:, 0]), np.array(full[:, -1]), atol=2e-5, rtol=2e-5)


class TestKVCacheRing:
    def test_ring_overwrites_oldest_under_sliding_window(self):
        """Property: after writing T > window tokens one at a time, the cache
        holds exactly the last `window` positions."""
        from repro.models.attention import KVCache, apply_attention, init_attention, init_kv_cache

        d, h, window = 16, 2, 8
        p = jax.tree_util.tree_map(lambda a: a[0], init_attention(jax.random.PRNGKey(0), 1, d, h, h, d // h))
        b, total = 1, 13
        cache = init_kv_cache(b, window, h, d // h, jnp.float32)
        for t in range(total):
            x = _rand(40 + t, (b, 1, d))
            pos = jnp.full((b, 1), t, jnp.int32)
            _, cache = apply_attention(p, x, pos, sliding_window=window, cache=cache, update_cache=True)
        held = sorted(int(v) for v in np.array(cache.pos[0]))
        assert held == list(range(total - window, total))
