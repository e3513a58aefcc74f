"""Checkpointing: roundtrip, transformations, atomicity, async, PAIO
enforcement on the write path."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, CheckpointManager, latest_step
from repro.core import (
    BG_CHECKPOINT,
    DifferentiationRule,
    HousekeepingRule,
    RequestType,
    Stage,
    VirtualClock,
)


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {
            "w1": jax.random.normal(k, (64, 32), jnp.float32),
            "w2": jax.random.normal(k, (32,), jnp.float32),
            "emb": jax.random.normal(k, (100, 16), jnp.bfloat16),
        },
        "opt": {"step": jnp.asarray(7, jnp.int32)},
    }


def _assert_tree_equal(a, b, atol=0.0):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(
            np.array(x, np.float32), np.array(y, np.float32), atol=atol, rtol=0
        )


class TestCheckpointManager:
    def test_roundtrip_bitexact(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = _state()
        mgr.save(10, state)
        assert latest_step(str(tmp_path)) == 10
        restored = mgr.restore(10, jax.eval_shape(lambda: state))
        _assert_tree_equal(state, restored)

    def test_compressed_roundtrip(self, tmp_path):
        pytest.importorskip("zstandard", reason="zstandard not installed")
        mgr = CheckpointManager(str(tmp_path), transform="compress")
        state = _state()
        mgr.save(1, state)
        restored = mgr.restore(1, jax.eval_shape(lambda: state))
        _assert_tree_equal(state, restored)

    def test_quantized_roundtrip_error_bound(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), transform="quantize")
        state = {"w": jax.random.normal(jax.random.PRNGKey(0), (512, 64), jnp.float32)}
        mgr.save(2, state)
        restored = mgr.restore(2, jax.eval_shape(lambda: state))
        scale = float(np.max(np.abs(np.array(state["w"])))) / 127.0
        assert float(np.max(np.abs(np.array(restored["w"]) - np.array(state["w"])))) <= scale * 1.01
        # quantized checkpoint is ~4x smaller
        manifest = mgr.manifest(2)
        assert manifest["tensors"]["['w']"]["nbytes"] < state["w"].nbytes / 3

    def test_corruption_detected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = _state()
        mgr.save(3, state)
        # flip bytes in one shard
        d = os.path.join(str(tmp_path), "step_3")
        victim = [f for f in os.listdir(d) if f.endswith(".bin")][0]
        with open(os.path.join(d, victim), "r+b") as f:
            f.seek(0)
            f.write(b"\xff\xff\xff\xff\xff\xff\xff\xff")
        with pytest.raises(IOError, match="checksum"):
            mgr.restore(3, jax.eval_shape(lambda: state))

    def test_crash_mid_save_preserves_previous(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = _state()
        mgr.save(1, state)
        # simulate crash: a half-written .tmp dir for step 2
        os.makedirs(os.path.join(str(tmp_path), "step_2.tmp"))
        with open(os.path.join(str(tmp_path), "step_2.tmp", "partial.bin"), "wb") as f:
            f.write(b"garbage")
        assert latest_step(str(tmp_path)) == 1  # .tmp ignored
        restored = mgr.restore(1, jax.eval_shape(lambda: state))
        _assert_tree_equal(state, restored)

    def test_gc_keeps_last_k(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        state = _state()
        for s in (1, 2, 3, 4):
            mgr.save(s, state)
        steps = sorted(int(d.split("_")[1]) for d in os.listdir(str(tmp_path)) if d.startswith("step_"))
        assert steps == [3, 4]

    def test_async_checkpointer(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        ck = AsyncCheckpointer(mgr)
        state = _state()
        ck.save(5, state)
        ck.wait()
        restored = mgr.restore(5, jax.eval_shape(lambda: state))
        _assert_tree_equal(state, restored)

    def test_paio_stage_sees_checkpoint_traffic(self, tmp_path):
        clk = VirtualClock()
        stage = Stage("ckpt", clock=clk)
        stage.hsk_rule(HousekeepingRule(op="create_channel", channel="ckpt_writes"))
        stage.hsk_rule(
            HousekeepingRule(
                op="create_object", channel="ckpt_writes", object_id="0", object_kind="drl",
                params={"rate": 1e12},
            )
        )
        stage.dif_rule(
            DifferentiationRule(channel="ckpt_writes", match={"request_context": BG_CHECKPOINT})
        )
        mgr = CheckpointManager(str(tmp_path), stage=stage)
        state = _state()
        mgr.save(1, state)
        stats = stage.collect()
        total_bytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(jax.device_get(state)))
        assert stats.per_channel["ckpt_writes"].ops == len(jax.tree_util.tree_leaves(state))
        assert stats.per_channel["ckpt_writes"].bytes == total_bytes

    def test_drl_limits_checkpoint_bandwidth(self, tmp_path):
        """With a DRL rate of R bytes/s the save is paced: virtual time
        advances by ≈ total_bytes / R."""
        clk = VirtualClock()
        stage = Stage("ckpt", clock=clk)
        stage.hsk_rule(HousekeepingRule(op="create_channel", channel="ckpt_writes"))
        rate = 1e4  # 10 KB/s
        stage.hsk_rule(
            HousekeepingRule(
                op="create_object", channel="ckpt_writes", object_id="0", object_kind="drl",
                params={"rate": rate, "refill_period": 0.1},
            )
        )
        stage.dif_rule(
            DifferentiationRule(channel="ckpt_writes", match={"request_context": BG_CHECKPOINT})
        )
        mgr = CheckpointManager(str(tmp_path), stage=stage)
        state = _state()
        total = sum(l.nbytes for l in jax.tree_util.tree_leaves(jax.device_get(state)))
        t0 = clk.now()
        mgr.save(1, state)
        elapsed = clk.now() - t0
        burst = rate * 0.1  # initial bucket capacity passes unpaced
        expected = (total - burst) / rate
        assert elapsed == pytest.approx(expected, rel=0.2)


class TestTrainResume:
    def test_resume_restores_onto_the_state_shardings(self, tmp_path):
        """train() takes an ArchConfig as is, and a resumed run continues from
        the saved state, placed with the mesh's state shardings."""
        import repro.configs as configs
        from repro.launch.train import train

        cfg = configs.get_reduced("llama3_2_1b").replace(name="lm-resume-test", n_layers=1)
        kw = dict(batch=2, seq=16, ckpt_dir=str(tmp_path), ckpt_every=2, seed=3)
        first = train(cfg, steps=2, **kw)
        assert len(first.losses) == 2 and latest_step(str(tmp_path)) == 2
        resumed = train(cfg, steps=3, resume=True, **kw)
        assert len(resumed.losses) == 1 and np.isfinite(resumed.losses[0])
        # the optimizer step counter came back from the checkpoint
        assert int(resumed.state["opt"]["step"]) == 3
        embed = resumed.state["params"]["embed"]
        assert embed.sharding.spec == first.state["params"]["embed"].sharding.spec
