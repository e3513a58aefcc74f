"""Distributed correctness on 8 virtual devices (subprocess: the XLA host
device-count flag must be set before jax initializes — tests stay at 1 device).

Covers: sharded train step ≡ single-device step, decode sharding ≡ single
device, elastic checkpoint resharding across meshes.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO
    )
    assert proc.returncode == 0, f"subprocess failed:\nSTDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-4000:]}"
    return proc.stdout


pytestmark = pytest.mark.slow


class TestShardedTraining:
    def test_dp_tp_train_step_matches_single_device(self):
        run_with_devices(
            """
import jax, jax.numpy as jnp, numpy as np
import repro.configs as configs
from repro.distributed.sharding import sharding_rules
from repro.launch.mesh import make_mesh
from repro.launch.steps import (TrainConfig, build_train_step, init_train_state,
                                make_state_shardings, rules_for, make_batch_shardings)
from repro.optim import AdamWConfig

cfg = configs.get_reduced("llama3_2_1b").replace(compute_dtype=jnp.float32)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
tcfg = TrainConfig(opt=AdamWConfig(lr=1e-3))

# single device reference
state = init_train_state(cfg, jax.random.PRNGKey(0))
step = jax.jit(build_train_step(cfg, tcfg))
state_ref, m_ref = step(state, {"tokens": tokens})

# 4x2 mesh (DP=4, TP=2)
mesh = make_mesh((4, 2))
rules = rules_for(cfg, batch_size=8, mesh=mesh)
with mesh, sharding_rules(mesh, rules):
    shardings = make_state_shardings(cfg, mesh, rules)
    state2 = init_train_state(cfg, jax.random.PRNGKey(0))
    state2 = jax.device_put(state2, shardings)
    bspec = {"tokens": jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)}
    bshard = make_batch_shardings(cfg, mesh, bspec, rules)
    batch = jax.device_put({"tokens": tokens}, bshard)
    step2 = jax.jit(build_train_step(cfg, tcfg), in_shardings=(shardings, bshard),
                    out_shardings=(shardings, None))
    state_sh, m_sh = step2(state2, batch)

assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-4, (m_ref["loss"], m_sh["loss"])
ref_leaves = jax.tree_util.tree_leaves(state_ref["params"])
sh_leaves = jax.tree_util.tree_leaves(state_sh["params"])
worst = max(float(jnp.max(jnp.abs(a - jax.device_get(b)))) for a, b in zip(ref_leaves, sh_leaves))
assert worst < 5e-4, worst
print("DP+TP equivalence OK, worst param diff", worst)
"""
        )

    def test_moe_expert_parallel_lowers_with_all_to_all(self):
        run_with_devices(
            """
import jax, jax.numpy as jnp
import repro.configs as configs
from repro.distributed.sharding import sharding_rules
from repro.launch.mesh import make_mesh
from repro.launch.steps import (TrainConfig, build_train_step, init_train_state,
                                make_state_shardings, rules_for, make_batch_shardings)

cfg = configs.get_reduced("granite_moe_1b_a400m").replace(moe_group_size=16)
mesh = make_mesh((2, 4))  # experts sharded over model=4
rules = rules_for(cfg, batch_size=8, mesh=mesh)
with mesh, sharding_rules(mesh, rules):
    shardings = make_state_shardings(cfg, mesh, rules)
    state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), shardings)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    step = jax.jit(build_train_step(cfg, TrainConfig()), in_shardings=(shardings, None),
                   out_shardings=(shardings, None))
    state, metrics = step(state, {"tokens": tokens})
    import numpy as np
    assert np.isfinite(float(metrics["loss"]))
    txt = step.lower(state, {"tokens": tokens}).compile().as_text()
assert ("all-to-all" in txt) or ("all-gather" in txt), "no EP collectives found"
print("EP sharded MoE step OK; collectives present")
"""
        )

    def test_decode_sharded_matches_single_device(self):
        run_with_devices(
            """
import jax, jax.numpy as jnp, numpy as np
import repro.configs as configs
from repro.distributed.sharding import sharding_rules
from repro.launch.mesh import make_mesh
from repro.launch.steps import (build_decode_step, build_prefill_step,
                                make_state_shardings, make_cache_shardings, rules_for)
from repro.models import init_caches, init_params

cfg = configs.get_reduced("llama3_2_1b").replace(compute_dtype=jnp.float32)
params = init_params(cfg, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)

caches = init_caches(cfg, 8, 32, dtype=jnp.float32)
prefill = jax.jit(build_prefill_step(cfg))
tok_ref, _, caches = prefill(params, caches, {"tokens": tokens})
decode = jax.jit(build_decode_step(cfg))
tok2_ref, _, _ = decode(params, caches, {"tokens": tok_ref, "positions": jnp.full((8,1), 16, jnp.int32)})

mesh = make_mesh((4, 2))
rules = rules_for(cfg, decode=True, batch_size=8, mesh=mesh)
with mesh, sharding_rules(mesh, rules):
    pshard = make_state_shardings(cfg, mesh, rules)["params"]
    cshard = make_cache_shardings(cfg, mesh, rules)
    params_s = jax.device_put(params, pshard)
    caches_s = jax.device_put(init_caches(cfg, 8, 32, dtype=jnp.float32), cshard)
    prefill_s = jax.jit(build_prefill_step(cfg), in_shardings=(pshard, cshard, None),
                        out_shardings=(None, None, cshard))
    tok_s, _, caches_s = prefill_s(params_s, caches_s, {"tokens": tokens})
    decode_s = jax.jit(build_decode_step(cfg), in_shardings=(pshard, cshard, None),
                       out_shardings=(None, None, cshard))
    tok2_s, _, _ = decode_s(params_s, caches_s, {"tokens": tok_s, "positions": jnp.full((8,1), 16, jnp.int32)})

assert np.array_equal(np.array(tok_ref), np.array(jax.device_get(tok_s)))
assert np.array_equal(np.array(tok2_ref), np.array(jax.device_get(tok2_s)))
print("sharded decode (seq-parallel KV) matches single device")
"""
        )


class TestElasticResharding:
    def test_checkpoint_restores_onto_different_mesh(self, tmp_path):
        run_with_devices(
            f"""
import jax, jax.numpy as jnp, numpy as np
import repro.configs as configs
from repro.checkpoint import CheckpointManager
from repro.distributed.sharding import sharding_rules
from repro.launch.mesh import make_mesh
from repro.launch.steps import (TrainConfig, build_train_step, init_train_state,
                                make_state_shardings, rules_for)

cfg = configs.get_reduced("llama3_2_1b").replace(compute_dtype=jnp.float32)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
mgr = CheckpointManager({str(tmp_path)!r})

# train 2 steps on an 8x1 mesh, checkpoint
mesh_a = make_mesh((8, 1))
rules_a = rules_for(cfg, batch_size=8, mesh=mesh_a)
with mesh_a, sharding_rules(mesh_a, rules_a):
    sh_a = make_state_shardings(cfg, mesh_a, rules_a)
    state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), sh_a)
    step = jax.jit(build_train_step(cfg, TrainConfig()), in_shardings=(sh_a, None), out_shardings=(sh_a, None))
    for _ in range(2):
        state, m = step(state, {{"tokens": tokens}})
    mgr.save(2, state)
    loss_a = float(m["loss"])

# elastic rescale: resume on a 2x4 mesh (node loss → different parallelism)
mesh_b = make_mesh((2, 4))
rules_b = rules_for(cfg, batch_size=8, mesh=mesh_b)
with mesh_b, sharding_rules(mesh_b, rules_b):
    sh_b = make_state_shardings(cfg, mesh_b, rules_b)
    abstract = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
    state_b = mgr.restore(2, abstract, shardings=sh_b)
    step_b = jax.jit(build_train_step(cfg, TrainConfig()), in_shardings=(sh_b, None), out_shardings=(sh_b, None))
    state_b, m_b = step_b(state_b, {{"tokens": tokens}})

# continuing on the new mesh must match continuing on the old mesh
with mesh_a, sharding_rules(mesh_a, rules_a):
    state_a2, m_a2 = step(state, {{"tokens": tokens}})
assert abs(float(m_b["loss"]) - float(m_a2["loss"])) < 1e-4, (m_b["loss"], m_a2["loss"])
print("elastic reshard OK: step-3 loss matches across meshes", float(m_b["loss"]))
"""
        )

