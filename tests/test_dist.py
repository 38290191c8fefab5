"""Distributed substrate tests.

These need multiple XLA devices; the device count is fixed at first jax
init, so each test runs a subprocess with XLA_FLAGS set to 8 host devices.
"""
import os
import subprocess
import sys


ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(script: str):
    res = subprocess.run(
        [sys.executable, "-c", script], env=ENV, capture_output=True, text=True, timeout=600
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    return res.stdout


def test_sharded_train_step_matches_single_device():
    _run(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.data import DataConfig, lm_batch
from repro.dist.context import ShardingRules, use_rules
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import batch_shardings, state_shardings
from repro.train import OptConfig, TrainConfig, init_train_state, make_train_step

cfg = get_config("qwen3-1.7b", reduced=True)
tc = TrainConfig(opt=OptConfig(peak_lr=1e-3))
dc = DataConfig(vocab=cfg.vocab, batch=8, seq=32)
batch = lm_batch(dc, 0)

# single-device reference
s0 = init_train_state(cfg, jax.random.PRNGKey(0))
s_ref, m_ref = jax.jit(make_train_step(cfg, tc))(s0, batch)

# sharded: 4-way data x 2-way model
mesh = make_host_mesh(data=4, model=2)
rules = ShardingRules(mesh, batch_axes=("data",))
with use_rules(rules), mesh:
    s1 = init_train_state(cfg, jax.random.PRNGKey(0))
    sh = state_shardings(s1, mesh, cfg)
    s1 = jax.tree.map(jax.device_put, s1, sh)
    step = jax.jit(make_train_step(cfg, tc),
                   in_shardings=(sh, batch_shardings(batch, mesh, 8)))
    s_sh, m_sh = step(s1, batch)
assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3, (m_ref, m_sh)
d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), s_ref["params"], s_sh["params"])
assert max(jax.tree.leaves(d)) < 5e-3, max(jax.tree.leaves(d))
print("sharded == single-device OK")
"""
    )


def test_moe_shard_map_matches_local():
    _run(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.data import arch_batch
from repro.dist.context import ShardingRules, use_rules
from repro.launch.mesh import make_host_mesh
from repro.models import forward, init_params

import dataclasses
cfg = dataclasses.replace(get_config("granite-moe-1b-a400m", reduced=True),
                          capacity_factor=8.0)
params = init_params(cfg, jax.random.PRNGKey(0))
batch = arch_batch(cfg, 4, 32, "train", seed=0)
h_local, _, aux_local = forward(params, cfg, batch)

mesh = make_host_mesh(data=4, model=2)
rules = ShardingRules(mesh, batch_axes=("data",))
with use_rules(rules), mesh:
    h_dist, _, aux_dist = jax.jit(lambda p, b: forward(p, cfg, b))(params, batch)
np.testing.assert_allclose(np.asarray(h_local), np.asarray(h_dist), atol=3e-3, rtol=1e-2)
print("moe shard_map == local OK", float(aux_local), float(aux_dist))
"""
    )


def test_int8_ring_allreduce():
    _run(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.compression import _ring_allreduce_int8, collective_bytes_saved
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(data=8, model=1)
xs = jnp.asarray(np.random.default_rng(0).normal(size=(8, 257)).astype(np.float32))
f = jax.jit(jax.shard_map(lambda x: _ring_allreduce_int8(x, "data", 8), mesh=mesh,
            in_specs=P("data", None), out_specs=P("data", None), check_vma=False))
out = np.asarray(f(xs))
expect = np.asarray(xs.sum(0))
rel = np.abs(out - expect[None]).max() / np.abs(expect).max()
assert rel < 0.05, rel
hlo = f.lower(xs).compile().as_text()
assert "s8" in hlo and "collective-permute" in hlo
acct = collective_bytes_saved(1_000_000, 8)
assert acct["fp32_psum_bytes"] / acct["int8_ring_bytes"] == 4.0
print("int8 ring OK rel_err", rel)
"""
    )


def test_error_feedback_converges():
    _run(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.dist.compression import ErrorFeedback, quantize_int8, dequantize_int8

# lossy reduce with EF: mean of quantised grads must track the true mean
ef = ErrorFeedback()
rng = np.random.default_rng(0)
true = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
acc_err = []
for step in range(50):
    g = {"w": true + 0.01 * jnp.asarray(rng.normal(size=(64,)).astype(np.float32))}
    red = ef.apply(g, lambda t: jax.tree.map(lambda x: dequantize_int8(*quantize_int8(x)), t))
    acc_err.append(float(jnp.abs(red["w"] - g["w"]).mean()))
# with EF the *accumulated* bias stays bounded (errors don't compound)
assert np.mean(acc_err[-10:]) < 0.05, acc_err[-5:]
print("error feedback OK")
"""
    )


def test_pipeline_parallel_matches_sequential():
    _run(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.dist.pipeline import pipeline_forward
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(data=1, model=1)
import jax.sharding
mesh = jax.make_mesh((8,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"])

rng = np.random.default_rng(0)
S = 8  # stages
stage_params = {"w": jnp.asarray(rng.normal(size=(S, 16, 16)).astype(np.float32) / 4)}
x = jnp.asarray(rng.normal(size=(4, 2, 16)).astype(np.float32))  # 4 microbatches

out = pipeline_forward(stage_fn, x, stage_params, mesh, axis_name="pod")
# sequential reference
ref = x
for s in range(S):
    ref = stage_fn({"w": stage_params["w"][s]}, ref)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("pipeline == sequential OK")
"""
    )


def test_elastic_checkpoint_reshard():
    _run(
        """
import jax, jax.numpy as jnp, tempfile
from repro.ckpt import CheckpointManager
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import state_shardings
from repro.train import init_train_state

cfg = get_config("qwen3-1.7b", reduced=True)
state = init_train_state(cfg, jax.random.PRNGKey(0))
with tempfile.TemporaryDirectory() as d:
    mesh_a = make_host_mesh(data=8, model=1)
    sh_a = state_shardings(state, mesh_a, cfg)
    state_a = jax.tree.map(jax.device_put, state, sh_a)
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(1, state_a)
    # restore onto a DIFFERENT mesh (elastic rescale 8x1 -> 2x4)
    mesh_b = make_host_mesh(data=2, model=4)
    sh_b = state_shardings(state, mesh_b, cfg)
    state_b = mgr.restore(1, state, sh_b)
    ok = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), state_a, state_b)
    assert all(jax.tree.leaves(ok))
print("elastic reshard OK")
"""
    )
