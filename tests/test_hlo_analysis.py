"""Loop-aware HLO analyzer: dot flops x trip counts, collective accounting."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze_hlo


def test_scan_trip_count_multiplies_dot_flops():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    hlo = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    r = analyze_hlo(hlo)
    assert r["dot_flops"] == 7 * 2 * 64**3


def test_nested_scans_multiply():
    def g(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return jnp.tanh(ci @ ci), None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out @ x

    hlo = jax.jit(g).lower(jnp.ones((32, 32))).compile().as_text()
    r = analyze_hlo(hlo)
    assert r["dot_flops"] == (5 * 3 + 5 + 1) * 2 * 32**3


def test_traffic_excludes_fusion_bodies():
    def f(x):
        return jnp.tanh(x * 2.0 + 1.0).sum()

    hlo = jax.jit(f).lower(jnp.ones((256, 256))).compile().as_text()
    r = analyze_hlo(hlo)
    # elementwise chain fuses: traffic should be O(tensor), not O(ops x tensor)
    assert r["hbm_traffic_proxy"] < 12 * 256 * 256 * 4


def test_cost_analysis_undercounts_vs_loop_aware():
    """Documents WHY the analyzer exists: XLA counts scan bodies once."""
    def f(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=16)
        return out

    compiled = jax.jit(f).lower(jnp.ones((48, 48))).compile()
    ca = compiled.cost_analysis()
    xla_flops = ca.get("flops", 0.0)
    la = analyze_hlo(compiled.as_text())
    assert la["dot_flops"] == 16 * 2 * 48**3
    assert xla_flops < la["dot_flops"] / 4  # XLA undercounts


def test_collectives_in_loops(tmp_path):
    import subprocess
    import sys
    import os

    script = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=8, model=1)

def f(x):
    def body(c, _):
        return jax.lax.psum(c, "data") * 0.125, None
    out, _ = jax.lax.scan(body, x, None, length=5)
    return out

g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P(None),
                          check_vma=False))
hlo = g.lower(jnp.ones((1024,))).compile().as_text()
r = analyze_hlo(hlo)
assert r["collective_counts"]["all-reduce"] == 5, r["collective_counts"]
assert r["collective_bytes"]["all-reduce"] == 5 * 1024 * 4, r["collective_bytes"]
print("OK")
"""
    env = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}
    res = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True,
                         text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
