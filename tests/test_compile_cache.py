"""Where ``repro.compile_cache.enable_compile_cache`` keeps compiled code.

Each case runs a fresh CPU process twice: the first writes cache entries,
the second must find them.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
the only directory written; otherwise the repository's ``.jax_cache`` is
(redirected here to a temporary directory, so the test leaves no entries
in the checkout).
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
import repro.compile_cache as cc
cc.DEFAULT_CACHE_DIR = Path(sys.argv[1])
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None
)
print("dir", cc.enable_compile_cache())
jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)))
print("hits", len(hits))
"""


def test_default_cache_dir_is_in_the_repository():
    from repro.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


@pytest.mark.parametrize("placed", ["env", "default"])
def test_cache_entries_land_in_one_directory_and_hit(placed, tmp_path):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if placed == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    want, other = (env_dir, default_dir) if placed == "env" else (default_dir, env_dir)
    outs = []
    for _ in range(2):
        res = subprocess.run(
            [sys.executable, "-c", SCRIPT, str(default_dir)], env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert res.returncode == 0, res.stderr[-3000:]
        outs.append(res.stdout.split())
    assert outs[0][:2] == ["dir", str(want)]
    assert any(want.iterdir()) and not other.exists()
    assert outs[0][-1] == "0" and int(outs[1][-1]) > 0
