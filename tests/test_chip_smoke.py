"""CPU rehearsal of ``chip_smoke.py``'s phases at a tiny size.

The smoke's build, serve and reference-check functions run here in
process on the CPU (Pallas in interpret mode), and its four-chip phase on
four virtual CPU devices in a subprocess.  ``main()``'s device check stays
strict: off the TPU the script must exit non-zero and print no result.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_generator_shapes_and_clean_fractions(smoke):
    cols, names, uni, clu = smoke.make_columns(3, 16)
    assert cols.shape == (64, 1 << 11) and cols.dtype == np.uint32
    assert len(set(names)) == 64 and names == uni + clu
    idx = smoke.build_index(cols, names, 1 << 16)
    slot = {n: i for i, n in enumerate(names)}
    st_u = idx.store.member_stats([slot[m] for m in uni])
    st_c = idx.store.member_stats([slot[m] for m in clu])
    assert st_u.clean_fraction == 0.0 and abs(st_u.density - 5 / 32) < 0.01
    assert st_c.clean_fraction >= 0.9
    census = idx.store.container_census([slot[m] for m in clu])
    assert census["sparse"] and census["run"] and census["dense"]


def test_reference_counter_matches_unpacked_counts(smoke):
    rng = np.random.default_rng(0)
    cols = rng.integers(0, 2**32, size=(13, 96), dtype=np.uint32)
    counts = np.unpackbits(cols.view(np.uint8), bitorder="little").reshape(
        13, -1).sum(0)
    planes = smoke.count_planes(cols, chunk=40)
    for t in (-1, 0, 1, 5, 13, 14, 16):
        got = np.unpackbits(
            smoke.count_at_least(planes, t).view(np.uint8), bitorder="little"
        ).astype(bool)
        np.testing.assert_array_equal(got, counts >= t, err_msg=f"t={t}")


def test_one_chip_phases_rehearse_on_cpu(smoke, capsys):
    with smoke.CompileLog() as clog:
        idx, phases = smoke.run_one_chip(0, 18, clog)
    out = capsys.readouterr().out
    assert "every result bit-identical to the numpy word-parallel counter" in out
    assert "errors 0" in out and "compilations inside: 0" in out
    assert "plan c_thr8: tiled_fused" in out
    # the default seed gives the scan engine's block grid work to do (on
    # the chip it runs as a Pallas grid; here as the XLA scan)
    _label, info = smoke.block_grid_query(idx, phases)
    assert info["engine"] == "scan" and info["kernel"] == "xla"


def test_four_chip_phase_rehearses_on_virtual_devices():
    script = (
        "import importlib.util, jax\n"
        "assert len(jax.devices()) == 4\n"
        f"spec = importlib.util.spec_from_file_location('s', {str(SMOKE)!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "modes = m.run_sharded(2, 15, 4)\n"
        "assert modes['clustered'] == ['per_shard'], modes\n"
        "print('modes', modes)\n"
    )
    env = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr[-3000:]
    assert "shard i's result on device i" in res.stdout
    assert "block i on device i for 4 blocks" in res.stdout


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_smoke_refuses_without_a_tpu(where, tmp_path):
    """Off the TPU, or without the rest of the repository, the script
    exits non-zero and prints no result line."""
    script = SMOKE
    if where == "alone":
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SMOKE, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, str(script)], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
