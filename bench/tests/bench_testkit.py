"""Shared helpers of the benchmark's CPU tests: a copy of the benchmark
at a small size, run in the test's own process."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def small_tree(tmp: Path, log2_rows: int = 16) -> Path:
    """A copy of ``bench/`` and ``BENCHMARK.json`` under ``tmp`` whose
    configurations hold ``2**log2_rows`` rows; returns its bench dir.
    ``src`` is linked, not copied."""
    dst = tmp / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    os.symlink(ROOT / "src", tmp / "src")
    for cfg in (dst / "configs").glob("*.json"):
        data = json.loads(cfg.read_text())
        data["params"]["log2_rows"] = log2_rows
        cfg.write_text(json.dumps(data))
    return dst


def run_cell(bench_dir: Path, workload: str, seed: int = 7, seconds: float = 0.5,
             trace: int = 0, fault=None, capsys=None) -> tuple:
    """(exit code, parsed last stdout line or None) of one run on the CPU."""
    import jax
    from yardstick.harness import main

    cache_on = jax.config.jax_enable_compilation_cache
    try:
        rc = main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_tpu=False, bench_dir=bench_dir, fault=fault,
                  compile_cache=False)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    if capsys is None:
        return rc, None
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
