"""The harness is driven by data: every name resolves to a file, names
and units keep to the allowed characters, and a new configuration,
traffic mix or metric is picked up from new files alone (CPU)."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_testkit import BENCH, ROOT, run_cell, small_tree
from yardstick import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _metrics():
    return BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


@pytest.mark.parametrize("workload", CELLS)
def test_every_file_of_a_cell_is_found_by_name(workload):
    spec = harness.resolve(BENCHMARK, workload)
    gen = harness.load_module("generators", spec["config"]["generator"])
    loop = harness.load_module("loops", spec["traffic"]["loop"])
    assert callable(gen.generate)
    assert all(callable(getattr(loop, f)) for f in ("queries", "prepare", "run"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_names_units_and_keys_keep_to_the_contract():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4)
    for m in _metrics():
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for text in ([c["why"] for c in BENCHMARK["configs"] + BENCHMARK["workloads"]]
                 + [m["layer"] for m in BENCHMARK["per_layer"]]
                 + [c["source"] for c in BENCHMARK["configs"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    names = [m["name"] for m in _metrics()]
    assert len(names) == len(set(names)) and len(CELLS) == len(set(CELLS))


def test_each_layer_metric_moves_a_metric_its_cells_report():
    for m in BENCHMARK["per_layer"]:
        for cell in m.get("workloads", CELLS):
            spec = harness.resolve(BENCHMARK, cell)
            assert m["moves"] in [e["name"] for e in spec["end_to_end"]], (m, cell)


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_config_mix_and_metric_need_only_new_files(tmp_path, capsys):
    bench_dir = small_tree(tmp_path)
    before = _digest(bench_dir)
    cfg = json.loads((bench_dir / "configs" / "paper_uniform.json").read_text())
    cfg["name"] = "tiny_uniform"
    cfg["params"]["n_bitmaps"] = 12
    (bench_dir / "configs" / "tiny_uniform.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "adhoc.json").read_text())
    mix.update(sizes=[[4, 2], [5, 3], [3, 3]], stream_length=4096)
    (bench_dir / "traffic" / "triple.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "answered_queries.py").write_text(
        "def read(rec):\n    return rec['answered']\n")
    data = json.loads((tmp_path / "BENCHMARK.json").read_text())
    data["configs"].append({"name": "tiny_uniform", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny_uniform.json", "why": "test"})
    data["workloads"].append({"name": "tiny_triple", "config": "tiny_uniform",
                              "traffic": "triple", "chips": 1, "why": "test"})
    data["per_layer"].append({"name": "answered_queries", "unit": "queries",
                              "better": "higher", "source": "program_counter",
                              "layer": "front end", "moves": "qps",
                              "workloads": ["tiny_triple"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    after = _digest(bench_dir)
    assert all(after[p] == d for p, d in before.items())
    rc, line = run_cell(bench_dir, "tiny_triple", trace=1, capsys=capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["answered_queries"]["value"] == line["attempted"] > 0
    assert list(line)[-1] == "check"


def _run_py(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "REPRO_TILED_ENGINE"}
    env.update({"JAX_PLATFORMS": "cpu"}, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_non_zero_off_the_tpu_and_names_the_device():
    got = _run_py(ROOT, {})
    assert got.returncode != 0 and got.stdout.strip() == ""
    assert "no TPU" in got.stderr and "cpu" in got.stderr


def test_run_refuses_a_forced_tiled_engine():
    got = _run_py(ROOT, {"REPRO_TILED_ENGINE": "merge"})
    assert got.returncode == 2 and got.stdout.strip() == ""


def test_run_fails_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    code = ("import sys; sys.path.insert(0, 'bench'); "
            "from yardstick.harness import main; "
            f"sys.exit(main(['--workload', '{CELLS[0]}', '--seed', '1', "
            "'--seconds', '1'], require_tpu=False))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    got = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert got.returncode != 0 and got.stdout.strip() == ""
    assert "repro" in got.stderr
