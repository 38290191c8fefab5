"""One run of one cell: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

The cell, its configuration, its traffic mix and its metrics are found
by name: ``BENCHMARK.json`` at the checkout's root names them, and
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/generators/<generator>.py``, ``bench/loops/<loop>.py`` and
``bench/metrics/<metric>.py`` hold them.  Set-up runs from process start
to the window's first submit; the window lasts ``--seconds``; then the
answers sampled from the window are compared with the plain reference
(:mod:`yardstick.reference`), and the last line of standard output is
the result as one JSON object.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


def log(*parts) -> None:
    """A line of the run's account, on standard error."""
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """``bench/<kind>/<name>.py`` as a module."""
    path = bench_dir / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The cell's entry, configuration, traffic mix and metric lists."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(bench_dir.parent / configs[cell["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")

    def of_cell(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": of_cell(bench["end_to_end"]),
            "per_layer": of_cell(bench["per_layer"])}


def device_problem(chips: int):
    """A reason this machine cannot run the cell, or None."""
    import jax

    devs = jax.devices()
    found = f"{devs[0].platform} x {len(devs)} ({devs[0].device_kind})"
    if devs[0].platform != "tpu":
        return f"no TPU: JAX found {found}"
    if len(devs) < chips:
        return f"{chips} chips needed, JAX found {found}"
    return None


class Cell:
    """Everything a loop needs: the data, the index and how to serve it."""

    def __init__(self, spec: dict, seed: int, annotate):
        self.seed, self.annotate = int(seed), annotate
        self.config, self.traffic = spec["config"], spec["traffic"]
        self.server = None

    def make_data(self, bench_dir: Path) -> None:
        """The columns, on the device, from the seed, and their tile
        classes and container bytes, on the host."""
        import jax
        import numpy as np
        from yardstick import words

        gen = load_module("generators", self.config["generator"], bench_dir)
        t0 = time.perf_counter()
        self.cols = jax.block_until_ready(gen.generate(self.config["params"], self.seed))
        self.n_cols, self.n_words = map(int, self.cols.shape)
        self.r = self.n_words * 32
        t1 = time.perf_counter()
        tw = int(self.config["index"]["tile_words"])
        self.tile_cls, self.tile_bytes = words.column_tile_stats(self.cols, tw)
        census = {k: int(np.sum(self.tile_cls == v))
                  for k, v in (("zero", 0), ("one", 1), ("dirty", 2))}
        log(f"data: {self.n_cols} x {self.r} rows made in {t1 - t0} s; tile "
            f"classes {census} in {time.perf_counter() - t1} s")

    def build_index(self) -> None:
        from yardstick import serve

        t0 = time.perf_counter()
        self.idx = serve.build_index(self.cols, self.r, self.config["index"])
        self.names = self.idx.names
        import jax

        log(f"index built in {time.perf_counter() - t0} s; containers "
            f"{self.idx.store.container_census()}; device bytes in use "
            f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}")

    def start_server(self):
        from repro.serve import QueryServer

        self.server = QueryServer(self.idx, **self.config["server"]).start()
        return self.server


def configure_cache(enabled: bool, root: Path) -> None:
    """JAX's persistent compilation cache on, at a fixed path inside the
    checkout, or off (the harness's own tests write none)."""
    import jax

    jax.config.update("jax_enable_compilation_cache", bool(enabled))
    if enabled:
        path = root / ".bench_cache" / "jax"
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def profile_options():
    """Device operations in full; on the host only annotations (the
    benchmark's own and the program's), and no HLO text, so a trace of
    the whole window stays small."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def needed_by_backend(cell: Cell, batches) -> dict:
    """Bytes the window's batches need, split by the backend the planner
    gave each query ({backend: bytes})."""
    from yardstick import needed, serve

    memo, out = {}, {}
    for batch in batches:
        if batch not in memo:
            algs = serve.plans(cell.idx, batch)
            per = {}
            for alg in set(algs):
                part = [q for q, a in zip(batch, algs) if a == alg]
                per[alg] = needed.needed_bytes(cell.tile_cls, cell.tile_bytes,
                                               part, cell.n_words)
            memo[batch] = per
        for alg, b in memo[batch].items():
            out[alg] = out.get(alg, 0) + b
    return out


def compare(cols, sample_items) -> dict:
    """Words that differ between the sampled answers and the reference's,
    and how many answers were compared (a failed query has no answer; it
    is counted as failed instead)."""
    import jax.numpy as jnp
    from yardstick.reference import DeviceReference

    ref = DeviceReference(cols)
    wrong = checked = 0
    for descs, results in sample_items:
        for q, got in zip(descs, results):
            if got is not None:
                wrong += int(jnp.count_nonzero(jnp.asarray(got) != ref.answer(q)))
                checked += 1
    return {"wrong_words": wrong, "checked": checked}


def check_lines(wrong: int, failed: int, checked: int) -> dict:
    """Each compared number beside its limit."""
    return {
        "wrong_words": {"value": wrong, "limit": 0},
        "failed_queries": {"value": failed, "limit": 0},
        "answers_checked": {"value": checked, "at_least": 1},
    }


def is_correct(check: dict) -> bool:
    return (check["wrong_words"]["value"] <= check["wrong_words"]["limit"]
            and check["failed_queries"]["value"] <= check["failed_queries"]["limit"]
            and check["answers_checked"]["value"] >= check["answers_checked"]["at_least"])


def main(argv=None, *, t_process: float | None = None, require_tpu: bool = True,
         bench_dir: Path = BENCH_DIR, fault=None, compile_cache: bool = True) -> int:
    """Run one cell; returns the exit code.  ``require_tpu=False``,
    ``fault`` (called with the index before the window) and
    ``compile_cache=False`` are for the harness's own tests."""
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_TILED_ENGINE"):
        print("REPRO_TILED_ENGINE is set: the planner must choose the engine",
              file=sys.stderr)
        return 2
    bench = load_json(bench_dir.parent / "BENCHMARK.json")
    spec = resolve(bench, args.workload, bench_dir)
    chips = int(spec["cell"]["chips"])
    if require_tpu:
        problem = device_problem(chips)
        if problem:
            print(problem, file=sys.stderr)
            return 1
    sys.path.insert(0, str(bench_dir.parent / "src"))
    import jax
    import numpy as np

    configure_cache(compile_cache, bench_dir.parent)
    kind = jax.devices()[0].device_kind
    peaks = load_json(bench_dir / "peaks.json")["devices"].get(kind)
    if peaks is None and require_tpu:
        print(f"no peaks for device kind {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 1
    from yardstick.compilelog import CompileLog
    from yardstick.serve import Reservoir

    annotate = jax.profiler.TraceAnnotation
    cell = Cell(spec, args.seed, annotate)
    with CompileLog() as clog:
        cell.make_data(bench_dir)
        cell.build_index()
        if fault is not None:
            fault(cell)
        loop = load_module("loops", spec["traffic"]["loop"], bench_dir)
        t0, n0 = time.perf_counter(), len(clog.events)
        state = loop.prepare(cell)
        warm = clog.events[n0:]
        log(f"warm-up: {time.perf_counter() - t0} s, {len(warm)} compilations "
            f"({sum(e[1] for e in warm)} s); compile cache {clog.hits} hits, "
            f"{clog.misses} misses; device bytes in use "
            f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}")
        sample = Reservoir(int(spec["traffic"]["sample_batches"]),
                           np.random.default_rng([args.seed, 99]))
        spans, trace_dir = [], None
        if args.trace:
            import repro.obs as obs
            from repro.obs import trace as obs_trace

            obs.enable()
            obs_trace.add_root_listener(spans.append)
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
        t_setup = time.perf_counter() - t_process
        with annotate("bench:window"):
            win = loop.run(state, args.seconds, sample)
        if args.trace:
            jax.profiler.stop_trace()
            obs.disable()
        compiles = clog.between(win["t_start"], win["t_end"])
    if compiles:
        top = sorted(compiles, key=lambda c: -c[1])[:8]
        log(f"compilations in the window: {len(compiles)}, longest "
            f"{[(name, secs) for _t, secs, name in top]}")
    devices = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    from yardstick import stats, tracereduce

    window_s = win["t_end"] - win["t_start"]
    answered, attempted = win["answered"], win["attempted"]
    record = {
        "setup_s": t_setup, "window_s": window_s, "answered": answered,
        "attempted": attempted, "latencies": stats.latencies(win["requests"]),
        "compiles": compiles,
        "spans": [s for s in spans if s.t0 >= win["t_start"]],
        "trace": None, "needed_bytes": needed_by_backend(cell, win["batches"]),
        "peaks": peaks,
    }
    if trace_dir is not None:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        record["trace"] = (tracereduce.reduce_trace(tracereduce.load_xplane(files[-1]))
                           if files else None)
        shutil.rmtree(trace_dir, ignore_errors=True)

    if cell.server is not None:
        cell.server.stop()
    cols, items = cell.cols, sample.items
    del cell, state, loop
    got = compare(cols, items)
    check = check_lines(got["wrong_words"], attempted - answered, got["checked"])
    correct = is_correct(check)

    metrics = {}
    for m in spec["per_layer" if args.trace else "end_to_end"]:
        value = load_module("metrics", m["name"], bench_dir).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted,
              "failed": attempted - answered, "metrics": metrics, "device": device}
    if args.trace and record["trace"] is not None:
        tr = record["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": [list(x) for x in tr["device_ops"]],
                               "idle_gaps": tr["idle_gaps"]}
    result["check"] = check
    for name, c in check.items():
        bound = ", ".join(f"{k} {v}" for k, v in c.items() if k != "value")
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
