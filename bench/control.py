#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--queries 16]

For each seed it makes the cell's data at the cell's own size, takes the
first ``--queries`` of the window's query stream, and puts in the
program's place the reference with one guarantee broken: every
symmetric leaf loses its last member.  The
comparison must read not correct.  Prints one line per seed with each
compared number beside its limit.
"""
import argparse
import json
import sys
from pathlib import Path


def main(argv=None, *, bench_dir: Path | None = None, require_tpu: bool = True) -> list:
    here = Path(__file__).resolve().parent
    sys.path[:0] = [str(here), str(here.parent / "src")]
    from yardstick import harness
    from yardstick.reference import DeviceReference

    bench_dir = bench_dir or harness.BENCH_DIR
    ap = argparse.ArgumentParser(description="Run the comparison's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=16)
    args = ap.parse_args(argv)
    spec = harness.resolve(harness.load_json(bench_dir.parent / "BENCHMARK.json"),
                           args.workload, bench_dir)
    if require_tpu:
        problem = harness.device_problem(int(spec["cell"]["chips"]))
        if problem:
            raise SystemExit(problem)
    loop = harness.load_module("loops", spec["traffic"]["loop"], bench_dir)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(spec, seed, None)
        cell.make_data(bench_dir)
        descs = loop.queries(cell)[:args.queries]
        control = DeviceReference(cell.cols, drop_last_member=True)
        items = [(tuple(descs), [control.answer(q) for q in descs])]
        got = harness.compare(cell.cols, items)
        check = harness.check_lines(got["wrong_words"], 0, got["checked"])
        line = {"workload": args.workload, "seed": seed,
                "correct": harness.is_correct(check), "check": check}
        print(json.dumps(line), flush=True)
        out.append(line)
        del cell, control, items
    return out


if __name__ == "__main__":
    main()
