"""Batched serving with continuous batching and bitmap slot tracking.

    PYTHONPATH=src python examples/serve_batch.py [--arch recurrentgemma-2b]

Feeds a stream of variable-length prompts through the slot-pool engine;
slot occupancy is tracked with packed bitmaps (the paper's machinery in the
serving layer).  Prints per-request outputs and throughput.
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import init_params
from repro.serve import Request, ServeEngine


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch_slots=args.slots, max_seq=128)
    rng = np.random.default_rng(0)
    pending = [
        Request(rid=i, prompt=rng.integers(1, cfg.vocab, rng.integers(3, 12)).tolist(),
                max_new=args.max_new)
        for i in range(args.requests)
    ]
    print(f"{args.requests} requests -> {args.slots} slots ({args.arch} reduced)")
    t0 = time.time()
    done = engine.run_until_drained(list(pending))
    dt = time.time() - t0
    for r in sorted(done, key=lambda r: r.rid)[:6]:
        print(f"  rid {r.rid:2d}: prompt[{len(r.prompt)}] -> {r.out}")
    toks = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {toks} tokens, {engine.step_count} engine steps, "
          f"{toks / dt:.1f} tok/s")
    assert len(done) == args.requests
    print("OK")


if __name__ == "__main__":
    main()
