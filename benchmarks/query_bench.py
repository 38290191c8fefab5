"""Query-layer benchmark: what the unified API + tiled storage engine buy.

  * composed expression compiled as ONE circuit (shared sideways-sum adder)
    vs leaf-at-a-time execution with a bitwise combine afterwards;
  * ``execute_many`` batching k independent queries into one jitted
    multi-output call vs k sequential calls;
  * compiled-circuit cache: cold (build + optimise + jit) vs warm hit;
  * clean-fraction sweep: dense fused kernel vs the storage engine's
    ``tiled_fused`` executor at clean fractions {0.0, 0.5, 0.9, 0.99} --
    wall time AND words touched (the roofline term), written to
    ``BENCH_query.json`` so CI tracks the perf trajectory;
  * shard-count sweep (1/2/4/8 row shards, mixed-density data): wall time +
    per-shard backend + per-shard words touched, so the trajectory captures
    scaling efficiency of the sharded engine, not just single-device
    numbers.  With >= 8 XLA devices the 8-shard point runs on a real mesh.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.query import (
    And,
    BitmapIndex,
    Interval,
    Not,
    Parity,
    Threshold,
    clear_compiled_cache,
)

CLEAN_FRACTIONS = (0.0, 0.5, 0.9, 0.99)
SHARD_COUNTS = (1, 2, 4, 8)
DENSITIES = (1e-4, 1e-3, 1e-2, 0.1, 0.5)


def _time(fn, reps=5):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _clean_fraction_bits(n, n_tiles, clean_fraction, seed=0, span=64 * 32):
    rng = np.random.default_rng(seed)
    r = n_tiles * span
    bits = np.zeros((n, r), bool)
    for i in range(n):
        for tj in range(n_tiles):
            u = rng.random()
            lo, hi = tj * span, (tj + 1) * span
            if u < clean_fraction / 2:
                pass
            elif u < clean_fraction:
                bits[i, lo:hi] = True
            else:
                bits[i, lo:hi] = rng.random(span) < 0.35
    return bits


def clean_fraction_sweep(smoke: bool = False) -> list:
    """Dense fused vs tiled_fused: wall time + words touched per backend,
    PLUS the plan the cost model actually picks at each point -- the sweep
    otherwise cannot show whether the launch-overhead pricing steers
    production away from tiled_fused in the regimes where it loses on wall
    time (e.g. 3868us vs 80us fused at cf=0.5 in smoke data)."""
    n, n_tiles = (8, 8) if smoke else (16, 48)
    sweep = []
    for cf in CLEAN_FRACTIONS:
        bits = _clean_fraction_bits(n, n_tiles, cf, seed=int(cf * 100) + 1)
        idx = BitmapIndex.from_dense(jnp.asarray(bits))
        q = Threshold(n // 2)
        plan = idx.explain(q)  # what production would run at this point
        dense_words = idx.n * idx.n_words + idx.n_words  # N reads + 1 write
        t_fused = _time(
            lambda: idx.execute(q, backend="fused").block_until_ready()
        )
        t_tiled = _time(lambda: idx.execute(q, backend="tiled_fused"))
        info = idx.last_info
        tiled_words = info["dirty_words_gathered"] + idx.n_words
        sweep.append(
            {
                "clean_fraction": cf,
                "n": n,
                "n_words": idx.n_words,
                "planned": {
                    "algorithm": plan.algorithm,
                    "cost_words": plan.cost,
                    "candidates": [[b, c] for b, c in plan.candidates],
                },
                "backends": {
                    "fused": {
                        "wall_us": t_fused * 1e6,
                        "words_touched": dense_words,
                    },
                    "tiled_fused": {
                        "wall_us": t_tiled * 1e6,
                        "words_touched": tiled_words,
                        "case3_tiles": info["case3_tiles"],
                        "const_tiles": info["const_tiles"],
                        "signatures": info["signatures"],
                    },
                },
            }
        )
    return sweep


def sparsity_sweep(smoke: bool = False) -> list:
    """Column-density sweep 1e-4 .. 0.5: memory footprint and words touched
    per container kind, container store vs the legacy dense dirty pack.

    The query is the membership scan Threshold(1) (every dirty tile
    participates, so nothing hides behind case-1/2 folding) forced through
    ``tiled_fused`` on both stores -- the words-touched delta is purely the
    container representation.  The acceptance bar: >= 4x reduction at
    density <= 1e-3, no regression at 0.5 (where every container is dense
    and both stores are byte-identical).
    """
    n, n_tiles = (8, 8) if smoke else (16, 48)
    span = 64 * 32
    r = n_tiles * span
    q = Threshold(1)
    out = []
    for d in DENSITIES:
        rng = np.random.default_rng(int(1 / d) % 2**31)
        bits = rng.random((n, r)) < d
        idx = BitmapIndex.from_dense(jnp.asarray(bits))
        legacy = BitmapIndex.from_dense(jnp.asarray(bits), containers=False)
        t_cont = _time(lambda: idx.execute(q, backend="tiled_fused"))
        info = idx.last_info
        t_leg = _time(lambda: legacy.execute(q, backend="tiled_fused"))
        linfo = legacy.last_info
        words = info["dirty_words_gathered"] + idx.n_words
        words_legacy = linfo["dirty_words_gathered"] + idx.n_words
        out.append(
            {
                "density": d,
                "n": n,
                "n_words": idx.n_words,
                "dense_words": idx.n * idx.n_words + idx.n_words,
                "census": idx.store.container_census(),
                "memory_words": idx.store.storage_words(),
                "memory_words_legacy": legacy.store.storage_words(),
                "words_touched": words,
                "words_touched_legacy": words_legacy,
                "words_by_kind": info["words_by_kind"],
                "event_tiles": info["event_tiles"],
                "densified_tiles": info["densified_tiles"],
                # container pack reads vs the dense dirty pack's (the output
                # write pass is identical on both sides and excluded)
                "reduction": linfo["dirty_words_gathered"]
                / max(1, info["dirty_words_gathered"]),
                "wall_us": t_cont * 1e6,
                "wall_us_legacy": t_leg * 1e6,
            }
        )
    return out


def _mixed_density_bits(n, n_tiles, seed=0, span=64 * 32):
    """Half the row space dense (cf=0.0), half mostly clean (cf=0.95)."""
    rng = np.random.default_rng(seed)
    bits = np.zeros((n, n_tiles * span), bool)
    for i in range(n):
        for tj in range(n_tiles):
            lo, hi = tj * span, (tj + 1) * span
            if tj < n_tiles // 2:
                bits[i, lo:hi] = rng.random(span) < 0.35
            else:
                u = rng.random()
                if u < 0.475:
                    pass
                elif u < 0.95:
                    bits[i, lo:hi] = True
                else:
                    bits[i, lo:hi] = rng.random(span) < 0.35
    return bits


def shard_sweep(smoke: bool = False) -> list:
    """Row-shard scaling: wall time + per-shard backends + words touched."""
    n, n_tiles = (8, 8) if smoke else (16, 48)
    bits = _mixed_density_bits(n, n_tiles, seed=11)
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    q = Threshold(n // 2)
    out = []
    for s in SHARD_COUNTS:
        mesh = None
        if s == len(jax.devices()) > 1:
            from repro.dist.mesh import make_host_mesh

            mesh = make_host_mesh(data=s)
        sidx = idx.shard(mesh=mesh, n_shards=s)
        t = _time(lambda: sidx.execute(q).gather().block_until_ready())
        info = sidx.last_info
        per_shard = []
        if info["mode"] == "per_shard":
            for sh, be, inf in zip(
                sidx.store.shards, info["backends"], info["per_shard"]
            ):
                words = (
                    inf["dirty_words_gathered"] + sh.n_words
                    if inf is not None
                    else sh.n * sh.n_words + sh.n_words
                )
                per_shard.append({"backend": be, "words_touched": int(words)})
        out.append(
            {
                "n_shards": sidx.n_shards,
                "mesh": mesh is not None,
                "mode": info["mode"],
                "wall_us": t * 1e6,
                "backends": list(info["backends"]),
                "per_shard": per_shard,
            }
        )
    return out


def run(smoke: bool = False, sweep: list | None = None):
    out = []
    rng = np.random.default_rng(0)
    n, nw = (16, 1 << 10) if smoke else (32, 1 << 14)
    bm = jnp.asarray(rng.integers(0, 2**32, (n, nw), dtype=np.uint32))
    idx = BitmapIndex(bm)

    q = And(Interval(2, 10), Not(Threshold(n - 2)))
    composed = _time(lambda: idx.execute(q, backend="circuit").block_until_ready())

    def leafwise():
        a = idx.execute(Interval(2, 10), backend="circuit")
        b = idx.execute(Threshold(n - 2), backend="ssum")
        return (a & ~b).block_until_ready()

    leaf = _time(leafwise)
    out.append(("query_composed_us", composed * 1e6, f"N={n} r={nw * 32}"))
    out.append(("query_leafwise_us", leaf * 1e6, "2 adder passes + combine"))
    out.append(("query_composed_speedup", leaf / composed, "one shared adder"))

    qs = [Threshold(t) for t in (2, n // 4, n // 2, n - 1)] + [Parity()]
    many = _time(lambda: [r.block_until_ready() for r in idx.execute_many(qs)])
    seq = _time(lambda: [idx.execute(x).block_until_ready() for x in qs])
    out.append(("query_batched_us", many * 1e6, f"{len(qs)} queries, one call"))
    out.append(("query_sequential_us", seq * 1e6, f"{len(qs)} separate executes"))

    clear_compiled_cache()
    t0 = time.perf_counter()
    idx.execute(q, backend="circuit").block_until_ready()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx.execute(q, backend="circuit").block_until_ready()
    warm = time.perf_counter() - t0
    out.append(("query_compile_cold_ms", cold * 1e3, "build + optimise + jit"))
    out.append(("query_cached_warm_ms", warm * 1e3, "compiled-circuit cache hit"))

    if sweep is None:
        sweep = clean_fraction_sweep(smoke)
    for row in sweep:
        cf = row["clean_fraction"]
        fused = row["backends"]["fused"]
        tiled = row["backends"]["tiled_fused"]
        out.append(
            (f"query_cf{cf}_fused_words", fused["words_touched"], "dense sweep")
        )
        out.append(
            (
                f"query_cf{cf}_tiled_words",
                tiled["words_touched"],
                f"{tiled['case3_tiles']} case-3 tiles",
            )
        )
        out.append((f"query_cf{cf}_fused_us", fused["wall_us"], ""))
        out.append((f"query_cf{cf}_tiled_us", tiled["wall_us"], ""))
        planned = row.get("planned")
        if planned:
            out.append(
                (
                    f"query_cf{cf}_planned_cost",
                    planned["cost_words"] or 0.0,
                    f"planner picks {planned['algorithm']}",
                )
            )
    return out


def write_json(path: str = "BENCH_query.json", smoke: bool = False,
               sweep: list | None = None, shards: list | None = None,
               sparsity: list | None = None) -> dict:
    """Write the perf-trajectory artifact consumed by CI."""
    payload = {
        "bench": "query",
        "smoke": bool(smoke),
        "n_devices": len(jax.devices()),
        "clean_fraction_sweep": sweep if sweep is not None else clean_fraction_sweep(smoke),
        "shard_sweep": shards if shards is not None else shard_sweep(smoke),
        "sparsity_sweep": sparsity if sparsity is not None else sparsity_sweep(smoke),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return payload


if __name__ == "__main__":
    import sys

    smoke = "--smoke" in sys.argv
    sweep = clean_fraction_sweep(smoke)  # measured once, printed + persisted
    shards = shard_sweep(smoke)
    sparsity = sparsity_sweep(smoke)
    for name, val, extra in run(smoke, sweep=sweep):
        print(f"{name},{val:.2f},{extra}")
    write_json(smoke=smoke, sweep=sweep, shards=shards, sparsity=sparsity)
    for row in sweep:
        be = row["backends"]
        print(
            f"cf={row['clean_fraction']}: fused {be['fused']['words_touched']} words, "
            f"tiled {be['tiled_fused']['words_touched']} words, "
            f"planner -> {row['planned']['algorithm']}"
        )
    for row in shards:
        print(
            f"shards={row['n_shards']} ({row['mode']}): {row['wall_us']:.0f} us, "
            f"backends {sorted(set(row['backends']))}"
        )
    for row in sparsity:
        c = row["census"]
        print(
            f"density={row['density']}: {row['words_touched']} words vs "
            f"{row['words_touched_legacy']} legacy ({row['reduction']:.1f}x), "
            f"mem {row['memory_words']}/{row['memory_words_legacy']} words, "
            f"census d/s/r={c['dense']}/{c['sparse']}/{c['run']}"
        )
    print("wrote BENCH_query.json")
