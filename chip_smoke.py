#!/usr/bin/env python3
"""Bring-up smoke of the served query path on a TPU.

    python chip_smoke.py               # one chip: 64 stores x 2**27 products
    python chip_smoke.py --chips 4     # the sharded index over four chips

One chip: builds a ``BitmapIndex`` of 64 packed columns over 2**27 rows from
``--seed`` -- 32 uniform columns (bit density 5/32, every tile dirty, which
the planner sends to the fused Pallas kernel) and 32 clustered columns
(long runs and sparse spans, >= 90% clean tiles, which it sends to the
tiled scan engine) -- and serves a few dozen requests through
``QueryServer``: the paper's "on sale in 2 to 10 stores" over all 64
columns, thresholds and intervals over each half, a composite, shape
buckets that run multi-output circuits, and repeats answered from the
result cache.  Every answer is checked bit for bit over the whole row
space against a word-parallel numpy counter that does not import
``repro``.  The run fails unless the fused kernel and the scan engine's
Pallas grid were compiled through Mosaic (``tpu_custom_call``, not
interpreted) and the warm repeat compiled nothing.

Four chips (``--chips 4``): the same generator at 4 x 2**27 rows, sharded
over a four-device mesh with ``BitmapIndex.shard``.  Uniform queries take
the ``shard_map`` path, clustered ones the per-shard path; each shard's
dense view, packs and results must sit on its own device, and results must
match the numpy reference and the unsharded index.

Times printed here are a smoke, not a benchmark.  Any failure raises and
exits non-zero; the last line of standard output is one JSON object naming
the device.  Off the TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.query import And, BitmapIndex, Col, Interval, Not, Threshold  # noqa: E402
from repro.serve import QueryServer  # noqa: E402

TILE_WORDS = 64
N_UNIFORM = N_CLUSTERED = 32
LOG2_ROWS = 27
#: clustered columns: the row space is cut into segments, each belonging to
#: one of a few categories; a store's state per category is one of these
N_CATEGORIES = 24
STATE_P = {"zero": 0.64, "one": 0.31, "sparse": 0.02, "run": 0.02, "dense": 0.01}
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# Data: packed uint32 words made from a seed, never an [N, r] bool array
# ---------------------------------------------------------------------------


def uniform_words(rng, shape) -> np.ndarray:
    """Random packed words at bit density 5/32: ``a & b & (c | d & e)``."""
    a, b, c, d, e = (
        rng.integers(0, 2**32, size=shape, dtype=np.uint32) for _ in range(5)
    )
    return a & b & (c | (d & e))


def run_words(start, stop, tile_words: int) -> np.ndarray:
    """uint32[len(start), tile_words]: bits [start, stop) set in each tile."""
    w = np.arange(tile_words, dtype=np.int64) * 32
    lo = np.clip(start[:, None] - w, 0, 32).astype(np.uint64)
    hi = np.clip(stop[:, None] - w, 0, 32).astype(np.uint64)
    one = np.uint64(1)
    return (((one << hi) - one) ^ ((one << lo) - one)).astype(np.uint32)


def clustered_words(rng, out: np.ndarray, tile_words: int) -> None:
    """Fill ``out`` (uint32[n, n_words]) with clustered columns.

    Segments of whole tiles are assigned to categories; each column is
    all-zero or all-one over a category, or holds sparse positions, one run
    per tile, or random dense words there.  The per-category states are
    drawn first, so they do not depend on the row count."""
    n, n_words = out.shape
    kinds = list(STATE_P)
    state = rng.choice(len(kinds), size=(n, N_CATEGORIES),
                       p=list(STATE_P.values()))
    n_tiles = n_words // tile_words
    seg = max(1, n_tiles // 1024)
    cat = rng.integers(0, N_CATEGORIES, -(-n_tiles // seg))
    tile_cat = np.repeat(cat, seg)[:n_tiles]
    span = tile_words * 32
    for i in range(n):
        tiles = out[i].reshape(n_tiles, tile_words)
        st = state[i][tile_cat]
        tiles[:] = 0
        tiles[st == kinds.index("one")] = 0xFFFFFFFF
        sp = np.nonzero(st == kinds.index("sparse"))[0]
        pos = rng.integers(0, span, (sp.size, 4))
        np.bitwise_or.at(
            tiles, (np.repeat(sp, 4), (pos // 32).ravel()),
            (np.uint32(1) << (pos % 32).astype(np.uint32)).ravel(),
        )
        rn = np.nonzero(st == kinds.index("run"))[0]
        start = rng.integers(1, span // 2, rn.size)
        tiles[rn] = run_words(start, start + rng.integers(1, span // 2, rn.size),
                              tile_words)
        dn = np.nonzero(st == kinds.index("dense"))[0]
        tiles[dn] = uniform_words(rng, (dn.size, tile_words))


def make_columns(seed: int, log2_rows: int):
    """(uint32[64, n_words] columns, names, uniform names, clustered names)."""
    n_words = 1 << (log2_rows - 5)
    cols = np.empty((N_UNIFORM + N_CLUSTERED, n_words), np.uint32)

    def fill_uniform(i):  # numpy's generators release the GIL
        cols[i] = uniform_words(np.random.default_rng([seed, 0, i]), n_words)

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill_uniform, range(N_UNIFORM)))
    clustered_words(np.random.default_rng([seed, 1]), cols[N_UNIFORM:],
                    TILE_WORDS)
    uni = tuple(f"u{i:02d}" for i in range(N_UNIFORM))
    clu = tuple(f"c{i:02d}" for i in range(N_CLUSTERED))
    return cols, uni + clu, uni, clu


# ---------------------------------------------------------------------------
# Reference: a word-parallel numpy counter, independent of repro
# ---------------------------------------------------------------------------


def count_planes(cols: np.ndarray, chunk: int = 1 << 15) -> list:
    """Bit-sliced per-row counts of ``cols`` (uint32[n, n_words]): plane j
    holds bit j of every row's count, least significant plane first."""
    n, n_words = cols.shape
    planes = [np.zeros(n_words, np.uint32) for _ in range(n.bit_length())]
    for w0 in range(0, n_words, chunk):
        sub = [p[w0:w0 + chunk] for p in planes]
        for row in cols[:, w0:w0 + chunk]:
            carry = row
            for p in sub:
                nxt = p & carry
                p ^= carry
                carry = nxt
    return planes


def count_at_least(planes: list, t: int) -> np.ndarray:
    """Rows whose count is >= t, as packed words."""
    ones = np.full_like(planes[0], 0xFFFFFFFF)
    if t <= 0:
        return ones
    if t >= 1 << len(planes):
        return np.zeros_like(planes[0])
    gt, eq = np.zeros_like(ones), ones
    for j in reversed(range(len(planes))):
        if (t >> j) & 1:
            eq = eq & planes[j]
        else:
            gt = gt | (eq & planes[j])
            eq = eq & ~planes[j]
    return gt | eq


class Reference:
    """Expected answers over the whole row space, from count planes of
    the member sets the workload uses."""

    def __init__(self, cols: np.ndarray, names: tuple, groups: dict):
        self.cols, self.slot = cols, {n: i for i, n in enumerate(names)}
        self.planes = {
            key: count_planes(cols[[self.slot[m] for m in members]])
            for key, members in groups.items()
        }

    def threshold(self, group, t):
        return count_at_least(self.planes[group], t)

    def interval(self, group, lo, hi):
        return self.threshold(group, lo) & ~self.threshold(group, hi + 1)

    def column(self, name):
        return self.cols[self.slot[name]]


# ---------------------------------------------------------------------------
# Workload
# ---------------------------------------------------------------------------


def workload(uni: tuple, clu: tuple) -> list:
    """Served phases: [(phase, [(label, query, copies, expected-fn)])].

    Queries over one half share a shape bucket, so the uniform thresholds
    run as one 3-output fused circuit and the clustered ones as one tiled
    gather; the halves are served in separate phases because a bucket that
    mixes a tiled plan with a dense one runs dense.  The planner prices
    only bare thresholds on their member columns, so the clustered
    intervals run fused like the rest."""
    return [
        ("uniform", [
            ("headline", Interval(2, 10), 4,
             lambda R: R.interval("all", 2, 10)),
            ("composite", And(Interval(2, 10), Not(Col(uni[0]))), 3,
             lambda R: R.interval("all", 2, 10) & ~R.column(uni[0])),
            ("u_thr3", Threshold(3, over=uni), 3, lambda R: R.threshold("u", 3)),
            ("u_thr5", Threshold(5, over=uni), 3, lambda R: R.threshold("u", 5)),
            ("u_thr8", Threshold(8, over=uni), 3, lambda R: R.threshold("u", 8)),
            ("u_int", Interval(3, 6, over=uni), 2, lambda R: R.interval("u", 3, 6)),
        ]),
        ("clustered", [
            ("c_thr8", Threshold(8, over=clu), 3, lambda R: R.threshold("c", 8)),
            ("c_thr10", Threshold(10, over=clu), 3,
             lambda R: R.threshold("c", 10)),
            ("c_thr12", Threshold(12, over=clu), 3,
             lambda R: R.threshold("c", 12)),
            ("c_int", Interval(2, 10, over=clu), 3,
             lambda R: R.interval("c", 2, 10)),
            ("c_int2", Interval(5, 12, over=clu), 2,
             lambda R: R.interval("c", 5, 12)),
        ]),
    ]


def reference_groups(uni: tuple, clu: tuple) -> dict:
    return {"all": uni + clu, "u": uni, "c": clu}


def serve_phase(server: QueryServer, items, on_done=None) -> dict:
    """Submit every copy of every query, pump until all resolve; returns
    {label: [result, ...]} (every future is read).  ``on_done`` is called
    as each future resolves."""
    futs = {label: [server.submit(q) for _ in range(copies)]
            for label, q, copies, _ in items}
    if on_done is not None:
        for f in (f for fs in futs.values() for f in fs):
            f.add_done_callback(on_done)
    while any(not f.done() for fs in futs.values() for f in fs):
        if server.pump() == 0:
            raise RuntimeError("futures pending but nothing to pump")
    return {label: [f.result() for f in fs] for label, fs in futs.items()}


def to_host(result) -> np.ndarray:
    """A served result as host words (a sharded result is gathered)."""
    if hasattr(result, "gather"):
        result = result.gather()
    return np.asarray(jax.device_get(result), np.uint32)


def check_results(results: dict, items, ref: Reference) -> list:
    """Bit-identity of every served copy against the reference; returns
    one line per query naming what the check covered."""
    lines = []
    for label, _q, _copies, expect in items:
        want = expect(ref)
        for got in results[label]:
            got = to_host(got)
            if not np.array_equal(got, want):
                bad = int(np.count_nonzero(got != want))
                raise AssertionError(f"{label}: {bad} words differ from the reference")
        lines.append(
            f"  {label}: {len(results[label])} answers == numpy reference "
            f"over all {want.size * 32} rows"
        )
    return lines


class CompileLog:
    """Counts backend compilations and persistent-cache hits/misses through
    JAX's monitoring events."""

    def __init__(self):
        self.compiles, self.compile_s, self.hits, self.misses = 0, 0.0, 0, 0
        self.by_name: dict = {}  # jitted function name -> compile seconds

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_kw):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration
            self.by_name[fun_name] = self.by_name.get(fun_name, 0.0) + duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def build_index(cols, names, r: int) -> BitmapIndex:
    """The index as a user builds it: packed columns in, tile-classified
    store and device-resident dense view and container packs out."""
    idx = BitmapIndex(cols, names, r=r, tile_words=TILE_WORDS)
    idx.store.device_packs()
    jax.block_until_ready(idx.columns)
    return idx


def describe_store(idx: BitmapIndex, uni: tuple, clu: tuple) -> list:
    store, slot = idx.store, {n: i for i, n in enumerate(idx.names)}
    lines = [f"  census (all): {store.container_census()}"]
    for label, members in (("uniform", uni), ("clustered", clu)):
        st = store.member_stats([slot[m] for m in members])
        lines.append(
            f"  {label}: clean fraction {st.clean_fraction}, density "
            f"{st.density}, census {store.container_census([slot[m] for m in members])}"
        )
    return lines


def serve_all(idx, phases, clog: CompileLog, *, cache_entries: int) -> tuple:
    """Serve every phase, then repeat each distinct query once (answered
    from the result cache when it is on); returns (server, {label:
    results}, (seconds to the first answer, seconds compiling until
    then))."""
    server = QueryServer(idx, cache_entries=cache_entries)
    results, first = {}, []
    t0, c0 = time.perf_counter(), clog.compile_s

    def on_done(_f):
        if not first:
            first.append((time.perf_counter() - t0, clog.compile_s - c0))

    for _phase, items in phases:
        got = serve_phase(server, items, on_done)
        for label, outs in got.items():
            results.setdefault(label, []).extend(outs)
    repeat = [(label, q, 1, e) for _p, items in phases for label, q, _c, e in items]
    for label, outs in serve_phase(server, repeat).items():
        results[label].extend(outs)
    return server, results, first[0]


def warm_repeat(idx, phases, repeats: int = 3) -> list:
    """Per-query wall seconds of the served phases with the result cache
    off: each phase's requests are submitted, pumped and waited on (the
    server blocks on every result), and the phase time is shared among its
    distinct queries."""
    server = QueryServer(idx, cache_entries=0)
    per_query = []
    for _ in range(repeats):
        for _phase, items in phases:
            t0 = time.perf_counter()
            got = serve_phase(server, items)
            jax.block_until_ready([o for outs in got.values() for o in outs])
            per_query.append((time.perf_counter() - t0) / len(items))
    if server.info()["errors"]:
        raise AssertionError(f"warm repeat errors: {server.info()}")
    return per_query


def block_grid_query(idx, phases) -> tuple:
    """(label, ExecInfo) of the first tiled query whose residual tiles run
    through the scan engine's block grid (the rest are answered by the
    event merge alone); raises if there is none."""
    for _p, items in phases:
        for label, q, _c, _e in items:
            if idx.explain(q).algorithm != "tiled_fused":
                continue
            jax.block_until_ready(idx.execute(q))
            if idx.last_info["densified_tiles"]:
                return label, idx.last_info
    raise AssertionError("no tiled query ran tiles through the block grid")


def prove_kernels(idx, phases) -> list:
    """The fused kernel and the scan engine's Pallas grid ran compiled
    through Mosaic: raises otherwise."""
    from repro.kernels.threshold_ssum import INTERPRET, circuit_runner
    from repro.query.index import _fused_available, circuit_for

    if INTERPRET or not _fused_available():
        raise AssertionError("Pallas kernels resolve to interpret mode here")
    head = phases[0][1][0][1]
    if idx.explain(head).algorithm != "fused":
        raise AssertionError(f"headline planned {idx.explain(head).algorithm}")
    jax.block_until_ready(idx.execute(head))
    info = idx.last_info
    if (info["backend"], info["kernel"]) != ("fused", "pallas"):
        raise AssertionError(f"headline ran {info['backend']}/{info['kernel']}")
    runner = circuit_runner(circuit_for((head,), idx.n, idx.names),
                            interpret=INTERPRET)
    hlo = runner.lower(idx.columns).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("fused runner's compiled HLO has no tpu_custom_call")
    label, info = block_grid_query(idx, phases)
    if (info["engine"], info["kernel"]) != ("scan", "pallas"):
        raise AssertionError(f"{label} ran {info}")
    return [
        f"  fused: headline ran backend=fused kernel=pallas, INTERPRET={INTERPRET}, "
        f"tpu_custom_call in its compiled runner: True",
        f"  tiled_fused: {label} ran engine=scan kernel=pallas (use_pallas=True), "
        f"{info['densified_tiles']} tiles through the block grid, "
        f"{info['event_tiles']} event tiles, {info['launches']} launches",
    ]


def run_one_chip(seed: int, log2_rows: int, clog: CompileLog) -> tuple:
    """Build, serve, check against the reference and repeat warm; returns
    (index, phases)."""
    r = 1 << log2_rows
    t0 = time.perf_counter()
    cols, names, uni, clu = make_columns(seed, log2_rows)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx = build_index(cols, names, r)
    t_build = time.perf_counter() - t0
    dev = jax.devices()[0]
    log(f"data: {idx.n} columns x {r} rows, {cols.nbytes} bytes of packed "
        f"columns; generated in {t_gen} s, index built in {t_build} s")
    for line in describe_store(idx, uni, clu):
        log(line)
    stats = dev.memory_stats() or {}
    log(f"device memory in use after build: {stats.get('bytes_in_use')} bytes "
        f"(dense view {idx.columns.nbytes} bytes on {sorted(map(str, idx.columns.devices()))})")

    phases = workload(uni, clu)
    log("calibration: none installed -- plans come from the index's own "
        "words-touched model")
    for _p, items in phases:
        for label, q, _c, _e in items:
            log(f"  plan {label}: {idx.explain(q).algorithm}")

    c0, s0 = clog.compiles, clog.compile_s
    t0 = time.perf_counter()
    server, results, (t_first, c_first) = serve_all(
        idx, phases, clog, cache_entries=4096
    )
    t_serve = time.perf_counter() - t0
    info = server.info()
    log(f"served: {info['requests']} requests, {info['executed']} executions, "
        f"{info['batches']} batches {info['batch_size_hist']}, "
        f"{info['cache_hits']} cache hits, {info['dedup_hits']} deduped, "
        f"errors {info['errors']}")
    if info["errors"] or info["cache_hits"] < len(results):
        raise AssertionError(f"serving failed: {info}")
    log("smoke, not a benchmark:")
    log(f"  cold: first answer after {t_first} s, of which {c_first} s "
        f"compiling; all requests served in {t_serve} s, of which "
        f"{clog.compile_s - s0} s in {clog.compiles - c0} compilations")
    slowest = sorted(clog.by_name.items(), key=lambda kv: -kv[1])[:4]
    log(f"  slowest compiles: {slowest}")

    t0 = time.perf_counter()
    ref = Reference(cols, names, reference_groups(uni, clu))
    check = [line for _p, items in phases
             for line in check_results(results, items, ref)]
    log(f"reference check ({time.perf_counter() - t0} s): every result "
        "bit-identical to the numpy word-parallel counter")
    for line in check:
        log(line)

    c0 = clog.compiles
    per_query = warm_repeat(idx, phases)
    warm_compiles = clog.compiles - c0
    log(f"  warm repeat: median per-query wall {statistics.median(per_query)} s "
        f"over {len(per_query)} phase runs (each ends in block_until_ready); "
        f"compilations inside: {warm_compiles}")
    if warm_compiles:
        raise AssertionError(f"{warm_compiles} compilations in the warm repeat")
    return idx, phases


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def sampled_tiles(rng, n_words: int, k: int = 64) -> np.ndarray:
    """Word indices of ``k`` random tiles."""
    tiles = rng.choice(n_words // TILE_WORDS, size=k, replace=False)
    return (tiles[:, None] * TILE_WORDS + np.arange(TILE_WORDS)).ravel()


def run_sharded(seed: int, log2_rows: int, n_chips: int) -> dict:
    """The sharded path over ``n_chips`` devices, checked against the
    numpy reference and the unsharded index; returns the modes seen per
    query set."""
    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()[:n_chips]
    if len(devices) < n_chips:
        raise RuntimeError(f"{n_chips} devices needed, {len(jax.devices())} found")
    mesh = make_host_mesh(data=n_chips, model=1)
    r = n_chips << log2_rows
    t0 = time.perf_counter()
    cols, names, uni, clu = make_columns(seed, log2_rows + (n_chips - 1).bit_length())
    idx = build_index(cols, names, r)
    sidx = idx.shard(mesh=mesh)
    t_build = time.perf_counter() - t0
    log(f"data: {idx.n} columns x {r} rows over {sidx.n_shards} shards "
        f"({cols.nbytes // n_chips} bytes of columns per device); generated "
        f"and built in {t_build} s")

    homes = [d for d in mesh.devices.ravel()]
    for i, shard in enumerate(sidx.store.shards):
        placed = {
            "dense": shard.densify().devices(),
            "packs": shard.device_packs()[0].devices(),
        }
        if any(devs != {homes[i]} for devs in placed.values()):
            raise AssertionError(f"shard {i} placed on {placed}, not {homes[i]}")
        log(f"  shard {i}: {shard.n_words} words, dense view and packs on {homes[i]}")
    spmd = sidx.store.spmd_dense(mesh, "data")
    for piece in spmd.addressable_shards:
        i = piece.index[1].start // sidx.store.spmd_width
        if piece.device != homes[i]:
            raise AssertionError(f"spmd block {i} on {piece.device}, not {homes[i]}")
    log(f"  spmd_dense: block i on device i for {len(spmd.addressable_shards)} blocks")

    ref = Reference(cols, names, {"u": uni, "c": clu})
    # each inner list is served as one request burst; the two clustered
    # thresholds share a shape bucket, so they run as one 2-output circuit
    sets = {
        "uniform": [
            [("u_int", Interval(2, 10, over=uni), lambda R: R.interval("u", 2, 10))],
            [("u_thr5", Threshold(5, over=uni), lambda R: R.threshold("u", 5))],
        ],
        "clustered": [[
            ("c_thr10", Threshold(10, over=clu), lambda R: R.threshold("c", 10)),
            ("c_thr8", Threshold(8, over=clu), lambda R: R.threshold("c", 8)),
        ]],
    }
    rng = np.random.default_rng(seed + 1)
    sample = sampled_tiles(rng, idx.n_words)
    modes = {}
    server = QueryServer(sidx)
    for set_name, bursts in sets.items():
        for burst in bursts:
            qs = [q for _label, q, _e in burst]
            outs = server.serve_many(qs + qs)
            mode, backends = sidx.last_info["mode"], sidx.last_info["backends"]
            modes.setdefault(set_name, set()).add(mode)
            ones = idx.execute_many(qs)
            for (label, _q, expect), res, one in zip(burst, outs, ones):
                for i, part in enumerate(res.shards):
                    if part.devices() != {homes[i]}:
                        raise AssertionError(
                            f"{label}: shard {i} result on {part.devices()}")
                got = to_host(res)
                if not np.array_equal(got, expect(ref)):
                    raise AssertionError(f"{label}: sharded result != numpy reference")
                if not np.array_equal(got[sample], to_host(one)[sample]):
                    raise AssertionError(f"{label}: sharded != unsharded on sampled tiles")
                log(f"  {set_name} {label}: mode {mode}, backends {backends}; == "
                    f"numpy reference over all {r} rows, == unsharded index on "
                    f"{sample.size // TILE_WORDS} sampled tiles; shard i's result "
                    "on device i")
    info = server.info()
    if info["errors"]:
        raise AssertionError(f"serving failed: {info}")
    log(f"served: {info['requests']} requests, {info['executed']} executions, "
        f"{info['dedup_hits']} deduped, errors {info['errors']}")
    return {k: sorted(v) for k, v in modes.items()}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}", file=sys.stderr)
        return 1
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: {dev.device_kind} x {len(jax.devices())} ({dev.platform}); "
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}")
    if os.environ.get("REPRO_TILED_ENGINE"):
        raise RuntimeError("REPRO_TILED_ENGINE is set; the planner must choose")
    log(f"compile cache: {enable_compile_cache()}")

    with CompileLog() as clog:
        if args.chips == 1:
            idx, phases = run_one_chip(args.seed, LOG2_ROWS, clog)
            log("kernels:")
            for line in prove_kernels(idx, phases):
                log(line)
        else:
            modes = run_sharded(args.seed, LOG2_ROWS, args.chips)
            if modes != {"uniform": ["shard_map"], "clustered": ["per_shard"]}:
                raise AssertionError(f"sharded modes {modes}")
        log(f"compile cache: {clog.hits} hits, {clog.misses} misses"
            + (" -- cache hit" if clog.hits else ""))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
