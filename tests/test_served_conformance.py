"""Served-path conformance: every query kind through ``QueryServer`` over
every kind of index the server accepts, word for word against numpy.

Each case submits to a ``QueryServer`` driven synchronously with
``pump()`` and compares every word of the answer, tail bits included,
with a reference packed from per-row member counts of the boolean
matrix -- computed with numpy alone, never with ``repro`` code.

  * the matrix: 6 index kinds x 8 query kinds, each backend as the
    planner chooses it (tiled and dense choices both occur per kind);
  * the fused bare path forced (``backend="fused"``) on every index kind
    at four member shapes: it must read its members in place from the
    store's plane view (``members == "planes"``);
  * cache invalidation: a served query, a mutation of one member column,
    and the same query again must answer the mutated matrix.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro import persist
from repro.query import (
    And,
    BitmapIndex,
    Col,
    Exactly,
    Interval,
    Not,
    Or,
    Parity,
    Threshold,
    Weighted,
)
from repro.serve import QueryServer
from repro.stream import CompactionPolicy, StreamingIndex

SPAN = 64 * 32  # rows per tile at the default granularity
N = 40
R = 6 * SPAN + 517  # not a multiple of 32: the last word has tail bits
NAMES = tuple(f"c{i}" for i in range(N))
CLEAN = range(20)  # columns of zero, full and a few dirty tiles
# the rest are dirty everywhere


def _matrix(seed: int) -> np.ndarray:
    """``N x R`` bits: columns 0-19 are clean-heavy (an all-zero tile, an
    all-one tile, coin-flip constant tiles, a dirty tile on every fourth
    column and a dirty partial tail on every fifth), columns 20-39 are
    dirty at density 0.35 in every tile."""
    rng = np.random.default_rng(seed)
    bits = np.zeros((N, R), bool)
    for i in range(N):
        for tj in range(-(-R // SPAN)):
            lo, hi = tj * SPAN, min((tj + 1) * SPAN, R)
            if i not in CLEAN:
                dirty = True
            elif tj == 1:
                bits[i, lo:hi] = True
                continue
            elif tj in (3, 4):
                bits[i, lo:hi] = rng.random() < 0.5
                continue
            else:
                dirty = (tj == 2 and i % 4 == 0) or (hi == R and i % 5 == 0)
            if dirty:
                bits[i, lo:hi] = rng.random(hi - lo) < 0.35
    return bits


def _cols(ix) -> tuple:
    return tuple(NAMES[i] for i in ix)


def _count(bits: np.ndarray, ix) -> np.ndarray:
    """Per-row member count (a repeated member counts twice)."""
    return bits[list(ix)].sum(0, dtype=np.int64)


def _words(rows: np.ndarray) -> np.ndarray:
    """Pack a boolean row vector LSB-first into uint32 words, tail zero."""
    padded = np.zeros(-(-rows.size // 32) * 32, bool)
    padded[: rows.size] = rows
    return np.packbits(padded, bitorder="little").view("<u4").astype(np.uint32)


def _serve(src, queries, backend=None) -> list:
    """Submit every query, pump until drained, return the answers as
    host uint32 word arrays."""
    server = QueryServer(src, window=0)
    futs = [server.submit(q, backend=backend) for q in queries]
    while server.pump():
        pass
    return [_host(f.result(0)) for f in futs]


def _host(out) -> np.ndarray:
    return np.asarray(out.gather() if hasattr(out, "gather") else out)


def _assert_words(got: np.ndarray, want_rows: np.ndarray) -> None:
    want = _words(want_rows)
    assert got.dtype == np.uint32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Index kinds: one seeded matrix each, shared across the module
# ---------------------------------------------------------------------------

# member subsets (column numbers)
A = tuple(range(0, 9))  # clean-heavy: the planner tiles a bare threshold
B = tuple(range(21, 30))
C = (4, 17, 26, 33)
D = (1, 22, 5, 36, 11, 28, 9)
E = (5, 6, 7)
F = (25, 26, 27)
W = (21, 2, 29, 12, 37)
WEIGHTS = (1, 2, 3, 4, 5)


def _stream(bits: np.ndarray, n_shards=None) -> StreamingIndex:
    idx = BitmapIndex.from_dense(bits, NAMES)
    if n_shards:
        idx = idx.shard(n_shards=n_shards)
    return StreamingIndex(idx, policy=CompactionPolicy(auto=False))


def _mutate(s: StreamingIndex, bits: np.ndarray) -> np.ndarray:
    """An uncompacted delta over clean, full and dirty tiles; returns the
    mutated matrix."""
    bits = bits.copy()
    on = np.r_[np.arange(5, SPAN, 7), 3 * SPAN + 11, R - 1]
    off = np.arange(SPAN, 2 * SPAN + 40, 3)
    s.set_bits(NAMES[3], on)
    bits[3, on] = True
    s.clear_bits(NAMES[21], off)
    bits[21, off] = False
    s.set_bits(NAMES[6], off)
    bits[6, off] = True
    return bits


def _build(kind: str, tmp_path_factory):
    """(bits, source the server wraps, the index whose ``last_info``
    records how the fused kernel read its members)."""
    seed = KINDS.index(kind)
    bits = _matrix(seed)
    if kind == "bitmap":
        idx = BitmapIndex.from_dense(bits, NAMES)
        return bits, idx, lambda: idx
    if kind == "bitmap_legacy":
        idx = BitmapIndex.from_dense(bits, NAMES, containers=False)
        return bits, idx, lambda: idx
    if kind in ("stream_delta", "stream_compacted"):
        s = _stream(bits)
        bits = _mutate(s, bits)
        if kind == "stream_compacted":
            assert s.compact()
        return bits, s, s.index
    if kind == "paged":
        path = tmp_path_factory.mktemp("paged") / "index.bmsnap"
        persist.save(BitmapIndex.from_dense(bits, NAMES), path)
        # fewer cells than one tiled query gathers: pages evict mid-query
        store = persist.PagedTileStore(persist.load(path), capacity_tiles=2)
        idx = BitmapIndex(names=NAMES, _store=store)
        return bits, idx, lambda: idx
    if kind == "sharded":
        sidx = BitmapIndex.from_dense(bits, NAMES).shard(n_shards=3)
        assert sidx.n_shards == 3
        return bits, sidx, lambda: sidx
    raise AssertionError(kind)


KINDS = ("bitmap", "bitmap_legacy", "stream_delta", "stream_compacted",
         "paged", "sharded")


@pytest.fixture(scope="module", params=KINDS)
def kind(request, tmp_path_factory):
    return (request.param,) + _build(request.param, tmp_path_factory)


# ---------------------------------------------------------------------------
# The matrix: 8 query kinds over each index kind, planner's choice
# ---------------------------------------------------------------------------

# query kind -> [(query, reference from the bits), ...]
QUERIES = {
    "threshold": [
        (Threshold(3, over=_cols(A)), lambda b: _count(b, A) >= 3),
    ],
    "vacuous": [
        (Threshold(0, over=_cols(C)), lambda b: _count(b, C) >= 0),
        (Threshold(len(C) + 1, over=_cols(C)), lambda b: _count(b, C) >= len(C) + 1),
    ],
    "interval": [
        (Interval(2, 4, over=_cols(D)),
         lambda b: (_count(b, D) >= 2) & (_count(b, D) <= 4)),
    ],
    "exactly": [
        (Exactly(3, over=_cols(B)), lambda b: _count(b, B) == 3),
    ],
    "parity": [
        (Parity(over=_cols(D)), lambda b: _count(b, D) % 2 == 1),
    ],
    "weighted": [
        (Weighted(WEIGHTS, 7, over=_cols(W)),
         lambda b: (np.asarray(WEIGHTS)[:, None] * b[list(W)]).sum(0) >= 7),
    ],
    "and_not": [
        (And(Threshold(2, over=_cols(A[:4])), Not(Col(NAMES[30]))),
         lambda b: (_count(b, A[:4]) >= 2) & ~b[30]),
    ],
    "nested": [
        (Threshold(2, over=(Or(Interval(1, 2, over=_cols(E)), Col(NAMES[24])),
                            Parity(over=_cols(F)), Col(NAMES[13]))),
         lambda b: ((_count(b, E) >= 1) & (_count(b, E) <= 2) | b[24]).astype(int)
         + _count(b, F) % 2 + b[13] >= 2),
    ],
}


@pytest.mark.parametrize("qkind", list(QUERIES))
def test_served_answer_matches_numpy(kind, qkind):
    _, bits, src, _ = kind
    pairs = QUERIES[qkind]
    got = _serve(src, [q for q, _ in pairs])
    for answer, (_, ref) in zip(got, pairs):
        _assert_words(answer, ref(bits))


def _algorithms(src, q) -> set:
    """Backends the planner picks for ``q`` (every shard's, if sharded)."""
    if hasattr(src, "plan"):
        return set(src.plan(q).backends)
    return {src.explain(q).algorithm}


def test_matrix_takes_tiled_and_dense_paths(kind):
    """The matrix reaches both planner families on every index kind, and
    the paged store evicts while it serves a tiled query."""
    name, bits, src, _ = kind
    algs = set()
    for pairs in QUERIES.values():
        for q, _ in pairs:
            algs |= _algorithms(src, q)
    assert "tiled_fused" in algs and algs - {"tiled_fused"}, algs
    if name == "paged":
        q, ref = QUERIES["threshold"][0]
        assert _algorithms(src, q) == {"tiled_fused"}
        before = src.store.cache_info()["evictions"]
        (got,) = _serve(src, [q])
        _assert_words(got, ref(bits))
        assert src.store.cache_info()["evictions"] > before


# ---------------------------------------------------------------------------
# The fused bare path, forced, on every index kind
# ---------------------------------------------------------------------------

ALL = tuple(range(N - 1, -1, -1))
FUSED = {
    "m1": (Threshold(1, over=_cols((3,))), lambda b: _count(b, (3,)) >= 1),
    "m2_repeated": (Threshold(2, over=_cols((21, 21))), lambda b: _count(b, (21, 21)) >= 2),
    "m13_t_eq_m": (Threshold(13, over=_cols(range(2, 15))),
                   lambda b: _count(b, range(2, 15)) >= 13),
    "all_t1": (Threshold(1, over=_cols(ALL)), lambda b: _count(b, ALL) >= 1),
}


@pytest.mark.parametrize("shape", list(FUSED))
def test_forced_fused_reads_planes(kind, shape):
    _, bits, src, executed = kind
    q, ref = FUSED[shape]
    executed().last_info = None
    (got,) = _serve(src, [q], backend="fused")
    _assert_words(got, ref(bits))
    assert executed().last_info["members"] == "planes"


# ---------------------------------------------------------------------------
# Cache invalidation on the mutable kinds
# ---------------------------------------------------------------------------

MUT = 24  # the mutated member column
INVALIDATED = {
    "bare": (Threshold(2, over=_cols((MUT, 2, 25, 31))),
             lambda b: _count(b, (MUT, 2, 25, 31)) >= 2),
    "nested": QUERIES["nested"][0],  # MUT sits inside Or(Interval, Col)
}


@pytest.mark.parametrize("query", list(INVALIDATED))
@pytest.mark.parametrize("mutation", ["set_bits", "clear_bits", "set_bits_compact"])
@pytest.mark.parametrize("n_shards", [None, 3])
def test_mutation_invalidates_served_answer(n_shards, mutation, query):
    bits = _matrix(7)
    s = _stream(bits, n_shards)
    q, ref = INVALIDATED[query]
    server = QueryServer(s, window=0)

    def serve():
        fut = server.submit(q)
        while server.pump():
            pass
        return _host(fut.result(0))

    first = serve()
    _assert_words(first, ref(bits))
    np.testing.assert_array_equal(serve(), first)  # cached
    assert server.info()["cache_hits"] == 1

    rows = np.arange(0, R, 3)
    bits = bits.copy()
    if mutation == "clear_bits":
        s.clear_bits(NAMES[MUT], rows)
        bits[MUT, rows] = False
    else:
        s.set_bits(NAMES[MUT], rows)
        bits[MUT, rows] = True
    if mutation == "set_bits_compact":
        assert s.compact()
    assert not np.array_equal(_words(ref(bits)), first)

    _assert_words(serve(), ref(bits))
    assert server.info()["invalidations"] >= 1
