"""Serving front-end: cache tier, dedup fan-out, admission, batching.

The acceptance bar for ``repro.serve.QueryServer``:

  * everything served is bit-identical to direct ``BitmapIndex.execute``
    (oracle), cached or not, on every backend;
  * a streaming mutation invalidates exactly the cache entries reading a
    touched column -- and a post-mutation resubmit observes the NEW bits
    (the stale-read regression);
  * identical in-flight queries run once and fan out to every waiter;
  * past ``max_pending`` distinct queries, ``submit`` sheds with
    :class:`Overloaded`;
  * plans come through the per-store memo (hit/miss counters move, and
    ``clear_compiled_cache`` clears it).
"""
from __future__ import annotations

import threading

import numpy as np
import pytest

from repro.core.threshold import ALGORITHMS
from repro.query import (
    And,
    AndNot,
    BitmapIndex,
    Col,
    Interval,
    Not,
    Threshold,
    clear_compiled_cache,
    plan_memo_info,
)
from repro.serve import Overloaded, QueryServer, shape_bucket
from repro.stream import StreamingIndex


def _bits(n=8, r=512, seed=0, density=0.3):
    rng = np.random.default_rng(seed)
    return rng.random((n, r)) < density


def _names(n):
    return [f"s{i}" for i in range(n)]


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_compiled_cache()
    yield
    clear_compiled_cache()


# -- oracle: served == executed, across the cache tier and every backend ---

def test_served_bit_identical_to_execute():
    bits = _bits()
    idx = BitmapIndex.from_dense(bits, names=_names(8))
    server = QueryServer(idx, window=0)
    pool = [
        Interval(2, 6),
        Threshold(3, over=("s0", "s1", "s2", "s4")),
        And(Threshold(2, over=("s1", "s3", "s5")), Not(Col("s7"))),
        AndNot(Interval(1, 2, over=("s2", "s6")), Col("s0")),
    ]
    futs = [server.submit(q) for q in pool]
    while server.pump():
        pass
    for q, f in zip(pool, futs):
        np.testing.assert_array_equal(
            np.asarray(f.result(0)), np.asarray(idx.execute(q))
        )


@pytest.mark.parametrize("alg", ALGORITHMS)
def test_cached_result_bit_identical_per_backend(alg):
    """First serve executes; the resubmit is a cache hit -- both must equal
    direct execution on the same backend (bare threshold: every backend
    accepts it)."""
    bits = _bits(n=6, r=256, seed=3)
    idx = BitmapIndex.from_dense(bits, names=_names(6))
    server = QueryServer(idx, window=0)
    t = {"wide_or": 1, "wide_and": 6}.get(alg, 3)  # degenerate-only backends
    q = Threshold(t, over=tuple(_names(6)))
    ref = np.asarray(idx.execute(q, backend=alg))

    cold = server.submit(q, backend=alg)
    assert server.pump() == 1
    np.testing.assert_array_equal(np.asarray(cold.result(0)), ref)

    warm = server.submit(q, backend=alg)
    assert warm.done(), "second submit should complete from the result cache"
    np.testing.assert_array_equal(np.asarray(warm.result(0)), ref)
    info = server.info()
    assert info["cache_hits"] == 1 and info["executed"] == 1


def test_semantic_cache_key_ignores_member_order():
    idx = BitmapIndex.from_dense(_bits(), names=_names(8))
    server = QueryServer(idx, window=0)
    a = server.submit(Threshold(2, over=("s1", "s3", "s5")))
    server.pump()
    b = server.submit(Threshold(2, over=("s5", "s1", "s3")))
    assert b.done() and server.info()["cache_hits"] == 1
    np.testing.assert_array_equal(np.asarray(a.result(0)), np.asarray(b.result(0)))


# -- streaming invalidation: exact, and no stale reads ---------------------

def test_invalidation_touches_exactly_mutated_columns():
    bits = _bits()
    stream = StreamingIndex.from_dense(bits, names=_names(8))
    server = QueryServer(stream, window=0)
    q_a = Threshold(1, over=("s0", "s1"))
    q_b = Threshold(1, over=("s6", "s7"))
    server.serve_many([q_a, q_b])
    assert server.info()["cache_entries"] == 2

    stream.set_bits("s0", [5])  # touches q_a's support only
    info = server.info()
    assert info["invalidations"] == 1
    assert info["cache_entries"] == 1

    hit = server.submit(q_b)  # untouched support: still a hit
    assert hit.done() and server.info()["cache_hits"] == 1


def test_no_stale_reads_after_update():
    """The regression the version vector exists for: mutate, resubmit, and
    the served bits must be the NEW bits."""
    bits = _bits(n=4, r=256, seed=7, density=0.0)  # all-zero columns
    stream = StreamingIndex.from_dense(bits, names=_names(4))
    server = QueryServer(stream, window=0)
    q = Threshold(1, over=("s0", "s1"))
    before = server.serve_many([q])[0]
    assert not np.asarray(before).any()

    stream.set_bits("s0", [0, 33, 77])
    after = server.serve_many([q])[0]
    np.testing.assert_array_equal(
        np.asarray(after), np.asarray(stream.index().execute(q))
    )
    assert np.asarray(after).any(), "served result must observe the mutation"


def test_view_columns_cascade_invalidation():
    bits = _bits()
    stream = StreamingIndex.from_dense(bits, names=_names(8))
    stream.materialize("hot", Threshold(2, over=("s0", "s1", "s2")))
    server = QueryServer(stream, window=0)
    served = server.serve_many([Col("hot")])[0]
    assert server.info()["cache_entries"] == 1
    np.testing.assert_array_equal(
        np.asarray(served), np.asarray(stream.index().execute(Col("hot")))
    )

    stream.set_bits("s1", [3])  # an INPUT of the view, not the view itself
    assert server.info()["cache_entries"] == 0, "view entry must cascade out"
    fresh = server.serve_many([Col("hot")])[0]
    np.testing.assert_array_equal(
        np.asarray(fresh), np.asarray(stream.index().execute(Col("hot")))
    )


# -- dedup: one execution, many waiters ------------------------------------

def test_dedup_fans_out_single_execution():
    idx = BitmapIndex.from_dense(_bits(), names=_names(8))
    server = QueryServer(idx, window=0)
    q = Interval(2, 5)
    futs = [server.submit(q) for _ in range(5)]
    # member order must not defeat dedup either
    futs.append(server.submit(Interval(2, 5, over=tuple(reversed(_names(8))))))
    server.pump()
    info = server.info()
    assert info["executed"] == 1 and info["batches"] == 1
    assert info["dedup_hits"] == 5
    assert info["served"] == 6
    ref = np.asarray(idx.execute(q))
    for f in futs:
        np.testing.assert_array_equal(np.asarray(f.result(0)), ref)


# -- admission control ------------------------------------------------------

def test_overload_sheds_with_explicit_signal():
    idx = BitmapIndex.from_dense(_bits(), names=_names(8))
    server = QueryServer(idx, window=0, max_pending=2, cache_entries=0)
    server.submit(Threshold(1, over=("s0",)))
    server.submit(Threshold(1, over=("s1",)))
    with pytest.raises(Overloaded):
        server.submit(Threshold(1, over=("s2",)))
    # duplicates of an admitted query are always accepted
    server.submit(Threshold(1, over=("s0",)))
    info = server.info()
    assert info["shed"] == 1 and info["dedup_hits"] == 1
    while server.pump():
        pass


# -- micro-batching ----------------------------------------------------------

def test_same_shape_queries_share_one_batch():
    idx = BitmapIndex.from_dense(_bits(), names=_names(8))
    server = QueryServer(idx, window=0)
    qs = [Threshold(2, over=("s0", "s1", "s2")),
          Threshold(3, over=("s3", "s5", "s7")),
          Threshold(1, over=("s4", "s6", "s0"))]
    assert len({shape_bucket(q) for q in qs}) == 1
    outs = server.serve_many(qs)
    info = server.info()
    assert info["batches"] == 1 and info["batch_size_hist"] == {3: 1}
    for q, out in zip(qs, outs):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(idx.execute(q)))


def test_shape_bucket_drops_names_keeps_arity():
    a = Threshold(2, over=("s0", "s1", "s2"))
    b = Threshold(5, over=("s3", "s4", "s5"))
    c = Threshold(2, over=("s0", "s1"))
    assert shape_bucket(a) == shape_bucket(b)
    assert shape_bucket(a) != shape_bucket(c)
    assert shape_bucket(And(a, Not(Col("s0")))) == shape_bucket(And(b, Not(Col("s7"))))


# -- batcher thread ----------------------------------------------------------

def test_threaded_mode_serves_concurrent_clients():
    bits = _bits(n=8, r=512, seed=11)
    idx = BitmapIndex.from_dense(bits, names=_names(8))
    pool = [Interval(2, 6), Threshold(2, over=("s0", "s3", "s6")),
            And(Col("s1"), Not(Col("s2")))]
    refs = [np.asarray(idx.execute(q)) for q in pool]
    with QueryServer(idx, window=0.001) as server:
        results: list = [None] * 4

        def client(ci):
            futs = [server.submit(pool[(ci + j) % len(pool)]) for j in range(9)]
            results[ci] = [np.asarray(f.result(30)) for f in futs]

        threads = [threading.Thread(target=client, args=(ci,)) for ci in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for ci, got in enumerate(results):
        for j, arr in enumerate(got):
            np.testing.assert_array_equal(arr, refs[(ci + j) % len(pool)])
    info = server.info()
    assert info["served"] == 36 and info["pending"] == 0


# -- plan memo ---------------------------------------------------------------

def test_plan_memo_hit_miss_and_clear():
    idx = BitmapIndex.from_dense(_bits(), names=_names(8))
    base = plan_memo_info()
    q = Threshold(3, over=("s0", "s2", "s4", "s6"))
    p0 = idx.explain(q)
    p1 = idx.explain(Threshold(3, over=("s6", "s4", "s2", "s0")))  # semantic twin
    assert p0.memo == "miss" and p1.memo == "hit"
    assert p1.algorithm == p0.algorithm
    info = plan_memo_info()
    assert info["misses"] >= base["misses"] + 1
    assert info["hits"] >= base["hits"] + 1
    clear_compiled_cache()
    cleared = plan_memo_info()
    assert cleared["entries"] == 0 and cleared["hits"] == 0 and cleared["misses"] == 0
    assert idx.explain(q).memo == "miss"


def test_server_info_reports_plan_memo_counters():
    idx = BitmapIndex.from_dense(_bits(), names=_names(8))
    server = QueryServer(idx, window=0)
    server.serve_many([Interval(2, 4)])
    assert "plan_memo" in server.info()
    assert set(server.info()["plan_memo"]) >= {"hits", "misses", "entries"}


# -- fused bare thresholds read their members in place ---------------------

def test_served_fused_bare_thresholds_read_store_planes(monkeypatch):
    """Served fused bare thresholds read their members from the store's
    plane view: the 2-D dense view is never built, every execution counts
    as a plane read, each dispatch span says so, and nothing is gathered."""
    import repro.obs as obs
    import repro.query.index as qindex
    from repro.core.bitmaps import unpack
    from repro.query import bare_members_info

    monkeypatch.setattr(qindex, "_fused_available", lambda: True)
    bits = _bits(n=16, r=4096 + 37, seed=7, density=0.4)
    idx = BitmapIndex.from_dense(bits, names=_names(16))
    members = [(3, 9, 1, 14), (0, 5, 6, 7, 8, 2), (11, 4, 1), (15, 12, 10, 13, 3)]
    queries = [Threshold(len(m) // 2 + 1, over=tuple(f"s{i}" for i in m))
               for m in members]
    assert [idx.explain(q).algorithm for q in queries] == ["fused"] * len(queries)
    before = bare_members_info()
    server = QueryServer(idx, window=0)
    spans = []
    obs.enable()
    try:
        for q, m in zip(queries, members):
            fut = server.submit(q)
            server.pump()
            got = np.asarray(unpack(fut.result(), idx.r))
            np.testing.assert_array_equal(got, bits[list(m)].sum(0) >= q.t)
            spans += [s for s in obs.last_trace().iter() if s.name == "dispatch"]
    finally:
        obs.disable()
    after = bare_members_info()
    assert server.info()["executed"] == len(queries)
    assert after["planes"] - before["planes"] == len(queries)
    assert after["rows"] == before["rows"]
    assert server.info()["bare_members"] == after
    assert [s.attrs["members"] for s in spans] == ["planes"] * len(queries)
    assert all(s.attrs["dirty_words_gathered"] == 0 for s in spans)
    assert idx.last_info["dirty_words_gathered"] == 0
    assert idx.store._dense is None
