"""Unified observability layer (repro.obs): metrics, spans, drift, slow log.

The acceptance bar:

  * a traced execution yields a plan / compile / dispatch (/ decode) span
    tree whose ``measured_words`` equals the executor's own ExecInfo
    accounting -- on EVERY backend, sharded and unsharded;
  * histogram merges are exact and associative (the fixed shared bucket
    edges are what make the cross-shard fold lossless);
  * the serving front-end's counters survive concurrent threaded clients
    with no lost increments, on both the server registry and the global
    mirror;
  * disabled mode mutates NOTHING: zero registry samples, no trace, no
    drift -- the hot path pays one branch;
  * the merged 8-shard ExecInfo equals the per-shard sum by schema;
  * the Prometheus exposition passes the scrape lint.
"""
from __future__ import annotations

import gc
import json
import threading

import numpy as np
import pytest

import repro.obs as obs
from repro.core.bitmaps import unpack
from repro.core.threshold import ALGORITHMS
from repro.dist.query import ShardedBitmapIndex
from repro.obs import trace
from repro.obs.registry import HistogramState, MetricsRegistry, lint_prometheus
from repro.obs.slowlog import SlowQueryLog
from repro.query import (
    And,
    BitmapIndex,
    Col,
    Interval,
    Not,
    Threshold,
    clear_compiled_cache,
)
from repro.query.execinfo import EXEC_INFO_SCHEMA, make_exec_info, merge_exec_infos
from repro.serve import QueryServer
from repro.serve.frontend import _REQUEST_IDS
from repro.storage import member_stats_info

N = 10
TILE_BITS = 64 * 32
R = 8 * TILE_BITS + 700  # 8 full tiles + a partial one


def _bits(seed=0, density=0.3):
    rng = np.random.default_rng(seed)
    bits = rng.random((N, R)) < density
    bits[: N // 3, : R // 2] = False  # clean territory for the tiled path
    return bits


def _t_for(alg: str) -> int:
    return {"wide_or": 1, "wide_and": N, "sopckt": 2}.get(alg, 4)


@pytest.fixture(scope="module")
def data():
    bits = _bits()
    return bits, bits.sum(0)


@pytest.fixture(scope="module")
def idx(data):
    bits, _ = data
    return BitmapIndex.from_dense(bits, names=[f"s{i}" for i in range(N)])


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# -- span words == executor words, every backend -----------------------------

def test_span_words_match_exec_info_every_backend(idx, data):
    _, counts = data
    for alg in ALGORITHMS:
        t = _t_for(alg)
        obs.enable()
        got = np.asarray(unpack(idx.execute(Threshold(t), backend=alg), idx.r))
        obs.disable()
        np.testing.assert_array_equal(got, counts >= t, err_msg=alg)
        root = obs.last_trace()
        assert root is not None and root.name == "execute", alg
        assert root.attrs["measured_words"] == idx.last_info["words_touched"], alg
        disp = root.find("dispatch")
        assert disp is not None and disp.attrs["backend"] == alg
        assert disp.attrs["measured_words"] == idx.last_info["words_touched"]
        obs.reset()


def test_span_words_match_exec_info_every_backend_sharded(idx, data):
    _, counts = data
    sidx = ShardedBitmapIndex.from_index(idx, n_shards=4)
    for alg in ALGORITHMS:
        t = _t_for(alg)
        obs.enable()
        res = sidx.execute(Threshold(t), backend=alg)
        obs.disable()
        got = np.asarray(unpack(res.gather(), sidx.r))
        np.testing.assert_array_equal(got, counts >= t, err_msg=alg)
        root = obs.last_trace()
        assert root is not None and root.name == "execute_sharded", alg
        merged = sidx.last_info
        assert root.attrs["measured_words"] == merged["words_touched"], alg
        shard_spans = [s for s in root.iter() if s.name == "shard"]
        assert len(shard_spans) == 4
        assert (
            sum(s.attrs["measured_words"] for s in shard_spans)
            == merged["words_touched"]
        ), alg
        obs.reset()


def test_planner_routed_trace_has_plan_and_predicted_words(idx):
    obs.enable()
    idx.execute(Interval(2, 8))
    obs.disable()
    root = obs.last_trace()
    plan_sp = root.find("plan")
    assert plan_sp is not None
    assert plan_sp.attrs["algorithm"] == root.attrs["backend"]
    assert plan_sp.attrs["predicted_words"] == root.attrs["predicted_words"]
    assert root.attrs["measured_words"] == idx.last_info["words_touched"]
    # the formatted tree is the docs surface: every span line renders
    text = root.format()
    assert "execute" in text and "plan" in text and "dispatch" in text


def test_compile_span_on_miss_hit_annotates_parent(idx):
    clear_compiled_cache()
    obs.enable()
    idx.execute(Interval(3, 7), backend="circuit")
    first = obs.last_trace()
    idx.execute(Interval(3, 7), backend="circuit")
    second = obs.last_trace()
    obs.disable()
    comp = first.find("compile")
    assert comp is not None and comp.attrs["cache"] == "miss"
    # steady state: no zero-duration child span, the hit rides the
    # enclosing dispatch span as an attribute
    assert second.find("compile") is None
    assert second.find("dispatch").attrs.get("compile_cache") == "hit"
    clear_compiled_cache()


def test_decode_span_only_on_tiled_path(idx):
    obs.enable()
    idx.execute(Threshold(4), backend="tiled_fused")
    tiled_root = obs.last_trace()
    idx.execute(Threshold(4), backend="fused")
    dense_root = obs.last_trace()
    obs.disable()
    dec = tiled_root.find("decode")
    assert dec is not None
    assert isinstance(dec.attrs["words_by_kind"], dict)
    # dense backends decode nothing: word accounting rides the dispatch span
    assert dense_root.find("decode") is None
    disp = dense_root.find("dispatch")
    assert disp.attrs["words_by_kind"].get("dense", 0) > 0


def test_acceptance_traced_server_request_full_span_tree():
    """ISSUE 9 acceptance: ONE traced QueryServer request produces a span
    tree with plan / compile / dispatch / decode spans, predicted AND
    measured words populated."""
    rng = np.random.default_rng(9)
    bits = rng.random((12, R)) < 0.25
    bits[:, : R * 7 // 8] = False  # mostly clean: planner routes tiled_fused
    idx2 = BitmapIndex.from_dense(bits, names=[f"store{i}" for i in range(12)])
    assert idx2.explain(Interval(2, 10)).algorithm == "tiled_fused"
    clear_compiled_cache()
    obs.enable()
    server = QueryServer(idx2, window=0)
    fut = server.submit(Interval(2, 10))  # the abstract's query
    while server.pump():
        pass
    fut.result(0)
    obs.disable()
    root = obs.last_trace()
    assert root is not None and root.name == "serve_batch"
    for name in ("execute_many", "plan", "compile", "dispatch", "decode"):
        assert root.find(name) is not None, name
    plan_sp = root.find("plan")
    assert plan_sp.attrs["predicted_words"] is not None
    disp = root.find("dispatch")
    assert disp.attrs["backend"] == "tiled_fused"
    assert disp.attrs["measured_words"] and disp.attrs["measured_words"] > 0
    em = root.find("execute_many")
    assert em.attrs["predicted_words"] is not None
    assert em.attrs["measured_words"] and em.attrs["measured_words"] > 0
    assert obs.drift_samples() >= 1
    clear_compiled_cache()


# -- histogram merge: exact + associative ------------------------------------

def test_histogram_merge_exact_and_associative():
    rng = np.random.default_rng(7)
    parts = []
    for _ in range(3):
        st = HistogramState()
        for v in 10.0 ** rng.uniform(-7.5, 9.5, 200):
            st.observe(float(v))
        parts.append(st)
    a, b, c = parts
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert left.counts == right.counts
    assert left.count == right.count == 600
    assert left.sum == pytest.approx(right.sum)
    # merging equals observing everything into one state (bucket-exactly)
    one = HistogramState()
    for st in parts:
        one.counts = [x + y for x, y in zip(one.counts, st.counts)]
        one.sum += st.sum
        one.count += st.count
    assert left.counts == one.counts
    for q in (0.5, 0.95, 0.99):
        assert np.isfinite(left.quantile(q))


def test_exec_info_merge_associative():
    rng = np.random.default_rng(3)
    infos = [
        make_exec_info(
            "tiled_fused",
            engine="scan",
            words_touched=int(rng.integers(1, 10_000)),
            launches=int(rng.integers(1, 5)),
            decode_words=int(rng.integers(0, 500)),
            words_by_kind={"dense": int(rng.integers(0, 99)), "run": 3},
        )
        for _ in range(3)
    ]
    a, b, c = infos
    left = merge_exec_infos([merge_exec_infos([a, b]), c])
    right = merge_exec_infos([a, merge_exec_infos([b, c])])
    assert left == right
    assert left["words_touched"] == sum(i["words_touched"] for i in infos)


def test_exec_info_schema_sum_at_8_shards(idx):
    """Regression: the merged 8-shard ExecInfo covers the full schema and
    every summable counter equals the per-shard sum (nothing dropped)."""
    sidx = ShardedBitmapIndex.from_index(idx, n_shards=8)
    obs.enable()
    res = sidx.execute(Threshold(4))
    obs.disable()
    merged = sidx.last_info
    assert set(EXEC_INFO_SCHEMA) <= set(merged)
    root = obs.last_trace()
    shard_spans = [s for s in root.iter() if s.name == "shard"]
    assert len(shard_spans) == 8
    for key in ("measured_words", "launches"):
        skey = "words_touched" if key == "measured_words" else key
        assert (
            sum(s.attrs[key] or 0 for s in shard_spans) == merged[skey]
        ), key
    # and the result is still the oracle's
    got = np.asarray(unpack(res.gather(), sidx.r))
    ref = np.asarray(unpack(idx.execute(Threshold(4)), idx.r))
    np.testing.assert_array_equal(got, ref)


# -- threaded serving front-end: no lost increments --------------------------

def test_threaded_server_counts_survive_concurrency(idx):
    obs.enable()
    pool = [Interval(2, 6), Threshold(2, over=("s0", "s3", "s6")),
            And(Col("s1"), Not(Col("s2")))]
    n_clients, per_client = 4, 25
    with QueryServer(idx, window=0.001) as server:
        def client(ci):
            futs = [
                server.submit(pool[(ci + j) % len(pool)])
                for j in range(per_client)
            ]
            for f in futs:
                f.result(30)

        threads = [
            threading.Thread(target=client, args=(ci,))
            for ci in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        info = server.info()
    obs.disable()
    total = n_clients * per_client
    assert info["requests"] == total
    # every request resolves through the latency histogram exactly once
    assert info["latency"]["count"] == total
    assert np.isfinite(info["latency"]["p99_s"])
    # the global mirror saw the same increments (no lost updates)
    g = obs.REGISTRY.counter("repro_serve_events_total")
    assert int(g.value(event="requests")) == total


# -- disabled mode: zero mutations -------------------------------------------

class _RecordedAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter and
    exit with its thread, and keeps every annotation made."""

    log: list = []
    made: list = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs
        self.made.append(self)

    def __enter__(self):
        self.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, threading.get_ident()))


@pytest.fixture
def recorded_annotations(monkeypatch):
    _RecordedAnnotation.log, _RecordedAnnotation.made = [], []
    monkeypatch.setattr(trace, "_TraceAnnotation", _RecordedAnnotation)
    return _RecordedAnnotation.log


def test_disabled_mode_mutates_nothing(idx, recorded_annotations):
    # warm every lazy import + registration the measured calls would do
    obs.enable()
    idx.execute(Interval(2, 8))
    with QueryServer(idx, window=0) as server:
        server.serve_many([Threshold(3)])
    obs.disable()
    obs.reset()
    recorded_annotations.clear()
    before = json.dumps(obs.REGISTRY.snapshot(), sort_keys=True, default=str)
    stats_before = member_stats_info()
    first_rid = next(_REQUEST_IDS)
    for _ in range(5):
        idx.execute(Interval(2, 8))
        idx.execute(Threshold(4), backend="tiled_fused")
    with QueryServer(idx, window=0.001) as server:  # batcher thread: coalesces
        server.serve_many([Threshold(3), Interval(2, 6)])
    after = json.dumps(obs.REGISTRY.snapshot(), sort_keys=True, default=str)
    assert before == after
    assert obs.last_trace() is None
    assert obs.drift_samples() == 0
    assert trace.span("anything") is trace.NULL_SPAN
    assert trace.current_span() is trace.NULL_SPAN
    # no profiler annotation (spans, coalescing sleep) and no gc hook
    assert trace.annotation("coalesce") is trace.NULL_SPAN
    assert recorded_annotations == []
    assert obs._gc_hook not in gc.callbacks
    # the member-statistics counters are plain ints (cache hits only here:
    # every subset was planned in the warm-up) and request ids one
    # integer per admitted query
    stats_after = member_stats_info()
    assert stats_after["misses"] == stats_before["misses"]
    assert stats_after["hits"] > stats_before["hits"]
    assert all(type(v) is int for v in stats_after.values())
    assert next(_REQUEST_IDS) == first_rid + 3


# -- the profiler clock ---------------------------------------------------------

def test_span_emits_profiler_annotation_only_while_enabled(recorded_annotations):
    with trace.span("plan"):
        pass
    assert recorded_annotations == []
    obs.enable()
    with trace.span("execute"):
        with trace.span("plan") as sp:
            sp.set(memo="hit")
    obs.disable()
    me = threading.get_ident()
    assert recorded_annotations == [
        ("enter", "repro:execute", me), ("enter", "repro:plan", me),
        ("exit", "repro:plan", me), ("exit", "repro:execute", me),
    ]
    # a span opened on another thread is annotated on that thread
    recorded_annotations.clear()
    obs.enable()
    worker = threading.Thread(target=lambda: trace.span("coalesce").__enter__().__exit__())
    worker.start()
    worker.join()
    obs.disable()
    assert {t for _, _, t in recorded_annotations} == {worker.ident}
    assert [n for _, n, _ in recorded_annotations] == ["repro:coalesce"] * 2


def test_disable_removes_gc_hook(recorded_annotations):
    obs.enable()
    obs.enable()  # idempotent: one hook
    assert gc.callbacks.count(obs._gc_hook) == 1
    gc.collect()
    gc_notes = [x for x in recorded_annotations if x[1] == "repro:gc"]
    assert [x[0] for x in gc_notes] == ["enter", "exit"]
    obs.disable()
    assert obs._gc_hook not in gc.callbacks
    recorded_annotations.clear()
    gc.collect()
    assert recorded_annotations == []


def test_spans_and_gc_land_on_the_profiler_trace(idx, tmp_path):
    """A real profiler trace (CPU) holds the spans and a collection as
    ``repro:`` events on the host plane, the collection with its
    generation and a served batch with its request ids."""
    import jax
    from jax.profiler import ProfileData

    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        idx.execute(Interval(2, 8))
        gc.collect()
        server = QueryServer(idx, cache_entries=0)
        fut = server.submit(Threshold(3))
        server.pump()
    finally:
        jax.profiler.stop_trace()
        obs.disable()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro:"):
                        events.setdefault(ev.name, []).extend(ev.stats)
    assert {"repro:execute", "repro:plan", "repro:dispatch", "repro:gc"} <= set(events)
    assert ("generation", 2) in events["repro:gc"]
    assert ("rids", f"[{fut.rid}]") in events["repro:serve_batch"]


# -- member statistics, request identity, batched dense accounting -------------

def test_member_stats_span_opens_only_on_cache_miss():
    bits = _bits(seed=5)
    fresh = BitmapIndex.from_dense(bits, names=[f"s{i}" for i in range(N)])
    q = Threshold(2, over=("s0", "s3", "s5"))
    start = member_stats_info()
    obs.enable()
    fresh.explain(q)
    first = obs.last_trace()
    fresh.explain(q)
    second = obs.last_trace()
    obs.disable()
    assert first.name == "plan" and second.name == "plan"
    ms = first.find("member_stats")
    assert ms is not None
    # s0 has clean tiles beside dirty ones; s3 and s5 are dirty throughout
    assert ms.attrs == {"members": 3, "tiles": fresh.store.n_tiles, "varying": 1}
    assert "stats_cache" not in first.attrs
    assert ms.wall_s <= first.wall_s
    # the hit rides the plan span; no zero-length child
    assert second.find("member_stats") is None
    assert second.attrs["stats_cache"] == "hit"
    end = member_stats_info()
    assert end["misses"] - start["misses"] == 1
    assert end["hits"] - start["hits"] == 1
    assert end["folded_members"] - start["folded_members"] == 2
    assert end["keyed_members"] - start["keyed_members"] == 1


def test_serve_batch_request_ids_join_the_client_futures(idx, recorded_annotations):
    """Each client's future names its request id; exactly one traced
    ``serve_batch`` span lists it, in the span tree and on the profiler
    annotation alike, beside the queue wait of its requests; a cache hit
    joins no batch."""
    batches = []

    def on_root(sp):
        if sp.name == "serve_batch":
            batches.append(sp)

    def waits():
        fam = obs.REGISTRY.snapshot()["repro_serve_queue_wait_seconds"]
        states = fam["samples"].values()
        return (sum(st["count"] for st in states), sum(st["sum"] for st in states))

    trace.add_root_listener(on_root)
    obs.enable()
    waits_before = waits()
    try:
        pool = [Interval(2, 6), Threshold(2, over=("s0", "s3", "s6")),
                And(Col("s1"), Not(Col("s2"))), Threshold(3), Interval(1, 9)]
        with QueryServer(idx, window=0.002, cache_entries=0) as server:
            futs = []

            def client(ci):
                for j in range(3):
                    fut = server.submit(pool[(ci + j) % len(pool)])
                    fut.result(30)
                    futs.append(fut)

            threads = [threading.Thread(target=client, args=(ci,))
                       for ci in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            executed = server.info()["executed"]
        with QueryServer(idx, window=0) as cached:
            cached.serve_many([Threshold(4)])
            hit = cached.submit(Threshold(4))
    finally:
        obs.disable()
        trace._ROOT_LISTENERS.remove(on_root)
    rids = [r for sp in batches for r in sp.attrs["rids"]]
    assert len(set(rids)) == len(rids)
    assert all(type(r) is int for r in rids)
    assert sum(sp.attrs["batch"] for sp in batches) == len(rids) == executed + 1
    # one queue wait observed per request a batch executed; each span
    # carries its batch's share of them
    count, total = waits()
    assert count - waits_before[0] == len(rids)
    assert all(sp.attrs["queue_wait_s"] >= 0 for sp in batches)
    assert sum(sp.attrs["queue_wait_s"] for sp in batches) == pytest.approx(
        total - waits_before[1], rel=1e-9)
    for sp in batches:
        assert len(sp.attrs["rids"]) == sp.attrs["batch"]
    assert len(futs) == 12 and all(f.rid in rids for f in futs)
    assert hit.done() and hit.rid is None
    # the profiler annotation carries the span's ids
    notes = [a.attrs["rids"] for a in _RecordedAnnotation.made
             if a.name == "repro:serve_batch"]
    assert notes == [sp.attrs["rids"] for sp in batches]


def test_batched_dense_execute_many_span_words_match_exec_info(idx, data):
    _, counts = data
    qs = [Threshold(3), Threshold(5), Interval(2, 6)]
    obs.enable()
    outs = idx.execute_many(qs, backend="circuit")
    obs.disable()
    np.testing.assert_array_equal(np.asarray(unpack(outs[0], idx.r)), counts >= 3)
    root = obs.last_trace()
    assert root.name == "execute_many"
    disp = [s for s in root.iter() if s.name == "dispatch"]
    assert len(disp) == 1 and disp[0].attrs["batched"] == len(qs)
    info = idx.last_info
    assert info["n_outputs"] == len(qs)
    assert disp[0].attrs["measured_words"] == info["words_touched"]
    assert disp[0].attrs["dirty_words_gathered"] == info["dirty_words_gathered"]
    assert disp[0].attrs["words_by_kind"] == info["words_by_kind"]
    assert root.attrs["measured_words"] == info["words_touched"]
    nw = idx.n_words
    assert info["words_touched"] == (N + len(qs)) * nw


# -- drift accounting ---------------------------------------------------------

def test_drift_samples_accumulate_over_100_queries(idx):
    obs.enable()
    for i in range(100):
        idx.execute(Threshold(2 + (i % 5)))
    n = obs.drift_samples()
    obs.disable()
    assert n >= 100
    d = obs.dump()["drift"]
    assert d["samples"] == n
    assert np.isfinite(d["ratio_p50"])


# -- slow-query log -----------------------------------------------------------

def test_slow_query_log_threshold_and_ring(idx):
    obs.enable(slow_query_threshold_s=0.0)  # record everything
    idx.execute(Interval(2, 8))
    assert len(obs.SLOW_QUERIES.entries()) >= 1
    entry = obs.SLOW_QUERIES.entries()[-1]
    assert entry["span"]["name"] == "execute"
    assert "algorithm" in entry["plan"]
    obs.SLOW_QUERIES.set_threshold(999.0)
    obs.SLOW_QUERIES.clear()
    idx.execute(Interval(2, 8))
    assert obs.SLOW_QUERIES.entries() == []
    obs.disable()
    # ring bound: capacity caps retention, dropped counts the overwrites
    log = SlowQueryLog(threshold_s=0.0, capacity=4)
    for i in range(6):
        sp = trace.Span(f"q{i}")
        sp.wall_s = 1.0
        log.maybe_record(sp)
    assert len(log.entries()) == 4
    assert log.dropped == 2


# -- export surfaces ----------------------------------------------------------

def test_prometheus_export_lints_clean_and_jsonl_parses(idx):
    obs.enable()
    for i in range(10):
        idx.execute(Threshold(2 + (i % 4)))
    with QueryServer(idx, window=0) as server:
        server.serve_many([Interval(2, 6), Threshold(3)])
    prom = obs.export_prometheus()
    problems = lint_prometheus(prom)
    obs.disable()
    assert problems == []
    # host enqueue time is not query wall time: the spans hold host time
    # and the serve latency histogram the request's
    assert "repro_query_words_touched" in prom
    assert "repro_query_wall_seconds" not in prom
    for line in obs.export_jsonl().strip().splitlines():
        fam = json.loads(line)
        assert {"name", "type", "samples"} <= set(fam)
    snap = obs.dump()
    assert snap["drift"]["samples"] >= 10
    assert snap["last_trace"] is not None


def test_registry_isolated_instances_and_reset():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("x_total", "", ("k",))
    bound = c.bind(k="a")
    bound.inc(2)
    c.inc(1, k="b")
    assert c.value(k="a") == 2 and c.value(k="b") == 1
    h = reg.histogram("h_seconds")
    h.observe(0.25)
    assert h.state().count == 1
    reg.reset()
    assert c.value(k="a") == 0 and h.state().count == 0
    bound.inc(3)  # bound handles survive reset and recreate their series
    assert c.value(k="a") == 3
    reg.enabled = False
    bound.inc(5)
    c.inc(5, k="b")
    h.observe(1.0)
    assert c.value(k="a") == 3 and c.value(k="b") == 0 and h.state().count == 0
