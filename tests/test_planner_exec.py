"""Every plan the planner can emit names a runnable executor (ISSUE fix:
the seed's planner returned wide_or/wide_and/rbmrg_block/dsk which
threshold() rejected)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitmaps import pack, unpack
from repro.core.planner import plan_query, plan_threshold
from repro.core.threshold import ALGORITHMS, threshold
from repro.query import Interval, Threshold, execute


def _mk(n, r, density, seed=0):
    rng = np.random.default_rng(seed)
    bits = rng.random((n, r)) < density
    return bits, pack(jnp.asarray(bits))


# (n, t, planner kwargs, data density) covering every reachable branch
SCENARIOS = [
    (8, 1, {}, 0.3, "wide_or"),
    (8, 8, {}, 0.3, "wide_and"),
    (16, 2, {}, 0.3, "looped"),
    (16, 8, {"clean_fraction": 0.9}, 0.02, "rbmrg_block"),
    (16, 15, {"density": 1e-4, "on_device": False}, 0.005, "dsk"),
    (16, 8, {}, 0.3, "fused"),
    (16, 8, {"fused_available": False}, 0.3, "ssum"),
    (2500, 700, {}, 0.3, "scancount_streaming"),
]


@pytest.mark.parametrize("n,t,kw,density,expected_alg", SCENARIOS)
def test_every_reachable_plan_executes(n, t, kw, density, expected_alg):
    plan = plan_threshold(n, t, **kw)
    assert plan.algorithm == expected_alg, plan
    assert plan.algorithm in ALGORITHMS
    bits, bm = _mk(n, 300, density, seed=n * 31 + t)
    got = np.asarray(unpack(threshold(bm, t, plan.algorithm), 300))
    np.testing.assert_array_equal(got, bits.sum(0) >= t, err_msg=plan.algorithm)


def test_all_algorithm_names_are_executable():
    """threshold() accepts every name in ALGORITHMS (no planner orphan)."""
    bits, bm = _mk(6, 200, 0.3, seed=5)
    counts = bits.sum(0)
    for alg in ALGORITHMS:
        t = {"wide_or": 1, "wide_and": 6}.get(alg, 3)
        got = np.asarray(unpack(threshold(bm, t, alg), 200))
        np.testing.assert_array_equal(got, counts >= t, err_msg=alg)


def test_wide_reductions_validate_t():
    _, bm = _mk(6, 100, 0.5)
    with pytest.raises(ValueError):
        threshold(bm, 3, "wide_or")
    with pytest.raises(ValueError):
        threshold(bm, 3, "wide_and")


TILE_BITS = 64 * 32


def _clean_fraction_bits(n, clean_fraction, seed, n_tiles=5, tail_bits=700):
    """Columns with ~clean_fraction all-zero/all-one tiles + a partial tile."""
    rng = np.random.default_rng(seed)
    r = n_tiles * TILE_BITS + tail_bits
    bits = np.zeros((n, r), bool)
    for i in range(n):
        for tj in range(n_tiles + 1):
            lo, hi = tj * TILE_BITS, min((tj + 1) * TILE_BITS, r)
            u = rng.random()
            if u < clean_fraction / 2:
                pass
            elif u < clean_fraction:
                bits[i, lo:hi] = True
            else:
                bits[i, lo:hi] = rng.random(hi - lo) < 0.35
    return bits


@pytest.mark.parametrize("clean_fraction", [0.0, 0.9, 1.0])
def test_all_backends_execute_against_tilestore_index(clean_fraction):
    """Acceptance: every ALGORITHMS backend runs against a TileStore-backed
    index and matches the oracle -- at clean fractions 0.0/0.9/1.0 and with
    a partial final tile."""
    from repro.query import BitmapIndex

    n = 10
    bits = _clean_fraction_bits(n, clean_fraction, seed=int(clean_fraction * 10) + 2)
    r = bits.shape[1]
    counts = bits.sum(0)
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    assert idx.store.n_tiles * idx.store.tile_words > idx.n_words  # partial tile
    for alg in ALGORITHMS:
        t = {"wide_or": 1, "wide_and": n, "sopckt": 2}.get(alg, 4)
        got = np.asarray(unpack(idx.execute(Threshold(t), backend=alg), r))
        np.testing.assert_array_equal(
            got, counts >= t, err_msg=f"{alg} cf={clean_fraction}"
        )


def test_planner_emits_tiled_fused_on_clean_data():
    from repro.query import BitmapIndex

    bits = _clean_fraction_bits(8, 0.95, seed=5)
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    plan = idx.explain(Threshold(4))
    assert plan.algorithm == "tiled_fused", plan
    assert plan.cost is not None
    dense = dict(plan.candidates).get("fused")
    assert dense is not None and plan.cost < dense
    counts = bits.sum(0)
    got = np.asarray(unpack(idx.execute(Threshold(4)), bits.shape[1]))
    np.testing.assert_array_equal(got, counts >= 4)
    # words-touched accounting from the actual run
    assert idx.last_info is not None
    assert idx.last_info["dirty_words_gathered"] < idx.n * idx.n_words


def test_planner_prices_signature_dispatch_overhead():
    """Regression (BENCH_query.json): tiled_fused was 5-16x slower on wall
    time than fused at clean_fraction <= 0.5 despite touching fewer words,
    because every specialization signature was a separate launch.  The cost
    model now prices launch groups, so the planner must NOT pick tiled_fused
    at cf=0.0 / cf=0.5 and must still pick it on clean-dominated data."""
    from repro.query import BitmapIndex

    n, n_tiles = 8, 8
    for cf, expect_tiled in ((0.0, False), (0.5, False), (0.95, True)):
        bits = _bench_clean_fraction_bits(n, n_tiles, cf, seed=int(cf * 100) + 1)
        idx = BitmapIndex.from_dense(jnp.asarray(bits))
        plan = idx.explain(Threshold(n // 2))
        if expect_tiled:
            assert plan.algorithm == "tiled_fused", (cf, plan)
        else:
            assert plan.algorithm != "tiled_fused", (cf, plan)
            # with the fused kernel available the dense sweep must win
            stats = idx.store.member_stats(None)
            from repro.core.planner import plan_threshold

            p = plan_threshold(n, n // 2, stats=stats, fused_available=True)
            assert p.algorithm == "fused", (cf, p)
        # the estimate includes per-launch overhead: visible in candidates
        cands = dict(plan.candidates)
        assert "tiled_fused" in cands


def _bench_clean_fraction_bits(n, n_tiles, clean_fraction, seed=0, span=64 * 32):
    """The query_bench generator (duplicated: benchmarks/ is not a package)."""
    rng = np.random.default_rng(seed)
    bits = np.zeros((n, n_tiles * span), bool)
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


def test_container_cost_monotone_and_bounded_by_dense():
    """Container-aware pricing: tiled_fused estimates grow monotonically
    with container size and never exceed the same store's dense-pack
    estimate (ratio == 1.0 when every container is dense)."""
    from repro.core.planner import estimate_words_touched
    from repro.storage import TileStore

    n, n_tiles, span = 4, 8, 64 * 32
    prev = None
    for bits_per_tile in (1, 8, 32, 64, 120, 1024):
        rng = np.random.default_rng(bits_per_tile)
        bits = np.zeros((n, n_tiles * span), bool)
        for i in range(n):
            for t in range(n_tiles):
                bits[i, t * span + rng.choice(span, bits_per_tile,
                                              replace=False)] = True
        store = TileStore.from_packed(pack(jnp.asarray(bits)))
        legacy = TileStore.from_packed(pack(jnp.asarray(bits)),
                                       containers=False)
        stats = store.member_stats(None)
        est = estimate_words_touched(
            "tiled_fused", n, 1, n_words=stats.n_words, stats=stats
        )
        dense_est = estimate_words_touched(
            "tiled_fused", n, 1, n_words=stats.n_words,
            stats=legacy.member_stats(None),
        )
        assert est is not None and est <= dense_est, (bits_per_tile, est, dense_est)
        assert stats.compressed_words <= stats.dirty_words
        if prev is not None:
            assert est >= prev, (bits_per_tile, est, prev)
        prev = est
    # fully dense: the container store prices exactly like the legacy one
    assert est == dense_est


def test_bench_words_touched_never_exceed_dense_estimate():
    """BENCH_query.json regression guard (like the cf<=0.5 tiled_fused bug
    fixed in PR 3): recorded words-touched for the tiled/container paths
    must never exceed the dense-store estimate for the same query, the
    density <= 1e-3 sweep points must show the >= 4x container reduction,
    and density 0.5 must show no regression."""
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_query.json"
    if not path.exists():
        pytest.skip("no BENCH_query.json checked in")
    data = json.loads(path.read_text())
    rows = data.get("sparsity_sweep")
    if not rows:
        pytest.skip("BENCH_query.json predates the sparsity sweep")
    for row in rows:
        assert row["words_touched"] <= row["dense_words"], row["density"]
        assert row["words_touched"] <= row["words_touched_legacy"], row["density"]
        assert row["memory_words"] <= row["memory_words_legacy"], row["density"]
        if row["density"] <= 1e-3:
            assert row["reduction"] >= 4.0, row
        if row["density"] >= 0.5:
            assert row["words_touched"] == row["words_touched_legacy"], row
            assert row["memory_words"] == row["memory_words_legacy"], row
    for row in data.get("clean_fraction_sweep", []):
        tiled = row["backends"]["tiled_fused"]["words_touched"]
        dense = row["backends"]["fused"]["words_touched"]
        assert tiled <= dense, row["clean_fraction"]


def test_collapsed_launch_pricing_and_realised_counters():
    """Regression for the single-scan engine recalibration: the launch
    overhead now prices at most two dispatches (plus per-group switch
    overhead and a decode-staging factor), and that must NOT re-admit
    tiled_fused at cf <= 0.5 on the scalar threshold path -- while the
    plan-predicted words ordering still matches the realised ``info``
    counters on the scan path."""
    from repro.core.planner import estimate_words_touched
    from repro.query import BitmapIndex

    n, n_tiles = 8, 8
    realised = {}
    predicted = {}
    for cf in (0.0, 0.5, 0.95):
        bits = _bench_clean_fraction_bits(n, n_tiles, cf, seed=int(cf * 100) + 1)
        idx = BitmapIndex.from_dense(jnp.asarray(bits))
        stats = idx.store.member_stats(None)
        plan = plan_threshold(n, n // 2, stats=stats, fused_available=True)
        if cf <= 0.5:
            assert plan.algorithm != "tiled_fused", (cf, plan)
        else:
            assert plan.algorithm == "tiled_fused", (cf, plan)
        predicted[cf] = estimate_words_touched(
            "tiled_fused", n, n // 2, n_words=stats.n_words, stats=stats
        )
        idx.execute(Threshold(n // 2), backend="tiled_fused")
        info = idx.last_info
        assert info["engine"] == "scan"
        assert info["launches"] <= 2, (cf, info)
        realised[cf] = info["dirty_words_gathered"]
    # cheaper predictions must correspond to fewer realised words
    assert predicted[0.95] < predicted[0.5] < predicted[0.0]
    assert realised[0.95] < realised[0.5] < realised[0.0]


def test_plan_query_names_resolve():
    """plan_query outputs execute directly through the query layer."""
    bits, bm = _mk(10, 300, 0.3, seed=9)
    counts = bits.sum(0)
    cases = [
        (Threshold(1), counts >= 1),
        (Threshold(5), counts >= 5),
        (Interval(2, 6), (counts >= 2) & (counts <= 6)),
        (Interval(2, 6) & ~Threshold(8), (counts >= 2) & (counts <= 6) & ~(counts >= 8)),
    ]
    for q, expect in cases:
        plan = plan_query(q, 10)
        got = np.asarray(unpack(execute(bm, q), 300))
        np.testing.assert_array_equal(got, expect, err_msg=f"{q} via {plan.algorithm}")


def test_planner_picks_min_cost_candidate():
    """Regression: with member statistics in hand, the plan used to fall
    through to the scalar default -- selecting ssum (45056 cost words at
    clean_fraction <= 0.5) while its own candidate list priced fused at
    4608 whenever the fused kernel wasn't flagged available.  The stats
    path must end by picking the min-cost runnable candidate (the fused
    backend runs everywhere: Pallas on TPU, interpret/XLA elsewhere),
    with tiled_fused still owned by the advantage gate."""
    from repro.query import BitmapIndex

    n, n_tiles = 8, 8
    for cf in (0.0, 0.5, 0.95):
        bits = _bench_clean_fraction_bits(n, n_tiles, cf, seed=int(cf * 100) + 1)
        idx = BitmapIndex.from_dense(jnp.asarray(bits))
        stats = idx.store.member_stats(None)
        for fused_available in (True, False):
            p = plan_threshold(n, n // 2, stats=stats,
                               fused_available=fused_available)
            cands = dict(p.candidates)
            non_tiled = {k: v for k, v in cands.items() if k != "tiled_fused"}
            if p.algorithm != "tiled_fused":
                best = min(non_tiled, key=non_tiled.get)
                assert p.algorithm == best, (cf, fused_available, p, non_tiled)
                assert p.cost == non_tiled[best]
        # the chosen plan executes bit-identically to the oracle backend
        got = np.asarray(idx.execute(Threshold(n // 2)))
        ref = np.asarray(idx.execute(Threshold(n // 2), backend="scancount"))
        np.testing.assert_array_equal(got, ref, err_msg=f"cf={cf}")
    # the concrete regression: cf=0.5 with fused "unavailable" picks fused
    # by cost, never the 10x-priced ssum fallback
    bits = _bench_clean_fraction_bits(n, n_tiles, 0.5, seed=51)
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    p = plan_threshold(n, n // 2, stats=idx.store.member_stats(None),
                       fused_available=False)
    assert p.algorithm == "fused", p
    # scalar path (no stats) keeps the documented default rules
    assert plan_threshold(16, 8, fused_available=False).algorithm == "ssum"


# ---------------------------------------------------------------------------
# Feedback-calibrated planner (core.calibration)
# ---------------------------------------------------------------------------


@pytest.fixture()
def _no_calibration():
    """Tests below install calibrations; never leak one into other tests."""
    from repro.core.calibration import clear_calibration

    clear_calibration()
    yield
    clear_calibration()


def test_identity_calibration_never_inverts_words_ranking(_no_calibration):
    """Regression anchor: a uniform words->us rate must reproduce the raw
    words-touched ranking exactly -- same chosen backend, and every
    candidate's calibrated price a fixed rescale of its words price -- at
    clean fractions 0.0 / 0.5 / 0.95."""
    from repro.core.calibration import Calibration, clear_calibration, set_calibration
    from repro.query import BitmapIndex

    n, n_tiles = 8, 8
    for cf in (0.0, 0.5, 0.95):
        bits = _bench_clean_fraction_bits(n, n_tiles, cf, seed=int(cf * 100) + 1)
        idx = BitmapIndex.from_dense(jnp.asarray(bits))
        stats = idx.store.member_stats(None)
        clear_calibration()
        base = plan_threshold(n, n // 2, stats=stats, fused_available=True)
        assert base.cost_us is None and base.candidates_us == ()

        set_calibration(Calibration.identity(ALGORITHMS))
        calibrated = plan_threshold(n, n // 2, stats=stats, fused_available=True)
        assert calibrated.algorithm == base.algorithm, (cf, base, calibrated)
        words = dict(calibrated.candidates)
        assert calibrated.candidates_us, cf
        for backend, us in calibrated.candidates_us:
            assert us == pytest.approx(words[backend] / 1024.0), (cf, backend)
        # the µs list is sorted: a backend that touches fewer words is
        # never priced above one that touches more
        prices = [us for _, us in calibrated.candidates_us]
        assert prices == sorted(prices)
        assert calibrated.cost_us == pytest.approx(
            calibrated.cost / 1024.0
        ), (cf, calibrated)


def test_skewed_calibration_steers_selection(_no_calibration):
    """The point of calibration: when measurement says the words-best
    backend is slow on this device, the planner picks the measured-fast
    one (and says so in the rationale)."""
    from repro.core.calibration import Calibration, set_calibration
    from repro.query import BitmapIndex

    n, n_tiles = 8, 8
    bits = _bench_clean_fraction_bits(n, n_tiles, 0.0, seed=1)
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    stats = idx.store.member_stats(None)
    base = plan_threshold(n, n // 2, stats=stats, fused_available=True)
    others = [b for b, _ in base.candidates if b not in (base.algorithm, "tiled_fused")]
    assert others, base

    skew = Calibration.identity(ALGORITHMS)
    skew.us_per_kword[base.algorithm] = 1e6  # "measured" terrible
    set_calibration(skew)
    steered = plan_threshold(n, n // 2, stats=stats, fused_available=True)
    assert steered.algorithm != base.algorithm, steered
    assert steered.algorithm in others
    assert "calibrated" in steered.rationale


def test_calibration_cost_us_monotone_in_words():
    from repro.core.calibration import Calibration

    c = Calibration(device="x", us_per_kword={"ssum": 3.0}, dispatch_us={"ssum": 50.0})
    prices = [c.cost_us("ssum", w) for w in (0, 1024, 4096, 1 << 20)]
    assert prices == sorted(prices) and prices[0] == 50.0
    assert c.cost_us("nope", 1024) is None
    assert c.cost_us("ssum", None) is None


def test_calibration_observe_ewma_and_clamp():
    from repro.core.calibration import Calibration

    c = Calibration.identity(("ssum",))
    c.observe("ssum", 1024, 1.0)  # absurd 1s observation: clamped to 8x
    assert c.us_per_kword["ssum"] == pytest.approx(0.8 * 1.0 + 0.2 * 8.0)
    assert c.samples["ssum"] == 1
    # unknown backends are admitted at the observed rate
    c.observe("looped", 1024, 1e-6)
    assert c.us_per_kword["looped"] == pytest.approx(1.0)
    # junk observations are ignored
    before = dict(c.us_per_kword)
    c.observe("ssum", None, 1.0)
    c.observe("ssum", 0, 1.0)
    c.observe("ssum", 1024, 0.0)
    assert c.us_per_kword == before


def test_calibration_persist_roundtrip(tmp_path, _no_calibration):
    from repro.core.calibration import Calibration, get_calibration
    from repro.persist import load_calibration, save_calibration
    from repro.persist.calibration import ensure_calibration

    c = Calibration(device="identity", us_per_kword={"ssum": 2.5, "fused": 0.5},
                    dispatch_us={"fused": 40.0}, samples={"ssum": 3})
    target = save_calibration(c, tmp_path)
    assert target.name == "calibration.json"
    back = load_calibration(tmp_path)
    assert back is not None and back.to_obj() == c.to_obj()

    # device-mismatched constants are stale: refuse unless asked
    c2 = Calibration(device="some_tpu", us_per_kword={"ssum": 9.0})
    save_calibration(c2, tmp_path / "other")
    assert load_calibration(tmp_path / "other") is None
    loose = load_calibration(tmp_path / "other", allow_mismatch=True)
    assert loose is not None and loose.us_per_kword["ssum"] == 9.0

    # ensure_calibration: load-or-measure, installs as process-active
    got = ensure_calibration(tmp_path, repeats=1, n_words=256)
    assert got.to_obj() == c.to_obj()  # loaded, not re-measured
    assert get_calibration() is got


def test_measure_calibration_surfaces_backend_errors(monkeypatch, _no_calibration):
    """A backend that fails while it is measured (a kernel the compiler
    refuses, say) raises out of the measurement pass instead of silently
    vanishing from the constants.  Only a backend the planner never offers
    on this device -- ``fused`` off the TPU -- is skipped."""
    from repro.core.calibration import measure_calibration
    from repro.query import BitmapIndex
    from repro.query.index import _fused_available

    calib = measure_calibration(("fused", "ssum"), n=8, n_words=64, repeats=1)
    assert "ssum" in calib.us_per_kword
    assert ("fused" in calib.us_per_kword) == _fused_available()

    real = BitmapIndex.execute

    def failing(self, query, *, backend=None, block_words=None):
        if backend == "ssum":
            raise RuntimeError("ssum refused by the compiler")
        return real(self, query, backend=backend, block_words=block_words)

    monkeypatch.setattr(BitmapIndex, "execute", failing)
    with pytest.raises(RuntimeError, match="refused by the compiler"):
        measure_calibration(("wide_or", "ssum"), n=8, n_words=64, repeats=1)


def test_plan_memo_invalidated_by_calibration_swap(_no_calibration):
    """Swapping calibration constants must not serve stale memoized plans:
    the memo key embeds the calibration generation."""
    from repro.core.calibration import Calibration, set_calibration
    from repro.query import BitmapIndex, clear_compiled_cache

    clear_compiled_cache()
    idx = BitmapIndex.from_dense(jnp.asarray(_mk(8, 300, 0.3, seed=4)[0]))
    q = Threshold(4)
    assert idx.explain(q).memo == "miss"
    assert idx.explain(q).memo == "hit"
    set_calibration(Calibration.identity(ALGORITHMS))
    fresh = idx.explain(q)
    assert fresh.memo == "miss", "stale pre-calibration plan served"
    assert fresh.cost_us is not None
    assert idx.explain(q).memo == "hit"
    clear_compiled_cache()


def test_topology_swap_resets_stale_constants(_no_calibration):
    """Constants recorded on another device topology must be dropped, not
    EWMA-blended: the observe clamp anchors every new sample to within 8x
    of the dead running value, so a swapped device would be mispriced
    forever (a 1e9 us/kword constant can never decay to ~1e3)."""
    from repro.core.calibration import Calibration, device_signature

    dead = Calibration(device="tpux8", us_per_kword={"ssum": 1e9},
                       samples={"ssum": 500})
    assert device_signature() != "tpux8" and dead.is_stale()
    dead.observe("ssum", 1024, 1e-3)  # 1000us for 1k words
    assert dead.device == device_signature() and not dead.is_stale()
    # re-admitted at the OBSERVED rate, not clamped around the dead value
    assert dead.us_per_kword["ssum"] == pytest.approx(1000.0)
    assert dead.samples["ssum"] == 1

    # portable calibrations are never stale: identity keeps its constants
    ident = Calibration.identity(("ssum",))
    assert not ident.is_stale("tpux8") and not ident.is_stale()
    ident.observe("ssum", 1024, 1e-6)
    assert ident.samples["ssum"] == 1 and ident.device == "identity"


def test_stale_active_calibration_reset_on_read(_no_calibration):
    """get_calibration() topology-checks the installed constants and bumps
    the plan-memo generation when it has to reset them -- memoized plans
    priced with dead constants must not be served."""
    from repro.core.calibration import (
        Calibration,
        calibration_generation,
        device_signature,
        get_calibration,
        set_calibration,
    )

    stale = Calibration(device="gpux64", us_per_kword={"ssum": 1e9})
    set_calibration(stale)
    gen = calibration_generation()
    active = get_calibration()
    assert active is stale
    assert active.device == device_signature() and not active.us_per_kword
    assert calibration_generation() == gen + 1
    # subsequent reads are quiet: no further resets or generation bumps
    assert get_calibration() is stale
    assert calibration_generation() == gen + 1


def test_load_calibration_adopts_legacy_device_stamp(tmp_path, _no_calibration):
    """Files written before signatures carried device counts stamped the
    bare backend name; loading must adopt the full signature so the
    staleness check doesn't immediately wipe the loaded constants."""
    import jax

    from repro.core.calibration import Calibration, device_signature
    from repro.persist import load_calibration, save_calibration

    legacy = Calibration(device=jax.default_backend(),
                         us_per_kword={"ssum": 2.0}, samples={"ssum": 4})
    save_calibration(legacy, tmp_path)
    back = load_calibration(tmp_path)
    assert back is not None
    assert back.device == device_signature() and not back.is_stale()
    assert back.us_per_kword == {"ssum": 2.0}

    current = Calibration(device=device_signature(), us_per_kword={"ssum": 1.0})
    save_calibration(current, tmp_path / "sig")
    back = load_calibration(tmp_path / "sig")
    assert back is not None and not back.is_stale()
