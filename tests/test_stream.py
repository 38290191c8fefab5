"""Streaming update engine: delta overlay == rebuild, for every backend.

The contract under test: a ``StreamingIndex`` that absorbed any sequence
of mutations answers EVERY planner backend bit-identically to a
``BitmapIndex`` rebuilt from scratch over the mutated data -- before and
after compaction, sharded and unsharded -- and materialized views stay
fresh while their refresh touches only the mutated tiles.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.bitmaps import unpack
from repro.core.threshold import ALGORITHMS
from repro.query import And, BitmapIndex, Col, Interval, Not, Threshold
from repro.stream import CompactionPolicy, DeltaStore, StreamingIndex

SPAN = 64 * 32  # bits per tile at the default granularity


def _bits(n, r, density=0.2, seed=0):
    rng = np.random.default_rng(seed)
    return rng.random((n, r)) < density


def _names(n):
    return [f"c{i}" for i in range(n)]


def _stream(bits, *, n_shards=None, policy=None):
    names = _names(bits.shape[0])
    idx = BitmapIndex.from_dense(jnp.asarray(bits), names)
    if n_shards:
        idx = idx.shard(n_shards=n_shards)
    return StreamingIndex(idx, policy=policy or CompactionPolicy(auto=False))


def _result(s, q, **kw):
    out = s.execute(q, **kw)
    if hasattr(out, "gather"):
        out = out.gather()
    return np.asarray(unpack(out, s.r))


def _oracle(bits, q, **kw):
    idx = BitmapIndex.from_dense(jnp.asarray(bits), _names(bits.shape[0]))
    return np.asarray(unpack(idx.execute(q, **kw), bits.shape[1]))


def _t_for(alg, n):
    return {"wide_or": 1, "wide_and": n}.get(alg, 3)


# ---------------------------------------------------------------------------
# Update semantics, per mutation kind, vs the rebuild oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [None, 3])
def test_fused_bare_threshold_reads_overlay_planes(n_shards):
    """Over base + delta the fused kernel reads the overlay's plane view:
    the words match the rows path and the rebuild oracle, mutated tiles
    included."""
    bits = _bits(6, 3 * SPAN + 517, density=0.3, seed=4)
    s = _stream(bits, n_shards=n_shards)
    pos = [0, 33, SPAN + 7, 3 * SPAN + 500]
    s.set_bits("c4", pos)
    s.clear_bits("c1", np.arange(0, 3 * SPAN, 5))
    bits = bits.copy()
    bits[4, pos] = True
    bits[1, np.arange(0, 3 * SPAN, 5)] = False
    q = Threshold(2, over=("c4", "c1", "c3", "c0"))
    got = _result(s, q, backend="fused")
    np.testing.assert_array_equal(got, _result(s, q, backend="ssum"))
    np.testing.assert_array_equal(got, bits[[4, 1, 3, 0]].sum(0) >= 2)
    idx = s.index()
    _result(s, q, backend="fused")
    assert idx.last_info["members"] == "planes"
    assert idx.last_info["dirty_words_gathered"] == 0


class TestMutationKinds:
    N, R = 5, 4 * SPAN + 517  # partial final tile by construction

    def _parity(self, s, bits):
        for q in (Threshold(2), Interval(1, 3), And(Threshold(1), Not(Col("c0")))):
            assert (_result(s, q) == _oracle(bits, q)).all(), q

    def test_set_bits(self):
        bits = _bits(self.N, self.R, seed=1)
        s = _stream(bits)
        pos = [0, 31, 32, SPAN - 1, SPAN, self.R - 1]
        s.set_bits("c1", pos)
        bits = bits.copy()
        bits[1, pos] = True
        self._parity(s, bits)

    def test_clear_bits(self):
        bits = _bits(self.N, self.R, seed=2)
        s = _stream(bits)
        pos = np.arange(100, 4000, 7)
        s.clear_bits("c2", pos)
        bits = bits.copy()
        bits[2, pos] = False
        self._parity(s, bits)

    def test_set_then_clear_idempotence(self):
        """set; clear of the same bits restores the base exactly -- and a
        second identical round changes nothing."""
        bits = _bits(self.N, self.R, seed=3)
        s = _stream(bits)
        pos = [5, 77, SPAN + 3, 3 * SPAN + 100]
        for _ in range(2):
            s.set_bits("c0", pos)
            s.clear_bits("c0", pos)
        # bits that were already set must stay cleared-to-zero only if they
        # started zero; replay the semantics on the oracle side
        mut = bits.copy()
        mut[0, pos] = False
        self._parity(s, mut)

    def test_update_inside_all_zero_and_all_one_tile(self):
        bits = _bits(self.N, self.R, seed=4)
        bits[3, :SPAN] = False  # tile 0 of c3 all-zero
        bits[3, SPAN : 2 * SPAN] = True  # tile 1 of c3 all-one
        s = _stream(bits)
        s.set_bits("c3", [10])  # zero tile gains a bit
        s.clear_bits("c3", [SPAN + 10])  # one tile loses a bit
        mut = bits.copy()
        mut[3, 10] = True
        mut[3, SPAN + 10] = False
        self._parity(s, mut)
        # and back: restore both tiles to clean constants
        s.clear_bits("c3", [10])
        s.set_bits("c3", [SPAN + 10])
        self._parity(s, bits)

    def test_append_rows_crossing_tile_boundary(self):
        """Appended rows fill the partial final tile AND spill into new
        tiles; every column grows, absent bits default to zero."""
        bits = _bits(self.N, self.R, seed=5)
        s = _stream(bits)
        k = (SPAN - self.R % SPAN) + SPAN // 2  # crosses the tile boundary
        app = _bits(self.N, k, density=0.4, seed=6)
        s.append_rows(app)
        assert s.r == self.R + k
        mut = np.concatenate([bits, app], axis=1)
        self._parity(s, mut)

    def test_partial_final_tile_update(self):
        bits = _bits(self.N, self.R, seed=7)
        s = _stream(bits)
        s.set_bits("c4", [self.R - 1, self.R - 17])
        mut = bits.copy()
        mut[4, [self.R - 1, self.R - 17]] = True
        self._parity(s, mut)
        with pytest.raises(ValueError):
            s.set_bits("c4", [self.R])  # outside the universe

    def test_compaction_preserves_results_and_is_tile_granular(self):
        bits = _bits(self.N, self.R, seed=8)
        s = _stream(bits)
        s.set_bits("c0", [3, SPAN + 3])
        mut = bits.copy()
        mut[0, [3, SPAN + 3]] = True
        before = _result(s, Threshold(2))
        base_store = s._base.store
        assert s.compact() is True
        # untouched columns share their classified tiles with the old base
        assert s._base.store._cols[1] is base_store._cols[1]
        assert s.delta_words == 0
        assert (_result(s, Threshold(2)) == before).all()
        self._parity(s, mut)


# ---------------------------------------------------------------------------
# The acceptance sweep: 1k random single-bit updates, every backend,
# pre/post compaction, sharded and unsharded
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards", [None, 4])
def test_1k_random_updates_every_backend_matches_rebuild(n_shards):
    n, r = 6, 8 * SPAN + 321
    bits = _bits(n, r, seed=11)
    s = _stream(bits, n_shards=n_shards)
    rng = np.random.default_rng(12)
    mut = bits.copy()
    cols = rng.integers(0, n, 1000)
    pos = rng.integers(0, r, 1000)
    on = rng.random(1000) < 0.5
    # dedupe to the LAST write per (col, pos): update() applies all sets
    # before all clears, so a colliding draw would otherwise make the
    # sequential numpy oracle and the batched apply legitimately disagree
    last = {(int(c), int(p)): bool(o) for c, p, o in zip(cols, pos, on)}
    sets: dict = {}
    clears: dict = {}
    for (c, p), o in last.items():
        (sets if o else clears).setdefault(f"c{c}", []).append(p)
        mut[c, p] = o
    s.update(sets=sets, clears=clears)

    def check(tag):
        for alg in ALGORITHMS:
            t = _t_for(alg, n)
            got = _result(s, Threshold(t), backend=alg)
            want = _oracle(mut, Threshold(t), backend=alg)
            assert (got == want).all(), (tag, alg)

    check("pre-compaction")
    assert s.compact() is True
    check("post-compaction")


def test_stale_overlay_index_is_a_consistent_snapshot():
    """An overlay index captured with a live delta must keep answering AS
    OF that instant, identically on every backend, after further
    mutations land -- the stale-reference guarantee extends to overlays."""
    bits = _bits(4, 3 * SPAN + 99, seed=41)
    s = _stream(bits)
    s.set_bits("c0", [7])
    mut_then = bits.copy()
    mut_then[0, 7] = True
    stale = s.index()  # overlay over base + the one-bit delta
    s.clear_bits("c1", np.arange(0, 2000))  # later mutation, same delta store
    for backend in ("fused", "tiled_fused", "ssum", "scancount"):
        got = np.asarray(unpack(stale.execute(Threshold(2), backend=backend),
                                stale.store.r))
        want = _oracle(mut_then, Threshold(2), backend=backend)
        assert (got == want).all(), backend
    # planner statistics describe the same instant too
    exp = BitmapIndex.from_dense(jnp.asarray(mut_then), _names(4))
    assert stale.store.cardinalities == exp.store.cardinalities


def test_overlay_planner_sees_mutated_stats():
    """The planner prices the OVERLAID data: dirtying a clean index's tiles
    must be visible in the member statistics it plans from."""
    bits = np.zeros((4, 8 * SPAN), bool)
    bits[:, :7] = True  # one dirty tile, rest all-zero
    s = _stream(bits)
    clean_stats = s.index().store.member_stats(None)
    rng = np.random.default_rng(0)
    for c in range(4):
        s.set_bits(f"c{c}", rng.integers(0, 8 * SPAN, 2000))
    dirty_stats = s.index().store.member_stats(None)
    assert dirty_stats.clean_fraction < clean_stats.clean_fraction
    assert dirty_stats.dirty_words > clean_stats.dirty_words
    plan = s.explain(Threshold(2))
    assert plan.algorithm in ALGORITHMS + ("circuit",)


# ---------------------------------------------------------------------------
# Materialized views
# ---------------------------------------------------------------------------


class TestMaterializedViews:
    N, R = 6, 6 * SPAN + 123

    def _fresh(self, s, mut, lo=2, hi=4):
        counts = mut.sum(0)
        want = (counts >= lo) & (counts <= hi)
        col = s.column("mid")
        if hasattr(col, "gather"):
            col = col.gather()
        assert (np.asarray(unpack(col, s.r)) == want).all()
        assert s.count(Col("mid")) == int(want.sum())

    def test_freshness_after_each_mutation_kind(self):
        bits = _bits(self.N, self.R, seed=21)
        s = _stream(bits)
        s.materialize("mid", Interval(2, 4))
        mut = bits.copy()
        self._fresh(s, mut)
        # set
        s.set_bits("c0", [9, SPAN + 9])
        mut[0, [9, SPAN + 9]] = True
        self._fresh(s, mut)
        # clear
        s.clear_bits("c1", np.arange(0, 2000, 3))
        mut[1, np.arange(0, 2000, 3)] = False
        self._fresh(s, mut)
        # set-then-clear back
        s.set_bits("c2", [42])
        s.clear_bits("c2", [42])
        mut[2, 42] = False
        self._fresh(s, mut)
        # append crossing a tile boundary
        k = SPAN
        app = _bits(self.N, k, density=0.5, seed=22)
        s.append_rows(app)
        mut = np.concatenate([mut, app], axis=1)
        self._fresh(s, mut)
        # compaction keeps the view column and its count
        assert s.compact() is True
        self._fresh(s, mut)

    def test_refresh_touches_only_mutated_tiles(self):
        """The words-touched counter: refresh work scales with the mutated
        tiles, never the universe."""
        bits = _bits(self.N, self.R, seed=23)
        s = _stream(bits)
        s.materialize("mid", Interval(2, 4))
        s.set_bits("c3", [2 * SPAN + 5])  # exactly one tile touched
        s.refresh()
        info = s.view_info("mid")
        assert info["tiles_refreshed"] == 1
        tw = s.tile_words
        # at most the support columns' words for ONE tile + the output write
        assert info["words_touched"] <= (self.N + 1) * tw
        full_sweep = (self.N + 1) * s.index().store.n_tiles * tw
        assert info["words_touched"] < full_sweep / 4
        # an untouched query leaves nothing pending
        s.refresh()
        assert s.view_info("mid")["tiles_refreshed"] == 1  # unchanged record

    def test_view_binds_member_set_at_registration(self):
        """over=None means "all columns NOW": adding the view column (or a
        later view) must not change an existing view's member set."""
        bits = _bits(3, 2 * SPAN, seed=24)
        s = _stream(bits)
        s.materialize("two", Threshold(2))
        s.materialize("any", Threshold(1))  # second view; schema now 5 wide
        counts = bits.sum(0)
        for name, want in (("two", counts >= 2), ("any", counts >= 1)):
            col = s.column(name)
            assert (np.asarray(unpack(col, s.r)) == want).all(), name
            assert s.count(Col(name)) == int(want.sum())

    def test_view_over_view_chains(self):
        bits = _bits(4, 2 * SPAN + 77, seed=25)
        s = _stream(bits)
        s.materialize("two", Threshold(2))
        s.materialize("promo", And(Col("two"), Col("c0")))
        mut = bits.copy()
        s.set_bits("c1", [5, SPAN + 5])
        mut[1, [5, SPAN + 5]] = True
        counts = mut.sum(0)
        want = (counts >= 2) & mut[0]
        col = s.column("promo")
        assert (np.asarray(unpack(col, s.r)) == want).all()
        assert s.count(Col("promo")) == int(want.sum())

    def test_view_with_true_at_zero_weight_masks_padding(self):
        """A truth table with f(0)=1 (Interval(0, 1)) must not leak set
        bits past r into the partial final tile -- the popcount-delta
        count would silently drift."""
        r = SPAN + 100  # partial final tile
        bits = _bits(3, r, seed=29)
        s = _stream(bits)
        s.materialize("mid", Interval(0, 1))
        mut = bits.copy()
        s.set_bits("c0", [r - 1])
        mut[0, r - 1] = True
        counts = mut.sum(0)
        want = counts <= 1
        assert (np.asarray(unpack(s.column("mid"), s.r)) == want).all()
        assert s.count(Col("mid")) == int(want.sum())

    def test_constant_view_extends_over_appended_rows(self):
        """A query that folds to a constant has EMPTY circuit support --
        append_rows must still refresh it over the new rows (regression:
        it stayed all-zero there forever)."""
        r = SPAN + 40
        bits = _bits(3, r, seed=30)
        s = _stream(bits)
        s.materialize("always", Threshold(0))  # constant-true
        assert s.count(Col("always")) == r
        s.append_rows(_bits(3, 60, seed=31))
        assert s.r == r + 60
        assert s.count(Col("always")) == r + 60
        col = s.column("always")
        assert (np.asarray(unpack(col, s.r)) == True).all()  # noqa: E712
        s.compact()
        assert s.count(Col("always")) == r + 60

    def test_views_cannot_be_mutated_directly(self):
        bits = _bits(3, SPAN, seed=26)
        s = _stream(bits)
        s.materialize("mid", Interval(1, 2))
        with pytest.raises(ValueError):
            s.set_bits("mid", [0])

    def test_sharded_view_freshness(self):
        bits = _bits(self.N, self.R, seed=27)
        s = _stream(bits, n_shards=3)
        s.materialize("mid", Interval(2, 4))
        mut = bits.copy()
        rng = np.random.default_rng(28)
        pos = rng.integers(0, self.R, 64)
        s.set_bits("c4", pos)
        mut[4, pos] = True
        self._fresh(s, mut)
        info = s.view_info("mid")
        assert info["tiles_refreshed"] <= np.unique(pos // SPAN).size


# ---------------------------------------------------------------------------
# Compaction policy
# ---------------------------------------------------------------------------


def test_auto_compaction_policy_triggers():
    bits = _bits(4, 8 * SPAN, density=0.01, seed=31)
    s = _stream(
        bits,
        policy=CompactionPolicy(min_delta_words=2 * 64, max_delta_ratio=0.0),
    )
    assert s.compactions == 0
    s.set_bits("c0", [0])  # one tile: 64 words < threshold
    assert s.compactions == 0 and s.delta_words > 0
    s.set_bits("c1", [0, SPAN, 2 * SPAN])  # pushes past min_delta_words
    assert s.compactions == 1
    assert s.delta_words == 0


def test_compact_noop_when_empty():
    s = _stream(_bits(2, SPAN, seed=32))
    assert s.compact() is False


# ---------------------------------------------------------------------------
# DeltaStore unit behaviour
# ---------------------------------------------------------------------------


def test_delta_store_patch_and_popcount_delta():
    bits = np.zeros((2, 2 * SPAN), bool)
    bits[1, :SPAN] = True
    store = BitmapIndex.from_dense(jnp.asarray(bits), ["a", "b"]).store
    d = DeltaStore(store)
    assert d.empty
    t = d.set_bits(0, [3, 35])
    assert t == [0] and not d.empty
    assert d.card_delta(0) == 2
    words = np.zeros(64, np.uint32)
    words[0] = 0b1
    delta = d.patch_tile(0, 0, words)
    assert delta == -1  # 2 bits -> 1 bit
    assert d.card_delta(0) == 1
    # clearing the all-one tile of column b
    d.clear_bits(1, [7])
    assert d.card_delta(1) == -1
    assert d.delta_words == 2 * 64


# ---------------------------------------------------------------------------
# Schema growth (add_data_column) + append_rows row ranges
# ---------------------------------------------------------------------------


class TestSchemaGrowth:
    @pytest.mark.parametrize("n_shards", [None, 3])
    def test_add_data_column_then_mutate(self, n_shards):
        bits = _bits(4, 2 * SPAN + 100, seed=17)
        s = _stream(bits, n_shards=n_shards)
        assert "c9" not in s
        s.add_data_column("c9")
        assert "c9" in s and s.count(Col("c9")) == 0
        # a fresh column participates in every mutation kind
        rows = [0, SPAN + 5, s.r - 1]
        s.update(sets={"c9": rows})
        assert _result(s, Col("c9")).nonzero()[0].tolist() == sorted(rows)
        oracle = np.concatenate([bits, np.zeros((1, bits.shape[1]), bool)])
        oracle[4, rows] = True
        got = _result(s, Threshold(2, over=[Col("c0"), Col("c1"), Col("c9")]))
        want = _oracle(oracle, Threshold(2, over=[Col("c0"), Col("c1"), Col("c4")]))
        np.testing.assert_array_equal(got, want)

    def test_add_data_column_with_payload(self):
        bits = _bits(3, SPAN + 40, seed=18)
        s = _stream(bits)
        payload = np.zeros(s.index().n_words, np.uint32)
        payload[0] = 0b1011
        s.add_data_column("extra", payload)
        assert s.count(Col("extra")) == 3
        assert _result(s, Col("extra")).nonzero()[0].tolist() == [0, 1, 3]

    def test_add_data_column_validation(self):
        s = _stream(_bits(2, 200, seed=19))
        with pytest.raises(ValueError):
            s.add_data_column("c0")  # duplicate

    def test_add_data_column_flushes_pending_appends(self):
        """Schema growth compacts first: pending appends live in a read-only
        overlay that cannot grow columns, and must not be lost."""
        bits = _bits(3, 300, seed=20)
        s = _stream(bits)
        s.append_rows({"c0": np.ones(40, bool)})
        s.add_data_column("late")
        assert s.r == 340 and s.count(Col("c0")) == int(bits[0].sum()) + 40
        assert s.count(Col("late")) == 0

    def test_append_rows_returns_row_range(self):
        bits = _bits(2, 150, seed=21)
        s = _stream(bits)
        assert s.append_rows({}) == (150, 150)
        start, stop = s.append_rows({"c1": np.array([True, False, True])})
        assert (start, stop) == (150, 153)
        assert _result(s, Col("c1")).nonzero()[0].tolist() == sorted(
            np.nonzero(bits[1])[0].tolist() + [150, 152]
        )
        start, stop = s.append_rows({"c0": np.ones(5, bool)})
        assert (start, stop) == (153, 158)
