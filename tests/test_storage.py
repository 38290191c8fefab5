"""Storage engine: TileStore classification/layout, compressed containers
(sparse + run) round trips and crossover edges, tiled execution vs the
scancount oracle, planner cost model, stats-cache fix, shim deprecation."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitmaps import pack, unpack
from repro.core.circuits import build_interval_circuit, build_threshold_circuit
from repro.core.threshold import ALGORITHMS
from repro.query import And, BitmapIndex, Col, Interval, Not, Parity, Threshold
from repro.storage import (
    CONT_DENSE,
    CONT_NONE,
    CONT_RUN,
    CONT_SPARSE,
    TILE_DIRTY,
    TILE_ONE,
    TILE_RUN,
    TILE_ZERO,
    MemberStats,
    TileStore,
    member_stats_info,
    run_max_intervals,
    run_tiled_circuit,
    sparse_max_positions,
)

TW = 64
SPAN = TW * 32  # bit positions per tile


def _tiled_bits(n, n_tiles, clean_fraction, seed=0, tail_bits=0):
    """Columns whose tiles are all-zero/all-one with prob clean_fraction."""
    rng = np.random.default_rng(seed)
    r = n_tiles * SPAN + tail_bits
    bits = np.zeros((n, r), bool)
    total = n_tiles + (1 if tail_bits else 0)
    for i in range(n):
        for tj in range(total):
            lo, hi = tj * SPAN, min((tj + 1) * SPAN, r)
            u = rng.random()
            if u < clean_fraction / 2:
                pass  # all-zero
            elif u < clean_fraction:
                bits[i, lo:hi] = True
            else:
                bits[i, lo:hi] = rng.random(hi - lo) < 0.4
    return bits


# ---------------------------------------------------------------------------
# TileStore layout + classification
# ---------------------------------------------------------------------------


def test_tile_classes_and_dirty_packing():
    r = 4 * SPAN
    bits = np.zeros((3, r), bool)
    bits[0, :SPAN] = True              # tile 0: all-one
    bits[1, SPAN : SPAN + 100] = True  # tile 1: run (single transition)
    bits[2] = np.random.default_rng(0).random(r) < 0.5  # all dirty
    store = TileStore.from_packed(pack(jnp.asarray(bits)), tile_words=TW, r=r)
    assert store.classes[0].tolist() == [TILE_ONE, TILE_ZERO, TILE_ZERO, TILE_ZERO]
    assert store.classes[1].tolist() == [TILE_ZERO, TILE_RUN, TILE_ZERO, TILE_ZERO]
    assert (store.classes[2] == TILE_DIRTY).all()
    # dirty array holds exactly the dirty/run tiles; offsets point into it
    assert store.dirty.shape == (1 + 4, TW)
    assert store.dirty_index[1, 1] >= 0 and store.dirty_index[0, 0] == -1
    np.testing.assert_array_equal(np.asarray(store.densify()), np.asarray(pack(jnp.asarray(bits))))
    # per-column build-time stats
    assert store.col_stats[0].cardinality == SPAN
    assert store.col_stats[0].runcount == 2
    assert store.col_stats[1].runcount == 3
    assert store.col_stats[2].n_dirty_tiles == 4


def test_partial_final_tile_is_conservative_and_correct():
    r = 2 * SPAN + 777  # final tile partial
    bits = np.ones((2, r), bool)
    store = TileStore.from_packed(pack(jnp.asarray(bits)), tile_words=TW, r=r)
    assert store.n_tiles == 3
    assert (store.classes[:, :2] == TILE_ONE).all()
    # padded words are zero, so an all-ones partial tile classifies dirty/run
    assert (store.classes[:, 2] >= TILE_DIRTY).all()
    np.testing.assert_array_equal(np.asarray(store.densify()), np.asarray(pack(jnp.asarray(bits))))


def test_append_replace_share_and_reclassify():
    bits = _tiled_bits(4, 6, 0.5, seed=1)
    bm = np.asarray(pack(jnp.asarray(bits)))
    store = TileStore.from_packed(bm)
    grown = store.append(bm[0])
    assert grown.n == 5 and store.n == 4
    np.testing.assert_array_equal(grown.classes[4], store.classes[0])
    swapped = grown.replace(2, np.zeros(store.n_words, np.uint32))
    assert (swapped.classes[2] == TILE_ZERO).all()
    assert swapped.col_stats[2].cardinality == 0
    np.testing.assert_array_equal(
        np.asarray(swapped.densify())[[0, 1, 3, 4]], np.asarray(grown.densify())[[0, 1, 3, 4]]
    )


def test_apply_tile_updates_is_tile_granular():
    """Only touched tiles reclassify; untouched columns share _Column
    objects outright and cardinality moves by popcount deltas."""
    bits = _tiled_bits(4, 6, 0.5, seed=9, tail_bits=77)
    store = TileStore.from_packed(np.asarray(pack(jnp.asarray(bits))))
    tw = store.tile_words
    new_tile = np.zeros(tw, np.uint32)
    new_tile[:3] = 0xFFFFFFFF
    updated = store.apply_tile_updates({1: {2: new_tile}})
    # untouched columns are shared, not copied
    for i in (0, 2, 3):
        assert updated._cols[i] is store._cols[i]
    dense = np.asarray(updated.densify())
    base = np.asarray(store.densify())
    np.testing.assert_array_equal(dense[[0, 2, 3]], base[[0, 2, 3]])
    np.testing.assert_array_equal(dense[1, 2 * tw : 3 * tw], new_tile)
    np.testing.assert_array_equal(dense[1, : 2 * tw], base[1, : 2 * tw])
    old_tile_pop = int(np.unpackbits(
        base[1, 2 * tw : 3 * tw].view(np.uint8)).sum())
    assert updated.cardinalities[1] == store.cardinalities[1] - old_tile_pop + 96


def test_apply_tile_updates_class_transitions_and_growth():
    bits = _tiled_bits(2, 4, 0.0, seed=10)
    store = TileStore.from_packed(np.asarray(pack(jnp.asarray(bits))))
    tw = store.tile_words
    zeros = np.zeros(tw, np.uint32)
    ones = np.full(tw, 0xFFFFFFFF, np.uint32)
    updated = store.apply_tile_updates({0: {0: zeros, 1: ones}})
    assert updated.classes_word[0, 0] == TILE_ZERO
    assert updated.classes_word[0, 1] == TILE_ONE
    assert updated.dirty_index[0, 0] == -1 and updated.dirty_index[0, 1] == -1
    # universe growth: new tiles default all-zero everywhere
    grown = store.apply_tile_updates({}, r=store.r + 3 * SPAN)
    assert grown.n_tiles == store.n_tiles + 3
    assert (grown.classes_word[:, store.n_tiles :] == TILE_ZERO).all()
    np.testing.assert_array_equal(
        np.asarray(grown.densify())[:, : store.n_words], np.asarray(store.densify())
    )
    assert grown.cardinalities == store.cardinalities
    with pytest.raises(ValueError):
        store.apply_tile_updates({}, r=store.r - 1)  # no shrinking
    with pytest.raises(ValueError):
        store.apply_tile_updates({0: {99: zeros}})  # tile out of range


def test_run_tiled_circuit_restricted_to_tiles():
    bits = _tiled_bits(5, 8, 0.6, seed=11, tail_bits=33)
    store = TileStore.from_packed(np.asarray(pack(jnp.asarray(bits))))
    circ = build_threshold_circuit(5, 2, "ssum")
    full, info_full = run_tiled_circuit(store, circ)
    sel = np.array([0, 3, store.n_tiles - 1])
    sub, info = run_tiled_circuit(store, circ, tiles=sel)
    assert sub.shape == (1, sel.size, store.tile_words)
    assert info["dirty_words_gathered"] <= info_full["dirty_words_gathered"]
    padded = np.zeros(store.n_tiles * store.tile_words, np.uint32)
    padded[: store.n_words] = np.asarray(full)
    padded = padded.reshape(store.n_tiles, store.tile_words)
    for li, t in enumerate(sel.tolist()):
        np.testing.assert_array_equal(sub[0, li], padded[t])


def test_member_stats_per_subset_not_index_mean():
    n_tiles = 8
    clean = np.zeros((1, n_tiles * SPAN), bool)  # fully clean column
    dirty = np.random.default_rng(3).random((1, n_tiles * SPAN)) < 0.5
    store = TileStore.from_packed(pack(jnp.asarray(np.vstack([clean, dirty]))))
    assert store.member_stats([0]).clean_fraction == 1.0
    assert store.member_stats([1]).clean_fraction == 0.0
    assert 0.0 < store.member_stats(None).clean_fraction < 1.0
    assert store.member_stats([0]).dirty_words == 0


def _member_stats_oracle(store, slots):
    """The per-tile formula the per-column tables replaced: every field
    from a gather of the members' [m, n_tiles] classes and containers,
    the signatures from numpy's axis-unique rather than the store's own
    signature pass."""
    idx = np.arange(store.n) if slots is None else np.asarray(list(slots))
    if idx.size == 0:
        return MemberStats(0, store.n_words, store.tile_words, 1.0, 0.0, 0, 0)
    cls = store.classes_word[idx]
    dirty_tiles = int((cls >= TILE_DIRTY).sum())
    dens = [store.cardinalities[i] / max(store.r, 1) for i in idx]
    sigs, counts = np.unique(cls.T, axis=0, return_counts=True)
    kinds = store.container_kinds[idx]
    return MemberStats(
        n=int(idx.size),
        n_words=store.n_words,
        tile_words=store.tile_words,
        clean_fraction=1.0 - dirty_tiles / max(cls.size, 1),
        density=float(np.mean(dens)),
        dirty_words=dirty_tiles * store.tile_words,
        case3_tiles=int(((cls >= TILE_DIRTY).any(axis=0)).sum()),
        signatures=tuple(
            (int(cnt), int((sig == TILE_ONE).sum()), int((sig >= TILE_DIRTY).sum()))
            for sig, cnt in zip(sigs, counts)
        ),
        container_tiles=(
            int((kinds == CONT_DENSE).sum()),
            int((kinds == CONT_SPARSE).sum()),
            int((kinds == CONT_RUN).sum()),
        ),
        compressed_words=int(store.storage_words_cell[idx].sum()),
    )


def _member_bits(seed, shares, n=64, n_tiles=12):
    """Columns that are constant-ZERO, constant-ONE, constant-DIRTY or
    varying with the given ``shares``; a dirty tile is a sparse, a run or
    a dense container."""
    rng = np.random.default_rng(seed)
    bits = np.zeros((n, n_tiles * SPAN), bool)
    for i, kind in enumerate(rng.choice(4, n, p=shares)):
        for t in range(n_tiles):
            cls = rng.integers(3) if kind == 3 else kind
            tile = bits[i, t * SPAN:(t + 1) * SPAN]
            if cls == TILE_ONE:
                tile[:] = True
            elif cls == TILE_DIRTY:
                container = rng.integers(3)
                if container == 0:
                    tile[rng.choice(SPAN, rng.integers(1, 100), replace=False)] = True
                elif container == 1:
                    lo, hi = np.sort(rng.choice(np.arange(1, SPAN), 2, replace=False))
                    tile[lo:hi] = True
                else:
                    tile[:] = rng.random(SPAN) < 0.4
    return bits


_MIXED = (0.15, 0.15, 0.2, 0.5)


@pytest.fixture(scope="module")
def member_stores():
    """Stores from every constructor; derived ones are made after their
    base answered once, so stale per-column tables would show."""
    mixed = _store_of(_member_bits(41, _MIXED))
    varying = _store_of(_member_bits(42, (0.0, 0.0, 0.0, 1.0)))
    rows = pack(jnp.asarray(_member_bits(43, _MIXED)))
    base = TileStore.from_packed(rows[:-1])
    swap = pack(jnp.asarray(_member_bits(44, (0.0, 0.0, 0.0, 1.0), n=1)))[0]
    for s in (mixed, varying, base):
        s.member_stats(None)
    arrays = {"classes": mixed.classes_word, "kinds": mixed.container_kinds,
              "cardinalities": np.asarray(mixed.cardinalities, np.int64),
              **mixed.packs}
    return {
        "mixed": mixed,
        "varying": varying,
        "from_arrays": TileStore.from_arrays(
            arrays, tile_words=mixed.tile_words, n_words=mixed.n_words,
            r=mixed.r, containers=mixed.containers),
        "slice_tiles": mixed.slice_tiles(3, 10),
        "append": base.append(rows[-1]),
        "replace": mixed.replace(5, swap),
        "on_device": mixed.on_device(jax.devices()[0]),
    }


@pytest.mark.parametrize("size", [0, 1, 13, 31, 33, 64, None])
@pytest.mark.parametrize("kind", ["mixed", "varying", "from_arrays",
                                  "slice_tiles", "append", "replace",
                                  "on_device"])
def test_member_stats_match_the_per_tile_formula(member_stores, kind, size):
    """Per-column tables, with a per-tile pass over the varying members
    only, give exactly the old per-tile answer, signature order included,
    for unsorted subsets of every size."""
    store = member_stores[kind]
    slots = (None if size is None
             else np.random.default_rng(size).permutation(store.n)[:size].tolist())
    assert store.member_stats(slots) == _member_stats_oracle(store, slots)


class _TilePass(Exception):
    pass


class _NoRowGather:
    """A [N, n_tiles] table whose rows may not be gathered."""

    def __getitem__(self, key):
        raise _TilePass(key)


def test_constant_members_fold_without_a_tile_pass():
    store = _store_of(_member_bits(45, _MIXED))
    store.member_stats(None)  # builds the per-column tables
    cls = store.classes_word
    const = [i for i in range(store.n) if (cls[i] == cls[i, 0]).all()]
    varying = sorted(set(range(store.n)) - set(const))
    assert {int(cls[i, 0]) for i in const} == {TILE_ZERO, TILE_ONE, TILE_DIRTY}
    subset = const[::-1]
    expected = _member_stats_oracle(store, subset)
    assert len(expected.signatures) == 1
    store._classes_word = _NoRowGather()
    store._kinds_cache = _NoRowGather()
    store._storage_words_cell = _NoRowGather()
    start = member_stats_info()
    assert store.member_stats(subset) == expected
    end = member_stats_info()
    assert end["folded_members"] - start["folded_members"] == len(subset)
    assert end["keyed_members"] == start["keyed_members"]
    with pytest.raises(_TilePass):  # a varying member needs the tile pass
        store.member_stats(const[:3] + varying[:1])


# ---------------------------------------------------------------------------
# Compressed containers (sparse + run)
# ---------------------------------------------------------------------------


def _store_of(bits, r=None, containers=True, tile_words=TW):
    return TileStore.from_packed(
        pack(jnp.asarray(bits)), tile_words=tile_words,
        r=r if r is not None else bits.shape[1], containers=containers,
    )


def test_container_classification_crossover_edges():
    """Kind choice at the exact thresholds: popcount == sparse_max is still
    sparse, one more scattered bit tips dense; 1- and 2-interval tiles are
    run containers; run-ineligible interval counts fall through."""
    r = SPAN
    smax = sparse_max_positions(TW)  # 128 positions at TW=64
    rmax = run_max_intervals(TW)
    rows = []
    rng = np.random.default_rng(0)
    at = np.zeros(r, bool)
    at[rng.choice(np.arange(0, r, 2), smax, replace=False)] = True  # no runs>1bit
    rows.append(at)  # popcount exactly at the threshold -> sparse
    over = np.zeros(r, bool)
    over[rng.choice(np.arange(0, r, 2), smax + 1, replace=False)] = True
    rows.append(over)  # one past the threshold, many intervals -> dense
    single = np.zeros(r, bool)
    single[300:2000] = True
    rows.append(single)  # one interval -> run
    double = np.zeros(r, bool)
    double[10:800] = True
    double[1200:1900] = True
    rows.append(double)  # two intervals -> run
    toothy = np.zeros(r, bool)
    toothy[: (rmax + 1) * 2 : 2] = True  # rmax+1 intervals, tiny popcount
    rows.append(toothy)  # run-ineligible but sparse-eligible -> sparse
    store = _store_of(np.stack(rows))
    kinds = store.container_kinds[:, 0]
    assert kinds.tolist() == [
        CONT_SPARSE, CONT_DENSE, CONT_RUN, CONT_RUN, CONT_SPARSE
    ]
    # the decompressed store is bit-identical to the input
    np.testing.assert_array_equal(
        np.asarray(store.densify()), np.asarray(pack(jnp.asarray(np.stack(rows))))
    )
    # storage accounting: sparse = ceil(p/2) words, run = 1 word / interval
    cells = store.storage_words_cell[:, 0]
    assert cells[0] == (smax + 1) // 2 and cells[1] == TW
    assert cells[2] == 1 and cells[3] == 2
    assert cells[4] == (rmax + 2) // 2  # rmax + 1 positions, sparse-coded


def test_container_roundtrip_and_densify_parity():
    """Container and legacy stores densify identically on mixed data with a
    partial final tile; compressed storage never exceeds the dense pack."""
    bits = _tiled_bits(6, 6, 0.5, seed=31, tail_bits=123)
    sparse_rows = np.zeros((2, bits.shape[1]), bool)
    sparse_rows[0, ::997] = True
    sparse_rows[1, 100:5000] = True
    bits = np.vstack([bits, sparse_rows])
    store = _store_of(bits)
    legacy = _store_of(bits, containers=False)
    assert store.containers and not legacy.containers
    np.testing.assert_array_equal(
        np.asarray(store.densify()), np.asarray(legacy.densify())
    )
    assert store.cardinalities == legacy.cardinalities
    assert store.storage_words() <= legacy.storage_words()
    assert (legacy.container_kinds[legacy.classes_word >= TILE_DIRTY]
            == CONT_DENSE).all()
    # the legacy densified-dirty surface still covers every dirty tile
    np.testing.assert_array_equal(
        np.asarray(store.dirty), np.asarray(legacy.dirty)
    )
    # slicing preserves container packs without reclassifying
    sliced = store.slice_tiles(1, 4)
    np.testing.assert_array_equal(
        np.asarray(sliced.densify()),
        np.asarray(store.densify())[:, TW : 4 * TW],
    )
    np.testing.assert_array_equal(
        sliced.container_kinds, store.container_kinds[:, 1:4]
    )
    back = TileStore.concat_tiles(
        [store.slice_tiles(0, 1), sliced, store.slice_tiles(4, store.n_tiles)],
        n_words=store.n_words, r=store.r,
    )
    np.testing.assert_array_equal(
        np.asarray(back.densify()), np.asarray(store.densify())
    )
    np.testing.assert_array_equal(back.container_kinds, store.container_kinds)


def test_apply_tile_updates_reclassifies_containers():
    """Compaction picks the cheapest container per touched tile: a sparse
    tile mutated dense flips kind, clearing it back flips it back."""
    r = 4 * SPAN
    bits = np.zeros((2, r), bool)
    bits[0, ::1009] = True  # sparse everywhere
    bits[1] = np.random.default_rng(5).random(r) < 0.5
    store = _store_of(bits)
    assert store.container_kinds[0, 1] == CONT_SPARSE
    dense_tile = np.asarray(
        pack(jnp.asarray(np.random.default_rng(6).random(SPAN) < 0.5))
    ).astype(np.uint32)
    upd = store.apply_tile_updates({0: {1: dense_tile}})
    assert upd.container_kinds[0, 1] == CONT_DENSE
    np.testing.assert_array_equal(
        np.asarray(upd.densify())[0, TW : 2 * TW], dense_tile
    )
    sparse_tile = np.zeros(TW, np.uint32)
    sparse_tile[3] = 0b1001
    back = upd.apply_tile_updates({0: {1: sparse_tile}})
    assert back.container_kinds[0, 1] == CONT_SPARSE
    run_tile = np.zeros(TW, np.uint32)
    run_tile[:20] = 0xFFFFFFFF
    runb = back.apply_tile_updates({0: {1: run_tile}})
    assert runb.container_kinds[0, 1] == CONT_RUN
    cleared = runb.apply_tile_updates({0: {1: np.zeros(TW, np.uint32)}})
    assert cleared.container_kinds[0, 1] == CONT_NONE
    assert cleared.classes_word[0, 1] == TILE_ZERO
    # cardinality tracked by popcount deltas through every transition
    assert cleared.cardinalities[0] == store.cardinalities[0] - int(
        bits[0, SPAN : 2 * SPAN].sum()
    )


def test_query_results_stored_as_containers():
    """add_column compresses results: the paper's 'the result is again a
    bitmap which can be further processed' loop stays compressed."""
    bits = np.zeros((4, 4 * SPAN), bool)
    bits[0, ::501] = True
    bits[1, ::703] = True
    bits[2, 100:200] = True
    bits[3, SPAN:] = True
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    res = idx.execute(Threshold(2))
    idx2 = idx.add_column("hot", res)
    kinds = idx2.store.container_kinds[-1]
    dirty = idx2.store.classes_word[-1] >= TILE_DIRTY
    assert dirty.any()
    assert (kinds[dirty] != CONT_DENSE).any()  # stored compressed
    np.testing.assert_array_equal(
        np.asarray(idx2.column("hot")), np.asarray(res)
    )


def test_container_native_execution_differential():
    """Deterministic mirror of tests/test_containers_fuzz.py: mixed column
    kinds, every ALGORITHMS backend on bare thresholds plus circuit-family
    on a composite, container vs legacy vs sharded -- all bit-identical to
    the numpy oracle."""
    rng = np.random.default_rng(17)
    span8 = 8 * 32
    n, r = 5, 4 * span8 + 37
    bits = np.zeros((n, r), bool)
    bits[0, ::131] = True  # sparse
    bits[1, 40:500] = True  # runny
    bits[2] = rng.random(r) < 0.5  # dense
    bits[3, :span8] = True  # clean tile + zeros
    bits[4, ::2] = True  # toothy (run-ineligible, sparse-ineligible)
    counts = bits.sum(0)
    variants = []
    for containers in (True, False):
        idx = BitmapIndex.from_dense(
            jnp.asarray(bits), tile_words=8, containers=containers
        )
        variants += [(containers, False, idx), (containers, True, idx.shard(n_shards=3))]
    for t in (1, 2, n):
        expect = counts >= t
        for containers, sharded, idx in variants:
            for alg in ALGORITHMS:
                if (alg == "wide_or") != (t == 1) and alg == "wide_or":
                    continue
                if alg == "wide_and" and t != n:
                    continue
                res = idx.execute(Threshold(t), backend=alg)
                got = res.gather() if sharded else res
                np.testing.assert_array_equal(
                    np.asarray(unpack(got, r)), expect,
                    err_msg=f"alg={alg} t={t} containers={containers} sharded={sharded}",
                )
    q = And(Interval(2, 4), Not(Col("c1"))) | Parity(over=(Col("c0"), Col("c2")))
    expect = ((counts >= 2) & (counts <= 4) & ~bits[1]) | (
        bits[0] ^ bits[2]
    )
    for containers, sharded, idx in variants:
        for backend in (None, "circuit", "tiled_fused"):
            res = idx.execute(q, backend=backend)
            got = res.gather() if sharded else res
            np.testing.assert_array_equal(
                np.asarray(unpack(got, r)), expect,
                err_msg=f"composite containers={containers} sharded={sharded} {backend}",
            )


def test_event_path_engages_and_reduces_words():
    """On sparse data the executor resolves tiles container-natively (no
    densified gather) and touches far fewer words than the legacy store."""
    rng = np.random.default_rng(23)
    n, n_tiles = 6, 16
    r = n_tiles * SPAN
    bits = rng.random((n, r)) < (20 / SPAN)  # ~20 bits per tile per column
    circ = build_threshold_circuit(n, 1, "ssum")
    store = _store_of(bits)
    legacy = _store_of(bits, containers=False)
    out, info = run_tiled_circuit(store, circ)
    out2, info2 = run_tiled_circuit(legacy, circ)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    assert info["event_tiles"] > 0
    assert info["compressed_words_gathered"] > 0
    assert info2["event_tiles"] == 0
    assert info["dirty_words_gathered"] * 4 <= info2["dirty_words_gathered"], (
        info["dirty_words_gathered"], info2["dirty_words_gathered"]
    )
    assert info["words_by_kind"]["sparse"] > 0


def _mixed_bits(seed=29):
    """Deterministic dense/sparse/run/all-zero/all-one/partial-tile mix."""
    rng = np.random.default_rng(seed)
    span8 = 8 * 32
    n, r = 6, 5 * span8 + 41  # partial final tile
    bits = np.zeros((n, r), bool)
    bits[0, ::97] = True  # sparse everywhere
    bits[1, 30:700] = True  # one long run
    bits[2] = rng.random(r) < 0.5  # dense noise
    bits[3, :span8] = True  # all-one tile, zeros elsewhere
    bits[4, ::2] = True  # toothy: dirty but container-ineligible
    bits[5, span8 : 2 * span8] = rng.random(span8) < 0.1  # sparse island
    return bits, r


def test_scan_engine_matches_merge_oracle_deterministic():
    """Deterministic mirror of the fuzz suite's engine differential: the
    single-scan device engine (in-kernel container decode, O(1) dispatch)
    is bit-identical to the host event-merge oracle on dense/sparse/run/
    clean/partial-tile mixes, {containers, legacy} x {full, restricted},
    single- and multi-output circuits -- and launches at most twice."""
    bits, r = _mixed_bits()
    n = bits.shape[0]
    counts = bits.sum(0)
    circs = [
        (build_threshold_circuit(n, 2, "ssum"), counts >= 2),
        (build_interval_circuit(n, 2, 4), (counts >= 2) & (counts <= 4)),
    ]
    for containers in (True, False):
        store = _store_of(bits, containers=containers, tile_words=8)
        for circ, expect in circs:
            out_s, info_s = run_tiled_circuit(store, circ, engine="scan")
            out_m, info_m = run_tiled_circuit(store, circ, engine="merge")
            np.testing.assert_array_equal(
                np.asarray(out_s), np.asarray(out_m),
                err_msg=f"containers={containers}",
            )
            np.testing.assert_array_equal(np.asarray(unpack(out_s, r)), expect)
            assert info_s["engine"] == "scan" and info_m["engine"] == "merge"
            assert info_s["launches"] <= 2, info_s
            # consistent per-kind accounting on BOTH engines (legacy
            # stores used to report zeroed breakdowns on the device path)
            for info in (info_s, info_m):
                if info["densified_tiles"] or info["event_tiles"]:
                    assert sum(info["words_by_kind"].values()) > 0, info
            # restricted-tiles (view-refresh) parity, host [k, n_sel, tw]
            tiles = np.asarray([0, 2, store.n_tiles - 1])
            got_s, ri = run_tiled_circuit(
                store, circ, tiles=tiles, engine="scan"
            )
            got_m, _ = run_tiled_circuit(
                store, circ, tiles=tiles, engine="merge"
            )
            np.testing.assert_array_equal(got_s, got_m)
            assert ri["launches"] <= 2


def test_scan_engine_single_dispatch_multi_residual():
    """A batched multi-query circuit over clean-mixed data produces many
    structurally distinct residual groups; the seed path launched once per
    group, the scan engine at most twice total."""
    bits = _tiled_bits(8, 12, 0.5, seed=3)
    r = bits.shape[1]
    counts = bits.sum(0)
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    res = idx.execute_many(
        [Threshold(2), Threshold(5), Interval(3, 6)], backend="tiled_fused"
    )
    np.testing.assert_array_equal(np.asarray(unpack(res[0], r)), counts >= 2)
    np.testing.assert_array_equal(np.asarray(unpack(res[1], r)), counts >= 5)
    np.testing.assert_array_equal(
        np.asarray(unpack(res[2], r)), (counts >= 3) & (counts <= 6)
    )
    info = idx.last_info
    assert info["engine"] == "scan"
    assert info["residual_signatures"] >= 2  # genuinely multi-group
    assert info["launches"] <= 2, info
    # the merge oracle on the same workload launches once per group
    import os

    os.environ["REPRO_TILED_ENGINE"] = "merge"
    try:
        idx.execute_many(
            [Threshold(2), Threshold(5), Interval(3, 6)],
            backend="tiled_fused",
        )
    finally:
        del os.environ["REPRO_TILED_ENGINE"]
    assert idx.last_info["launches"] >= info["launches"]


def test_scan_engine_pallas_grid_parity():
    """FORCE_PALLAS_INTERPRET pins the scalar-prefetched Pallas grid kernel
    (the TPU path) against the XLA scan on CPU."""
    from repro.kernels import tiled_scan

    bits, r = _mixed_bits(seed=31)
    n = bits.shape[0]
    store = _store_of(bits, containers=True, tile_words=8)
    circ = build_threshold_circuit(n, 3, "ssum")
    out_xla, _ = run_tiled_circuit(store, circ, engine="scan")
    tiled_scan.FORCE_PALLAS_INTERPRET = True
    tiled_scan.clear_scan_runners()
    try:
        out_pl, _ = run_tiled_circuit(store, circ, engine="scan")
    finally:
        tiled_scan.FORCE_PALLAS_INTERPRET = False
        tiled_scan.clear_scan_runners()
    np.testing.assert_array_equal(np.asarray(out_xla), np.asarray(out_pl))


def test_scan_plan_scatter_indices_ascend():
    """The scan kernels declare every row scatter ``indices_are_sorted``
    (the TPU compiler needs it; the CPU ignores it), so the plan builder
    must hand them non-decreasing indices: decoded cells in block-row
    order, output cells ascending with the permutation that feeds them.
    The event stage's merge also needs its toggle keys sorted."""
    rng = np.random.default_rng(23)
    mixed, _r = _mixed_bits(seed=37)
    sparse_bits = rng.random((6, 16 * SPAN)) < 20 / SPAN
    # column 0 clean on odd tiles: two residual groups, interleaved tiles
    sparse_bits[0].reshape(16, SPAN)[1::2] = False
    # one residual group spanning several 16-tile blocks, with sparse and
    # run cells on different wires: cell order != block-row order
    wide = np.zeros((6, 40 * SPAN), bool)
    wide[0] = rng.random(40 * SPAN) < 0.5
    wide[1, ::97] = True
    wide[2, 5::89] = True
    wide[3] = (np.arange(40 * SPAN) % SPAN) // 700 == 1
    wide[4] = (np.arange(40 * SPAN) % SPAN) // 500 == 2
    kinds = set()

    def ascending(x):
        assert np.all(np.diff(np.asarray(x, np.int64)) >= 0)

    for bits, tw in ((mixed, 8), (sparse_bits, TW), (wide, TW)):
        store = _store_of(bits, containers=True, tile_words=tw)
        run_tiled_circuit(store, build_interval_circuit(6, 2, 4), engine="scan")
        (plan, _info), = store._scan_plan_cache.values()
        for _fn, args in plan["stages"]:
            args = [np.asarray(a) for a in args]
            if len(args) == 13:  # block stage
                kinds.add("block")
                (_gids, _dense, _src, _sparse, _spt, _spc, spr,
                 _runs, _rnt, _rnc, rnr, dst, perm) = args
                ascending(spr)
                ascending(rnr)
                ascending(dst)
                np.testing.assert_array_equal(np.sort(perm),
                                              np.arange(perm.size))
            else:  # event stage
                kinds.add("event")
                keys, _mask, _gid_row, _lut, out_dst, out_perm = args
                ascending(keys)
                for row, p in zip(out_dst, out_perm):
                    ascending(row)
                    np.testing.assert_array_equal(np.sort(p),
                                                  np.arange(p.size))
    assert kinds == {"block", "event"}


def test_specialize_memo_is_lru():
    """The residual memo evicts oldest-used entries one at a time (not a
    wholesale clear), and a hit refreshes recency."""
    from repro.storage import tiled

    memo = tiled._SPECIALIZE_MEMO
    saved = dict(memo)
    saved_order = list(memo)
    try:
        memo.clear()
        for i in range(4):
            memo[("c", bytes([i]))] = (None, None, None, None)
        old_cap, tiled._SPECIALIZE_MEMO_CAP = tiled._SPECIALIZE_MEMO_CAP, 4
        try:
            # a hit moves ("c", b"\x00") to the back...
            tiled._specialize_hit = memo.get(("c", b"\x00"))
            memo.move_to_end(("c", b"\x00"))
            bits = _tiled_bits(3, 2, 0.0, seed=5)
            store = _store_of(bits)
            circ = build_threshold_circuit(3, 2, "ssum")
            run_tiled_circuit(store, circ)
            # ...so the eviction (cap 4) drops ("c", b"\x01"), not the
            # refreshed entry and not the whole memo
            assert ("c", b"\x00") in memo
            assert ("c", b"\x01") not in memo
            assert len(memo) >= 3
        finally:
            tiled._SPECIALIZE_MEMO_CAP = old_cap
    finally:
        memo.clear()
        for k in saved_order:
            memo[k] = saved[k]


# ---------------------------------------------------------------------------
# Tiled execution vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("clean_fraction", [0.0, 0.9, 1.0])
def test_tiled_circuit_threshold_matches_oracle(clean_fraction):
    n = 9
    bits = _tiled_bits(n, 5, clean_fraction, seed=7, tail_bits=500)
    r = bits.shape[1]
    counts = bits.sum(0)
    store = TileStore.from_packed(pack(jnp.asarray(bits)), r=r)
    for t in (1, 3, n - 1, n):
        circ = build_threshold_circuit(n, t, "ssum")
        out, info = run_tiled_circuit(store, circ)
        np.testing.assert_array_equal(
            np.asarray(unpack(out, r)), counts >= t, err_msg=f"cf={clean_fraction} t={t}"
        )
    if clean_fraction == 1.0:
        assert info["dirty_words_gathered"] <= store.tile_words * store.n_tiles


def test_tiled_circuit_multi_output_shares_gather():
    n = 8
    bits = _tiled_bits(n, 6, 0.8, seed=11)
    r = bits.shape[1]
    counts = bits.sum(0)
    c1 = build_threshold_circuit(n, 3, "ssum")
    c2 = build_interval_circuit(n, 2, 5)
    # one multi-output circuit: merge manually via the query layer instead
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    res = idx.execute_many([Threshold(3), Interval(2, 5)], backend="tiled_fused")
    np.testing.assert_array_equal(np.asarray(unpack(res[0], r)), counts >= 3)
    np.testing.assert_array_equal(
        np.asarray(unpack(res[1], r)), (counts >= 2) & (counts <= 5)
    )
    # the batch shared ONE tile gather (k outputs, one info record)
    assert idx.last_info["n_outputs"] == 2
    single, _ = run_tiled_circuit(idx.store, c1)
    both_words = idx.last_info["dirty_words_gathered"]
    _, info1 = run_tiled_circuit(idx.store, c1)
    _, info2 = run_tiled_circuit(idx.store, c2)
    assert both_words <= info1["dirty_words_gathered"] + info2["dirty_words_gathered"]


def test_tiled_composite_gets_skipping():
    """Interval/And/Not compositions -- not just bare thresholds -- skip."""
    n = 6
    bits = _tiled_bits(n, 10, 0.95, seed=13)
    r = bits.shape[1]
    counts = bits.sum(0)
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    q = And(Interval(2, 4), Not(Col("c0")))
    expect = (counts >= 2) & (counts <= 4) & ~bits[0]
    out = idx.execute(q, backend="tiled_fused")
    np.testing.assert_array_equal(np.asarray(unpack(out, r)), expect)
    assert idx.last_info["work_fraction"] < 0.5, idx.last_info
    # and the planner chooses the tiled path by itself on this data
    plan = idx.explain(q)
    assert plan.algorithm == "tiled_fused", plan
    assert plan.cost is not None and plan.cost < n * idx.n_words


def test_planner_cost_model_per_member_subset():
    """Thresholds over a clean subset plan tiled even when the index-wide
    mean is dirty (the per-column-stats requirement)."""
    n_tiles = 8
    clean = _tiled_bits(4, n_tiles, 1.0, seed=17)
    dirty = _tiled_bits(4, n_tiles, 0.0, seed=18)
    bits = np.vstack([clean, dirty])
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    clean_cols = tuple(f"c{i}" for i in range(4))
    dirty_cols = tuple(f"c{i}" for i in range(4, 8))
    assert idx.explain(Threshold(2, over=clean_cols)).algorithm == "tiled_fused"
    assert idx.explain(Threshold(2, over=dirty_cols)).algorithm != "tiled_fused"
    # candidates carry per-backend words-touched estimates
    plan = idx.explain(Threshold(2, over=clean_cols))
    names = [name for name, _ in plan.candidates]
    assert "tiled_fused" in names and "fused" in names
    counts = clean.sum(0)
    out = idx.execute(Threshold(2, over=clean_cols))
    np.testing.assert_array_equal(np.asarray(unpack(out, bits.shape[1])), counts >= 2)


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------


def test_stats_cache_respects_tile_words():
    """stats(tile_words=128) after stats(tile_words=64) must not return the
    64-word-granularity numbers (the seed's cache ignored the argument)."""
    # one 64-word all-one tile next to one dirty tile: at 128-word tiles the
    # pair merges into a single dirty tile, so clean_fraction must change
    bits = np.zeros((1, 2 * SPAN), bool)
    bits[0, :SPAN] = True
    bits[0, SPAN::3] = True
    idx = BitmapIndex.from_dense(jnp.asarray(bits))
    s64 = idx.stats(tile_words=64)
    s128 = idx.stats(tile_words=128)
    assert s64.tile_words == 64 and s128.tile_words == 128
    assert s64.clean_fraction == 0.5
    assert s128.clean_fraction == 0.0
    assert idx.stats(tile_words=64) is s64  # still cached, per granularity
    assert idx.stats(tile_words=128) is s128


def test_single_consolidated_shim_deprecation_warning():
    """The whole fused_*/symmetric shim family warns once per process."""
    from repro.core.deprecation import reset_legacy_shim_warning
    from repro.core.symmetric import interval, parity
    from repro.kernels.ops import fused_interval, fused_threshold

    bits = np.random.default_rng(5).random((6, 200)) < 0.4
    bm = pack(jnp.asarray(bits))
    reset_legacy_shim_warning()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fused_threshold(bm, 2)
        interval(bm, 1, 3)
        parity(bm)
        fused_interval(bm, 1, 3)
    ours = [
        w for w in caught
        if issubclass(w.category, DeprecationWarning)
        and "deprecated shim" in str(w.message)
    ]
    assert len(ours) == 1, [str(w.message) for w in caught]


def test_shims_route_through_tiled_path_on_clean_data():
    from repro.core.deprecation import reset_legacy_shim_warning
    from repro.kernels.ops import fused_threshold

    bits = _tiled_bits(5, 8, 1.0, seed=23)
    counts = bits.sum(0)
    reset_legacy_shim_warning()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = fused_threshold(pack(jnp.asarray(bits)), 2)
    np.testing.assert_array_equal(np.asarray(unpack(out, bits.shape[1])), counts >= 2)
