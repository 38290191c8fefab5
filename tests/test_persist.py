"""repro.persist: snapshot format, WAL recovery, paging, golden fixture.

Deterministic coverage of the on-disk subsystem (the hypothesis mirror
lives in ``test_persist_fuzz.py``):

  * format framing: magic/version/crc validation, corrupt-tail rejection
  * snapshot round trips are bit-identical and byte-deterministic, for
    container-enabled AND legacy all-dense stores, and loads are
    zero-copy (memmap-backed pack views)
  * per-shard files round trip through ShardedBitmapIndex without gather
  * WAL: versions stay monotone across rotation, truncation (crash)
    recovers the valid prefix, recover() replays to the live state
  * PagedTileStore answers bit-identically while keeping packs host-side
  * the committed golden snapshot keeps loading AND regenerating
    byte-identically (format-stability contract)
"""
import importlib.util
import os
from pathlib import Path

import numpy as np
import pytest

from repro import persist
from repro.core.bitmaps import unpack
from repro.persist.wal import _HEADER as _WAL_HEADER
from repro.query import And, BitmapIndex, Col, Interval, Not, Threshold
from repro.query.expr import (
    AndNot,
    Exactly,
    Majority,
    Or,
    Parity,
    Sym,
    Weighted,
)
from repro.stream import CompactionPolicy, StreamingIndex

TW = 8
SPAN = TW * 32

_golden_spec = importlib.util.spec_from_file_location(
    "make_golden", Path(__file__).parent / "data" / "make_golden.py"
)
make_golden = importlib.util.module_from_spec(_golden_spec)
_golden_spec.loader.exec_module(make_golden)


def _mixed_bits(n=6, n_tiles=5, tail=17, seed=0):
    """Columns covering every container kind, partial final tile."""
    r = n_tiles * SPAN + tail
    rng = np.random.default_rng(seed)
    bits = np.zeros((n, r), bool)
    bits[0, :] = True
    bits[2, rng.choice(r, r // 40, replace=False)] = True
    bits[3, r // 8 : r // 2] = True
    bits[4 % n] = rng.random(r) < 0.4
    if n > 5:
        bits[5, : r // 3] = rng.random(r // 3) < 0.6
    return bits


def _index(bits, containers=True):
    names = [f"c{i}" for i in range(bits.shape[0])]
    return BitmapIndex.from_dense(bits, names, tile_words=TW,
                                  containers=containers)


def _assert_same_index(a, b):
    assert tuple(a.names) == tuple(b.names)
    sa, sb = a.store, b.store
    assert (sa.r, sa.n_words, sa.tile_words, sa.n) == (sb.r, sb.n_words,
                                                       sb.tile_words, sb.n)
    np.testing.assert_array_equal(sa.classes_word, sb.classes_word)
    np.testing.assert_array_equal(sa.container_kinds, sb.container_kinds)
    np.testing.assert_array_equal(np.asarray(sa.cardinalities),
                                  np.asarray(sb.cardinalities))
    np.testing.assert_array_equal(np.asarray(sa.densify()),
                                  np.asarray(sb.densify()))


# -- format framing --------------------------------------------------------

def test_rejects_bad_magic_and_version(tmp_path):
    p = tmp_path / "x.bmsnap"
    persist.save(_index(_mixed_bits()), p)
    raw = bytearray(p.read_bytes())
    (tmp_path / "bad_magic.bmsnap").write_bytes(b"NOTMAGIC" + raw[8:])
    with pytest.raises(persist.FormatError):
        persist.read_manifest(tmp_path / "bad_magic.bmsnap")
    bad_ver = bytearray(raw)
    bad_ver[8:12] = (99).to_bytes(4, "little")
    (tmp_path / "bad_ver.bmsnap").write_bytes(bad_ver)
    with pytest.raises(persist.FormatError):
        persist.read_manifest(tmp_path / "bad_ver.bmsnap")


def test_rejects_truncation_and_section_corruption(tmp_path):
    p = tmp_path / "x.bmsnap"
    persist.save(_index(_mixed_bits()), p)
    raw = p.read_bytes()
    (tmp_path / "trunc.bmsnap").write_bytes(raw[: len(raw) // 2])
    with pytest.raises(persist.FormatError):
        persist.read_manifest(tmp_path / "trunc.bmsnap")
    # flip one byte inside the first section: manifest still reads, but
    # verify_snapshot catches the crc mismatch
    manifest = persist.read_manifest(p)
    off = manifest["sections"][0]["offset"]
    corrupt = bytearray(raw)
    corrupt[off] ^= 0xFF
    (tmp_path / "corrupt.bmsnap").write_bytes(corrupt)
    persist.read_manifest(tmp_path / "corrupt.bmsnap")  # framing intact
    with pytest.raises(persist.FormatError):
        persist.verify_snapshot(tmp_path / "corrupt.bmsnap")


def test_snapshot_info(tmp_path):
    p = tmp_path / "x.bmsnap"
    idx = _index(_mixed_bits())
    persist.save(idx, p)
    info = persist.snapshot_info(p)
    assert info["kind"] == "tilestore"
    assert info["n_columns"] == 6
    assert info["names"] == list(idx.names)
    assert info["file_bytes"] == os.path.getsize(p)
    assert info["schema_digest"] == persist.schema_digest(
        tuple(idx.names), idx.store.r, idx.store.tile_words)


# -- snapshot round trips --------------------------------------------------

@pytest.mark.parametrize("containers", [True, False])
def test_round_trip_bit_identical(tmp_path, containers):
    bits = _mixed_bits(seed=3)
    idx = _index(bits, containers=containers)
    p = tmp_path / "x.bmsnap"
    persist.save(idx, p)
    loaded = persist.load_index(p, verify=True)
    _assert_same_index(idx, loaded)
    for q in (Threshold(2), Interval(1, 3), Parity(),
              And(Col("c0"), Not(Col("c2")))):
        np.testing.assert_array_equal(np.asarray(idx.execute(q)),
                                      np.asarray(loaded.execute(q)))


def test_save_is_byte_deterministic(tmp_path):
    idx = _index(_mixed_bits(seed=5))
    p1, p2, p3 = (tmp_path / f"{i}.bmsnap" for i in range(3))
    persist.save(idx, p1)
    persist.save(idx, p2)
    assert p1.read_bytes() == p2.read_bytes()
    # save(load(x)) reproduces x: the writer is a fixed point over loads
    persist.save(persist.load_index(p1), p3)
    assert p3.read_bytes() == p1.read_bytes()


def test_load_is_zero_copy(tmp_path):
    p = tmp_path / "x.bmsnap"
    persist.save(_index(_mixed_bits()), p)
    store = persist.load(p)
    import mmap

    for name in ("dense_pack", "sparse_pack", "run_pack"):
        arr = store.packs[name]
        assert not arr.flags.owndata, name
        base = arr
        while not isinstance(base, (np.memmap, mmap.mmap)):
            base = base.base
            assert base is not None, name  # chain must end in the mapping


def test_load_to_device_and_bare_store(tmp_path):
    bits = _mixed_bits(seed=7)
    store = _index(bits).store
    p = tmp_path / "bare.bmsnap"
    persist.save(store, p)  # no names: loads as a store, not an index
    loaded = persist.load(p, to_device=True)
    np.testing.assert_array_equal(np.asarray(store.densify()),
                                  np.asarray(loaded.densify()))
    with pytest.raises(ValueError):
        persist.load_index(p)


def test_extra_meta_keys_reserved(tmp_path):
    idx = _index(_mixed_bits())
    with pytest.raises(ValueError):
        persist.save(idx, tmp_path / "x.bmsnap", extra={"r": 1})


# -- per-shard files -------------------------------------------------------

def test_sharded_round_trip(tmp_path):
    pytest.importorskip("jax")
    from repro.dist.query import ShardedBitmapIndex

    bits = _mixed_bits(n=5, n_tiles=6, tail=0, seed=11)
    idx = _index(bits)
    sh = ShardedBitmapIndex.from_index(idx)
    d = tmp_path / "sharded"
    sh.save(d)
    m = persist.read_shard_map(d)
    assert m["n_shards"] >= 1
    assert sorted(x.name for x in d.glob("shard-*.bmsnap")) == [
        f"shard-{k:04d}.bmsnap" for k in range(m["n_shards"])]
    back = ShardedBitmapIndex.load(d)
    for q in (Threshold(2), And(Col("c0"), Col("c4"))):
        np.testing.assert_array_equal(
            np.asarray(idx.execute(q)),
            np.asarray(back.execute(q).gather()))
    # one shard loads alone, with its tile bounds
    store0, bounds = persist.load_shard(d, 0)
    assert len(bounds) == 2 and bounds[0] == 0
    assert store0.n == 5


# -- WAL ------------------------------------------------------------------

def test_wal_versions_survive_rotation(tmp_path):
    p = tmp_path / "wal.bmwal"
    with persist.WriteAheadLog(p) as wal:
        v1 = wal.append_update([0], [3], [True])
        v2 = wal.append_rows(np.ones((1, 4), bool))
        assert (v1, v2) == (1, 2)
        wal.rotate()
        assert wal.records == 0
        v3 = wal.append_materialize("m", Threshold(2))
        assert v3 == 3  # monotone across rotation
    with persist.WriteAheadLog(p) as wal2:
        recs = list(wal2.replay())
        assert [r["version"] for r in recs] == [3]
        assert recs[0]["name"] == "m"


def test_wal_truncated_tail_is_dropped(tmp_path):
    p = tmp_path / "wal.bmwal"
    with persist.WriteAheadLog(p) as wal:
        wal.append_update([0, 1], [3, 9], [True, False])
        wal.append_update([2], [5], [True])
    raw = p.read_bytes()
    # chop the last record mid-payload
    (p).write_bytes(raw[:-3])
    with persist.WriteAheadLog(p) as wal:
        assert wal.records == 1
        assert wal.last_version == 1
        recs = list(wal.replay())
        assert len(recs) == 1
        np.testing.assert_array_equal(recs[0]["cols"], [0, 1])
    # corrupt crc of the surviving record -> empty log, header intact
    raw = p.read_bytes()
    flip = bytearray(raw)
    flip[_WAL_HEADER + 8] ^= 0xFF
    p.write_bytes(flip)
    with persist.WriteAheadLog(p) as wal:
        assert wal.records == 0 and wal.last_version == 0


def test_query_codec_round_trips_every_node():
    qs = [
        Threshold(2), Threshold(1, over=(Col("a"), Col("b"))),
        Interval(1, 3), Exactly(2), Parity(), Majority(),
        Sym((False, True, True, False)),
        Weighted((1, 2, 3), 4),
        And(Col("a"), Col("b")), Or(Col("a"), Parity()),
        Not(Col("a")), AndNot(Col("a"), Col("b")),
    ]
    for q in qs:
        assert persist.query_from_obj(persist.query_to_obj(q)) == q


# -- StreamingIndex durability --------------------------------------------

def _stream(bits, tmp_path=None, **kw):
    names = [f"c{i}" for i in range(bits.shape[0])]
    idx = BitmapIndex.from_dense(bits, names, tile_words=TW)
    return StreamingIndex(idx, policy=CompactionPolicy(auto=False),
                          durable_dir=tmp_path, **kw)


def test_stream_checkpoint_recover_round_trip(tmp_path):
    bits = _mixed_bits(seed=13)
    d = tmp_path / "durable"
    s = _stream(bits, d)
    s.materialize("hot", Interval(2, 4))
    s.update(sets={"c1": [5, 77]}, clears={"c0": [3]})
    s.checkpoint()
    s.update(sets={"c2": [200]}, clears={"c1": [5]})  # WAL-only tail
    rec = StreamingIndex.recover(d)
    assert rec.wal_version == s.wal_version
    assert tuple(rec.names) == tuple(s.names)
    assert [v for v in rec.views] == [v for v in s.views]
    for q in (Threshold(2), Col("hot"), Interval(1, 3)):
        np.testing.assert_array_equal(np.asarray(s.execute(q)),
                                      np.asarray(rec.execute(q)))
    assert rec.count("hot") == s.count("hot")
    # recovered instance keeps logging: mutate both, recover again
    for t in (s, rec):
        t.update(sets={"c3": [9]})
    rec2 = StreamingIndex.recover(d)
    np.testing.assert_array_equal(np.asarray(s.execute(Threshold(2))),
                                  np.asarray(rec2.execute(Threshold(2))))


def test_stream_crash_recovery_truncated_wal(tmp_path):
    bits = _mixed_bits(seed=17)
    d = tmp_path / "durable"
    s = _stream(bits, d)
    s.checkpoint()
    s.update(sets={"c1": [10]})
    s.update(sets={"c2": [20]})
    # crash: last WAL record torn mid-write
    wal_path = d / "wal.bmwal"
    raw = wal_path.read_bytes()
    wal_path.write_bytes(raw[:-5])
    rec = StreamingIndex.recover(d)
    # reference: snapshot + ONLY the first update
    ref = _stream(bits)
    ref.update(sets={"c1": [10]})
    np.testing.assert_array_equal(np.asarray(ref.execute(Threshold(1))),
                                  np.asarray(rec.execute(Threshold(1))))
    np.testing.assert_array_equal(np.asarray(ref.execute(Col("c2"))),
                                  np.asarray(rec.execute(Col("c2"))))


def test_stream_append_rows_recovers(tmp_path):
    bits = _mixed_bits(seed=19)
    d = tmp_path / "durable"
    s = _stream(bits, d)
    s.checkpoint()
    extra = np.zeros((bits.shape[0], 40), bool)  # 40 new row positions
    extra[0, ::3] = True
    extra[2, 5] = True
    s.append_rows(extra)
    rec = StreamingIndex.recover(d)
    assert rec.r == s.r
    np.testing.assert_array_equal(np.asarray(s.execute(Threshold(2))),
                                  np.asarray(rec.execute(Threshold(2))))


def test_stream_checkpoint_folds_wal(tmp_path):
    bits = _mixed_bits(seed=23)
    d = tmp_path / "durable"
    s = _stream(bits, d)
    s.update(sets={"c0": [1]})
    v = s.wal_version
    s.checkpoint()
    assert os.path.getsize(d / "wal.bmwal") == _WAL_HEADER  # rotated empty
    rec = StreamingIndex.recover(d)
    assert rec.wal_version == v  # counter survives the rotation
    np.testing.assert_array_equal(np.asarray(s.execute(Threshold(1))),
                                  np.asarray(rec.execute(Threshold(1))))


def test_stream_sharded_durability(tmp_path):
    pytest.importorskip("jax")
    bits = _mixed_bits(n=4, n_tiles=6, tail=0, seed=29)
    names = [f"c{i}" for i in range(4)]
    idx = BitmapIndex.from_dense(bits, names, tile_words=TW).shard()
    d = tmp_path / "durable"
    s = StreamingIndex(idx, policy=CompactionPolicy(auto=False),
                       durable_dir=d)
    s.materialize("pair", Interval(2, 3))
    s.update(sets={"c1": [44]})
    s.checkpoint()
    s.update(clears={"c1": [44]})
    rec = StreamingIndex.recover(d)
    assert (d / "sharded.json").exists()
    for q in (Threshold(2), Col("pair")):
        a, b = s.execute(q), rec.execute(q)
        a = a.gather() if hasattr(a, "gather") else a
        b = b.gather() if hasattr(b, "gather") else b
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- paged tier ------------------------------------------------------------

def test_paged_store_bit_identical(tmp_path):
    bits = _mixed_bits(seed=31)
    idx = _index(bits)
    p = tmp_path / "x.bmsnap"
    persist.save(idx, p)
    base = persist.load(p)
    paged = persist.PagedTileStore(base, capacity_tiles=4)
    pidx = BitmapIndex(names=tuple(idx.names), _store=paged)
    for q in (Threshold(2), Interval(1, 4), Parity()):
        np.testing.assert_array_equal(np.asarray(idx.execute(q)),
                                      np.asarray(pidx.execute(q)))
    assert len(paged._cache) <= 4  # capacity respected


def test_paged_cache_counters(tmp_path):
    rng = np.random.default_rng(37)
    bits = rng.random((4, 6 * SPAN)) < 0.3  # dense dirty tiles
    idx = _index(bits)
    p = tmp_path / "x.bmsnap"
    persist.save(idx, p)
    paged = persist.PagedTileStore(persist.load(p), capacity_tiles=64)
    pidx = BitmapIndex(names=tuple(idx.names), _store=paged)
    np.testing.assert_array_equal(
        np.asarray(idx.execute(Threshold(2), backend="tiled_fused")),
        np.asarray(pidx.execute(Threshold(2), backend="tiled_fused")))
    i1 = paged.cache_info()
    assert i1["misses"] > 0
    # same member tiles: served from cache
    pidx.execute(Threshold(3), backend="tiled_fused")
    i2 = paged.cache_info()
    assert i2["hits"] > i1["hits"]
    assert i2["full_materializations"] == 0


# -- golden fixture --------------------------------------------------------

def test_golden_fixture_loads_and_queries():
    idx = persist.load_index(make_golden.FIXTURE, verify=True)
    bits = make_golden.golden_bits()
    r = bits.shape[1]
    assert tuple(idx.names) == make_golden.NAMES
    assert idx.store.r == r
    dense = np.stack([np.asarray(unpack(idx.store.column(i), r))
                      for i in range(len(make_golden.NAMES))]).astype(bool)
    np.testing.assert_array_equal(dense, bits)
    for q, exp in (
        (Threshold(2), bits.sum(0) >= 2),
        (Interval(1, 3), (bits.sum(0) >= 1) & (bits.sum(0) <= 3)),
        (And(Col("alpha"), Not(Col("delta"))), bits[0] & ~bits[3]),
    ):
        got = np.asarray(unpack(idx.execute(q), r)).astype(bool)
        np.testing.assert_array_equal(got, exp)


def test_golden_fixture_bytes_are_stable(tmp_path):
    """The writer still produces the committed bytes for the fixed recipe
    -- any drift is a format change and must bump the version."""
    regen = tmp_path / "regen.bmsnap"
    make_golden.write(str(regen))
    assert regen.read_bytes() == Path(make_golden.FIXTURE).read_bytes()
