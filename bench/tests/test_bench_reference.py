"""The benchmark's plain reference, word tools and needed-bytes arithmetic
(CPU, small sizes)."""
import numpy as np
import pytest

import bench_testkit  # noqa: F401 - puts bench/ on the path
from yardstick import needed, reference, words


def _row_counts(cols):
    return words.unpack_host(cols).sum(axis=0)


@pytest.mark.parametrize("n", [1, 7, 16, 33])
def test_reference_equals_per_row_popcount(n):
    rng = np.random.default_rng(n)
    cols = words.uniform_words(rng, (n, 96)) | rng.integers(0, 2**32, (n, 96),
                                                            dtype=np.uint32)
    counts = _row_counts(cols)
    planes = reference.count_planes(cols, chunk=32)
    for t in (-1, 0, 1, 2, n // 2, n, n + 1):
        got = words.unpack_host(reference.count_at_least(planes, t))
        assert np.array_equal(got, counts >= t), t


QUERIES = [
    ("threshold", None, 5),
    ("threshold", (0, 3, 5, 9), 2),
    ("interval", None, 2, 10),
    ("interval", (1, 2, 4, 8, 16), 1, 3),
    ("and", (("interval", None, 2, 10), ("not", ("col", 3)))),
    ("col", 7),
]


def test_device_reference_matches_numpy_counter():
    rng = np.random.default_rng(3)
    cols = words.uniform_words(rng, (20, 64)) | words.uniform_words(rng, (20, 64))
    ref = reference.DeviceReference(np.asarray(cols))
    bits = words.unpack_host(cols)
    for q in QUERIES:
        want = reference.answer_host(cols, q)
        assert np.array_equal(np.asarray(ref.answer(q)), want), q
    count = bits.sum(axis=0)
    assert np.array_equal(words.unpack_host(np.asarray(ref.answer(QUERIES[2]))),
                          (count >= 2) & (count <= 10))


def test_control_breaks_exactness():
    rng = np.random.default_rng(4)
    cols = words.uniform_words(rng, (20, 64)) | words.uniform_words(rng, (20, 64))
    good = reference.DeviceReference(cols)
    bad = reference.DeviceReference(cols, drop_last_member=True)
    for q in QUERIES[:5]:
        assert not np.array_equal(np.asarray(good.answer(q)), np.asarray(bad.answer(q)))


def test_tile_stats_classes_and_cheapest_bytes():
    tw = 64
    span = tw * 32
    sparse = np.zeros(tw, np.uint32)
    sparse[[0, 5, 9]] = [1, 3, 1 << 31]  # 4 set bits
    tiles = np.stack([
        np.zeros(tw, np.uint32),
        np.full(tw, 0xFFFFFFFF, np.uint32),
        words.run_words(np.array([100]), np.array([900]), tw)[0],  # one run
        sparse,
        np.full(tw, 0x55555555, np.uint32),  # 1024 runs, 1024 bits: dense
        words.run_words(np.array([31]), np.array([33]), tw)[0],  # run over a word edge
    ])
    cls, nbytes = words.tile_stats(tiles.reshape(-1), tw)
    assert np.asarray(cls).tolist() == [0, 1, 2, 2, 2, 2]
    # run: 1 interval = 4 bytes; sparse: 4 positions = 8 bytes; dense: 256;
    # the two-bit run ties sparse (1 word each) and is stored as a run
    assert np.asarray(nbytes).tolist() == [0, 0, 4, 8, 4 * tw, 4]
    assert span == 2048


def test_fill_is_deterministic_and_dense_as_asked():
    key = words.column_keys(2**40 + 5, 2, 1)
    starts = np.zeros((2, 1), np.int32)
    thr = np.full((2, 1), words.bernoulli_threshold(1 / 3), np.uint32)
    a = np.asarray(words.fill_columns(key, starts, thr, n_words=4096))
    b = np.asarray(words.fill_columns(key, starts, thr, n_words=4096))
    c = np.asarray(words.fill_columns(words.column_keys(6, 2, 1), starts, thr,
                                      n_words=4096))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    density = words.unpack_host(a).mean()
    # 2 * 131072 Bernoulli(1/3) bits: sd ~0.00092, so 5 sd is 0.0046
    assert abs(density - 1 / 3) < 0.0046


def test_segmented_fill_follows_each_segment():
    r = 4096 * 32
    starts = np.array([[0, 1000, 50000, r]], np.int32)
    thr = np.array([[words.bernoulli_threshold(1.0), 0,
                     words.bernoulli_threshold(0.5), 0]], np.uint32)
    thr[0, 1] = 0  # density 2**-32: in effect empty
    bits = words.unpack_host(np.asarray(words.fill_columns(
        words.column_keys(1, 1, 0), starts, thr, n_words=4096)))[0]
    assert bits[:1000].all()
    assert bits[1000:50000].sum() == 0
    assert abs(bits[50000:].mean() - 0.5) < 0.01


def _layout(rows):
    """Hand-made tile classes: 3 columns x 4 tiles."""
    cls = np.array(rows, np.int8)
    nbytes = np.where(cls == 2, 100, 0).astype(np.int64)
    return cls, nbytes


def test_needed_bytes_decides_tiles_from_classes():
    #            tile: 0  1  2  3
    cls, nbytes = _layout([[1, 0, 2, 2],
                           [1, 0, 2, 0],
                           [2, 0, 2, 1]])
    nw = 256  # 4 tiles of 64 words
    out = 4 * nw
    # T=2: tile 0 decided one (two all-one members), tile 1 decided zero,
    # tile 2 undecided (3 dirty), tile 3: one all-one + one dirty: undecided
    assert needed.needed_bytes(cls, nbytes, [("threshold", None, 2)], nw) == 400 + out
    # T=3: tile 0 undecided (2 ones + 1 dirty), tile 3 cannot reach 3
    assert needed.needed_bytes(cls, nbytes, [("threshold", None, 3)], nw) == 400 + out
    # a batch reads each needed cell once, and writes each output once:
    # tile 0's column 2, tile 2's three columns, tile 3's column 0
    batch = [("threshold", None, 2), ("threshold", None, 3)]
    assert needed.needed_bytes(cls, nbytes, batch, nw) == 500 + 2 * out
    # interval [1, 1]: tile 0 has 2 ones > 1: decided zero
    assert needed.needed_bytes(cls, nbytes, [("interval", (0, 1), 1, 1)], nw) == 300 + out
    # and-not: where column 2 is all-one the result is zero
    q = ("and", (("threshold", (0, 1), 1), ("not", ("col", 2))))
    # tile 0: col 2 dirty, left decided one -> needs col 2 only (100);
    # tile 2: left needs 0, 1 (200) and col 2 (100); tile 3: col 2 one -> zero
    assert needed.needed_bytes(cls, nbytes, [q], nw) == 400 + out


def test_needed_bytes_uniform_data_reads_every_member():
    cls = np.full((8, 16), 2, np.int8)
    nbytes = np.full((8, 16), 256, np.int64)
    q = ("threshold", (1, 2, 5), 2)
    assert needed.needed_bytes(cls, nbytes, [q], 1024) == 3 * 16 * 256 + 4 * 1024
