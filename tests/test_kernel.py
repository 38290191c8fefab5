"""Pallas kernel: shape/dtype sweep against the pure-jnp oracle (interpret)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import fused_interval, fused_symmetric, fused_threshold
from repro.kernels.ref import symmetric_ref, threshold_ref
from repro.kernels.threshold_ssum import (
    MAX_PLANE_BLOCK,
    host_planes,
    pick_block_words,
    plane_block,
    plane_count,
    threshold_pallas,
    threshold_rows,
)


def _bm(n, nw, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**32, (n, nw), dtype=np.uint32))


@pytest.mark.parametrize("n", [2, 3, 5, 16, 64, 130])
@pytest.mark.parametrize("nw", [1, 7, 100, 1030])
def test_threshold_kernel_shape_sweep(n, nw):
    bm = _bm(n, nw, seed=n * 1000 + nw)
    for t in sorted({1, 2, n // 2, n}):
        got = np.asarray(fused_threshold(bm, t, block_words=256))
        exp = np.asarray(threshold_ref(bm, t))
        np.testing.assert_array_equal(got, exp, err_msg=f"n={n} nw={nw} t={t}")


@pytest.mark.parametrize("block_words", [128, 1024, 4096])
def test_threshold_kernel_block_sizes(block_words):
    bm = _bm(33, 2050, seed=9)
    got = np.asarray(fused_threshold(bm, 11, block_words=block_words))
    np.testing.assert_array_equal(got, np.asarray(threshold_ref(bm, 11)))


def test_symmetric_kernel():
    rng = np.random.default_rng(4)
    for n in (4, 9, 31):
        bm = _bm(n, 300, seed=n)
        truth = tuple(bool(x) for x in rng.integers(0, 2, n + 1))
        got = np.asarray(fused_symmetric(bm, truth, block_words=256))
        np.testing.assert_array_equal(got, np.asarray(symmetric_ref(bm, truth)))


def test_interval_kernel():
    bm = _bm(12, 129, seed=5)
    got = np.asarray(fused_interval(bm, 3, 7))
    exp = np.asarray(symmetric_ref(bm, tuple(3 <= w <= 7 for w in range(13))))
    np.testing.assert_array_equal(got, exp)


def test_treeadd_kernel_variant():
    bm = _bm(21, 500, seed=6)
    got = np.asarray(threshold_rows(bm, 9, kind="treeadd", interpret=True))
    np.testing.assert_array_equal(got, np.asarray(threshold_ref(bm, 9)))


def test_pick_block_words_vmem_budget():
    # block must shrink as N grows to hold the working set in VMEM
    small_n = pick_block_words(8, 1 << 20)
    large_n = pick_block_words(512, 1 << 20)
    assert small_n >= large_n
    assert large_n >= 1024  # lane-aligned floor
    # working set (2 rows live per input) fits the 4 MiB default budget
    assert 2 * 512 * large_n * 4 <= 4 * 1024 * 1024 + 512 * 1024


def test_kernel_matches_all_jnp_algorithms():
    from repro.core.threshold import threshold

    bm = _bm(17, 200, seed=8)
    fused = np.asarray(threshold(bm, 6, "fused"))
    for alg in ("scancount", "ssum", "looped", "csvckt"):
        np.testing.assert_array_equal(fused, np.asarray(threshold(bm, 6, alg)))


# -- the plane kernel: bare thresholds read their members in place ----------

# 2**16 + 37 rows: a word count that is not a multiple of a slab or a block
PLANE_ROWS = (1 << 16) + 37
PLANE_WORDS = -(-PLANE_ROWS // 32)


def _plane_data(n=64, seed=11):
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 2**32, (n, PLANE_WORDS), dtype=np.uint32)
    return words, jnp.asarray(host_planes(words))


def _slots(m, n, seed):
    """``m`` unsorted member slots over ``n`` columns, with a repeat."""
    slots = np.random.default_rng(seed).integers(0, n, m).astype(np.int32)
    if m > 1:
        slots[-1] = slots[0]
    return slots


def _expected(words, t):
    m, nw = words.shape
    if t <= 0:
        return np.full(nw, 0xFFFFFFFF, np.uint32)
    if t > m:
        return np.zeros(nw, np.uint32)
    return np.asarray(threshold_ref(jnp.asarray(words), t))


def test_plane_view_layout():
    words, planes = _plane_data(n=3)
    p = plane_count(PLANE_WORDS)
    assert planes.shape == (3, p, 8, 128) and p * 1024 >= PLANE_WORDS
    flat = np.asarray(planes).reshape(3, -1)
    # slab s of a column holds its words [1024 s, 1024 s + 1024); zeros past
    np.testing.assert_array_equal(flat[:, :PLANE_WORDS], words)
    assert not flat[:, PLANE_WORDS:].any()
    # every block size the kernel may pick divides the padded plane
    assert plane_count(1) == 1 and plane_count(3 * 1024 + 1) == 4
    assert plane_count(MAX_PLANE_BLOCK * 1024 + 1) == 2 * MAX_PLANE_BLOCK
    for m in (1, 2, 13, 31, 64, 1000):
        bb = plane_block(m)
        assert bb & (bb - 1) == 0 and 1 <= bb <= MAX_PLANE_BLOCK
        assert 2 * (m + 1) * bb * 4096 <= 12 * 1024 * 1024 or bb == 1


@pytest.mark.parametrize("m", [1, 2, 13, 31, 64])
def test_plane_kernel_matches_word_parallel_reference(m):
    words, planes = _plane_data()
    slots = _slots(m, words.shape[0], seed=m)
    for t in sorted({0, 1, (m + 1) // 2, m, m + 1}):
        got = np.asarray(threshold_pallas(planes, slots, t, n_words=PLANE_WORDS,
                                          interpret=True))
        np.testing.assert_array_equal(got, _expected(words[slots], t),
                                      err_msg=f"m={m} t={t}")


def test_plane_kernel_symmetric_and_weighted_variants():
    words, planes = _plane_data()
    slots = _slots(9, words.shape[0], seed=3)
    members = jnp.asarray(words[slots])
    truth = tuple(bool(x) for x in np.random.default_rng(5).integers(0, 2, 10))
    got = threshold_pallas(planes, slots, truth=truth, n_words=PLANE_WORDS,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(symmetric_ref(members, truth)))
    weights = (3, 1, 2, 1, 1, 4, 2, 1, 1)
    got = threshold_pallas(planes, slots, 7, weights=weights,
                           n_words=PLANE_WORDS, interpret=True)
    bits = (words[slots][:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    score = np.tensordot(np.asarray(weights), bits.astype(np.int64), axes=1)
    exp = ((score >= 7).astype(np.uint32) << np.arange(32, dtype=np.uint32)).sum(
        axis=-1, dtype=np.uint32)
    np.testing.assert_array_equal(np.asarray(got), exp)


def test_plane_kernel_explicit_rows_wrapper():
    words, _ = _plane_data(n=13, seed=2)
    for t in (0, 5, 14):
        got = np.asarray(threshold_rows(words, t, interpret=True))
        np.testing.assert_array_equal(got, _expected(words, t))
