"""Streaming SCANCOUNT (huge-N) + the fused weighted-threshold kernel."""
import jax.numpy as jnp
import numpy as np

from repro.core.bitmaps import pack, unpack
from repro.core.threshold import threshold
from repro.kernels.ops import fused_weighted_threshold
from repro.core.weighted import weighted_threshold_decomposed


def test_streaming_scancount_matches_at_large_n():
    """The paper's 6 future-work question: N in the thousands+ is where the
    circuit family stops scaling; the streaming counter does not care."""
    rng = np.random.default_rng(0)
    n, r = 2048, 200
    bits = rng.random((n, r)) < 0.01
    bm = pack(jnp.asarray(bits))
    counts = bits.sum(0)
    for t in (2, 10, 25):
        got = np.asarray(unpack(threshold(bm, t, "scancount_streaming"), r))
        np.testing.assert_array_equal(got, counts >= t)


def test_streaming_matches_all_small_n():
    rng = np.random.default_rng(1)
    bits = rng.random((37, 500)) < 0.3
    bm = pack(jnp.asarray(bits))
    for t in (1, 5, 19, 37):
        a = np.asarray(threshold(bm, t, "scancount_streaming"))
        b = np.asarray(threshold(bm, t, "ssum"))
        np.testing.assert_array_equal(a, b)


def test_fused_weighted_kernel_matches_decomposed():
    rng = np.random.default_rng(2)
    bits = rng.random((9, 300)) < 0.4
    bm = pack(jnp.asarray(bits))
    w = tuple(int(x) for x in rng.integers(1, 30, 9))
    for t in (3, sum(w) // 2, sum(w) - 1):
        a = np.asarray(fused_weighted_threshold(bm, w, t))
        b = np.asarray(weighted_threshold_decomposed(bm, w, t))
        np.testing.assert_array_equal(a, b)
