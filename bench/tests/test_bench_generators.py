"""The benchmark's data generators (CPU, small universes)."""
import numpy as np
import pytest

import bench_testkit  # noqa: F401 - puts bench/ on the path
from yardstick.harness import load_module
from yardstick.words import unpack_host

UNI = load_module("generators", "uniform_bernoulli")
LOG2 = 16
R = 1 << LOG2


def test_generation_is_deterministic_per_seed():
    params = {"n_bitmaps": 4, "log2_rows": LOG2, "card_divisor": 3}
    a = np.asarray(UNI.generate(params, 2**33 + 9))
    b = np.asarray(UNI.generate(params, 2**33 + 9))
    c = np.asarray(UNI.generate(params, 9))
    assert a.shape == (4, R // 32) and a.dtype == np.uint32
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_uniform_density():
    params = {"n_bitmaps": 8, "log2_rows": LOG2, "card_divisor": 3}
    bits = unpack_host(np.asarray(UNI.generate(params, 5)))
    # 8 * 65536 Bernoulli(1/3) bits: sd 0.00065, 5 sd is 0.0033
    assert abs(bits.mean() - 1 / 3) < 0.0033
