"""Uniform columns: every bit set independently with one density.

The paper (section 5.3) draws exactly ``card`` distinct values per set;
here each bit is set with probability ``card / r``, so set sizes vary by
about their square root.  Made on the device in one program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from yardstick.words import bernoulli_threshold, column_keys, fill_columns


def generate(params: dict, seed: int) -> jax.Array:
    """uint32[n_bitmaps, 2**log2_rows / 32] on the device."""
    n = int(params["n_bitmaps"])
    thr = bernoulli_threshold(1.0 / int(params["card_divisor"]))
    return fill_columns(column_keys(seed, n, 1),
                        jnp.zeros((n, 1), jnp.int32),
                        jnp.full((n, 1), thr, jnp.uint32),
                        1 << (int(params["log2_rows"]) - 5))
