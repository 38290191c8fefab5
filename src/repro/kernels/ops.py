"""Jit'd public wrappers for the Pallas kernels.

Off the TPU (the CPU test path) the kernels run with interpret=True (the
Pallas interpreter executes the kernel body in Python); on a TPU backend the
same call lowers through Mosaic.  ``INTERPRET`` auto-detects.

.. deprecated:: these wrappers are thin shims over ``repro.query``; prefer
   ``BitmapIndex.execute``, which plans the backend itself from TileStore
   statistics and lets fused queries compose (one kernel launch for a whole
   expression tree).  The shims keep their fused-kernel contract on dense
   data, but when the transient index's tile statistics favour skipping
   they route through the ``tiled_fused`` path -- same results, a fraction
   of the words touched.  The family emits ONE consolidated
   DeprecationWarning per process (``core.deprecation``).
"""
from __future__ import annotations

import jax

from repro.core.deprecation import warn_legacy_shim

from .threshold_ssum import INTERPRET, pick_block_words, threshold_pallas  # noqa: F401


def _execute_fused(name, bitmaps, expr, block_words=None):
    warn_legacy_shim(name)
    from repro.query import BitmapIndex

    idx = BitmapIndex(bitmaps)
    plan = idx.explain(expr)
    backend = "tiled_fused" if plan.algorithm == "tiled_fused" else "fused"
    return idx.execute(expr, backend=backend, block_words=block_words)


def fused_threshold(bitmaps: jax.Array, t: int, block_words: int | None = None) -> jax.Array:
    """Fused theta(T, .) over packed bitmaps uint32[N, n_words]."""
    from repro.query import Threshold

    return _execute_fused(
        "kernels.ops.fused_threshold", bitmaps, Threshold(t), block_words
    )


def fused_symmetric(bitmaps: jax.Array, truth, block_words: int | None = None) -> jax.Array:
    """Fused arbitrary symmetric function given truth[w] for w = 0..N."""
    from repro.query import Sym

    return _execute_fused(
        "kernels.ops.fused_symmetric", bitmaps, Sym(tuple(truth)), block_words
    )


def fused_interval(bitmaps: jax.Array, lo: int, hi: int) -> jax.Array:
    from repro.query import Interval

    return _execute_fused("kernels.ops.fused_interval", bitmaps, Interval(lo, hi))


def fused_weighted_threshold(bitmaps: jax.Array, weights, t: int) -> jax.Array:
    """Fused weighted threshold (binary weight decomposition, core/weighted)."""
    from repro.query import Weighted

    return _execute_fused(
        "kernels.ops.fused_weighted_threshold",
        bitmaps,
        Weighted(tuple(int(w) for w in weights), t),
    )
