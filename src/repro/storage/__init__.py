"""`repro.storage`: the tiled hybrid storage engine.

The single home of tile classification and tile-skipping execution:

  * :class:`TileStore` -- tile-classified columns (all-zero / all-one /
    dirty / run), dirty tiles packed contiguously in one device array with
    an offsets table, per-column cardinality/density/runcount statistics
    computed once at build time.  ``BitmapIndex`` wraps one.
  * :func:`run_tiled_circuit` -- RBMRG clean/dirty skipping generalised
    from bare thresholds to arbitrary compiled circuits (the
    ``tiled_fused`` backend).
  * :func:`classify_tiles` / :func:`rbmrg_block_threshold` /
    :func:`runcount` -- the original block-RLE primitives (moved here from
    ``core/blockrle.py``, which is now a deprecated re-export shim).
"""

from .containers import (
    CONT_DENSE,
    CONT_NONE,
    CONT_RUN,
    CONT_SPARSE,
    CONTAINER_CROSSOVER,
    run_max_intervals,
    sparse_max_positions,
)
from .tiles import BlockStats, classify_tiles, rbmrg_block_threshold, runcount
from .tilestore import (
    TILE_DIRTY,
    TILE_ONE,
    TILE_RUN,
    TILE_ZERO,
    ColumnStats,
    MemberStats,
    TileStore,
    member_stats_info,
)
from .tiled import run_tiled_circuit

__all__ = [
    "BlockStats",
    "classify_tiles",
    "rbmrg_block_threshold",
    "runcount",
    "TileStore",
    "ColumnStats",
    "MemberStats",
    "member_stats_info",
    "TILE_ZERO",
    "TILE_ONE",
    "TILE_DIRTY",
    "TILE_RUN",
    "CONT_NONE",
    "CONT_DENSE",
    "CONT_SPARSE",
    "CONT_RUN",
    "CONTAINER_CROSSOVER",
    "sparse_max_positions",
    "run_max_intervals",
    "run_tiled_circuit",
]
