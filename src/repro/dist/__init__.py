"""The row-sharded query engine.

``query`` partitions a ``BitmapIndex``'s row space into per-device shards
with per-shard query planning (``BitmapIndex.shard(mesh)`` is the front
door); ``mesh`` builds the device mesh those shards run on.
"""

from .query import (
    ShardedBitmapIndex,
    ShardedPlan,
    ShardedResult,
    ShardedTileStore,
    shard_boundaries,
)

__all__ = [
    "ShardedBitmapIndex",
    "ShardedPlan",
    "ShardedResult",
    "ShardedTileStore",
    "shard_boundaries",
]
