"""repro: Threshold and Symmetric Functions over Bitmaps (Kaser & Lemire,
2014) as a JAX/TPU bitmap-index query engine.

Subpackages: core (the paper), storage (tiled hybrid column store),
query (expression language + BitmapIndex), kernels (Pallas), stream
(mutable index: delta overlay + compaction), persist (snapshots, WAL,
paging), dist (row-sharded engine + mesh), serve (QueryServer front end),
search (similarity search), obs (metrics + tracing), data (the paper's
dataset generators).
"""

__version__ = "1.0.0"
