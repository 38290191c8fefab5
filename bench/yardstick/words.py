"""Packed-word tools: generation on the device from a seed, per-tile
statistics, and the small host helpers the tests use.

Bits are little-endian within a word: row ``w * 32 + b`` is bit ``b`` of
word ``w``.  Nothing here imports ``repro``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32


def seed_key(seed: int, *path: int) -> jax.Array:
    """A threefry key made from all the bits of ``seed`` (JAX's own
    ``key(seed)`` keeps only the low 32 bits without 64-bit mode)."""
    state = np.random.SeedSequence([int(seed), *path]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(state, np.uint32),
                                    impl="threefry2x32")


def bernoulli_threshold(p: float) -> int:
    """The uint32 ``c`` with ``P(u <= c) = (c + 1) / 2**32 ~= p`` for a
    uniform uint32 ``u``; ``p = 1`` gives 2**32 - 1 (every bit set)."""
    if not 0.0 < p <= 1.0:
        raise ValueError(f"density {p} outside (0, 1]")
    return min(2**32 - 1, max(0, int(np.ceil(p * 2.0**32)) - 1))


def pack_bits(bits: jax.Array) -> jax.Array:
    """bool[r] (r a multiple of 32) -> uint32[r // 32], little-endian bits."""
    b = bits.reshape(-1, 32).astype(U32)
    return jnp.sum(b << jnp.arange(32, dtype=U32), axis=1, dtype=U32)


def _fill_column(key, starts, thresholds, n_words: int) -> jax.Array:
    r = n_words * 32
    if starts.shape[0] == 1:  # one segment: every row has its threshold
        per_row = thresholds[0]
    else:  # running sum of the threshold steps, modulo 2**32
        prev = jnp.concatenate([jnp.zeros(1, U32), thresholds[:-1]])
        steps = jnp.zeros(r, U32).at[starts].add(thresholds - prev, mode="drop")
        per_row = jnp.cumsum(steps, dtype=U32)
    u = jax.random.bits(key, (r,), U32)
    return pack_bits(u <= per_row)


@functools.partial(jax.jit, static_argnames=("n_words",))
def fill_columns(key_data, starts, thresholds, n_words: int) -> jax.Array:
    """uint32[n, n_words]: bit ``x`` of column ``i`` is set with the
    density of the segment of column ``i`` that holds row ``x``.

    ``key_data`` (uint32[n, 2]) holds a threefry key per column;
    ``starts`` (int32[n, S], ascending, first 0; entries equal to the row
    count are padding) opens each segment, and ``thresholds``
    (uint32[n, S]) is its :func:`bernoulli_threshold`.  Columns are made
    one after another inside the one program, so its temporaries are
    those of one column.  No row looks up its segment: the per-row
    threshold is the running sum of the steps between segments."""
    return jax.lax.map(
        lambda a: _fill_column(jax.random.wrap_key_data(a[0]), a[1], a[2], n_words),
        (key_data, starts, thresholds))


def column_keys(seed: int, n: int, stream: int) -> jax.Array:
    """uint32[n, 2] threefry key data, one key per column."""
    return jnp.stack([jax.random.key_data(seed_key(seed, stream, i))
                      for i in range(n)])


@functools.partial(jax.jit, static_argnames=("tile_words",))
def tile_stats(col: jax.Array, tile_words: int):
    """Per tile of one packed column: (class, bytes of the cheapest
    container), int8 and int32.

    Class 0 is all-zero, 1 all-one, 2 dirty.  A dirty tile is stored, by
    the Roaring-style rule of tile stores, as sorted uint16 positions when
    its popcount ``p <= 2 * tile_words`` (``ceil(p / 2)`` words), as
    (start, end) uint16 pairs when it has at most ``tile_words // 2``
    maximal runs (one word each; preferred on a tie), else as its dense
    words; the bytes are those of the chosen form.  Clean tiles store
    nothing."""
    t = col.reshape(-1, tile_words)
    span = tile_words * 32
    pc = jax.lax.population_count(t).astype(jnp.int32).sum(axis=1)
    carry_in = jnp.concatenate(
        [jnp.zeros((t.shape[0], 1), U32), t[:, :-1] >> 31], axis=1)
    rises = t & ~((t << 1) | carry_in)
    runs = jax.lax.population_count(rises).astype(jnp.int32).sum(axis=1)
    big = jnp.int32(2**30)
    cost_sparse = jnp.where(pc <= 2 * tile_words, (pc + 1) // 2, big)
    cost_run = jnp.where(runs <= max(1, tile_words // 2), runs, big)
    as_run = (cost_run <= tile_words) & (cost_run <= cost_sparse)
    as_sparse = ~as_run & (cost_sparse <= tile_words)
    nbytes = jnp.where(as_run, 4 * runs,
                       jnp.where(as_sparse, 2 * pc, 4 * tile_words))
    cls = jnp.where(pc == 0, 0, jnp.where(pc == span, 1, 2)).astype(jnp.int8)
    return cls, jnp.where(cls == 2, nbytes, 0)


def column_tile_stats(cols: jax.Array, tile_words: int):
    """(int8[n, n_tiles] classes, int64[n, n_tiles] bytes) on the host."""
    parts = [tile_stats(cols[i], tile_words) for i in range(cols.shape[0])]
    cls = np.stack([np.asarray(c) for c, _ in parts])
    nbytes = np.stack([np.asarray(b, np.int64) for _, b in parts])
    return cls, nbytes


# ---------------------------------------------------------------------------
# Host helpers (small sizes, tests)
# ---------------------------------------------------------------------------


def uniform_words(rng, shape) -> np.ndarray:
    """Random packed words at bit density 5/32: ``a & b & (c | d & e)``."""
    a, b, c, d, e = (
        rng.integers(0, 2**32, size=shape, dtype=np.uint32) for _ in range(5)
    )
    return a & b & (c | (d & e))


def run_words(start, stop, tile_words: int) -> np.ndarray:
    """uint32[len(start), tile_words]: bits [start, stop) set in each tile."""
    w = np.arange(tile_words, dtype=np.int64) * 32
    lo = np.clip(start[:, None] - w, 0, 32).astype(np.uint64)
    hi = np.clip(stop[:, None] - w, 0, 32).astype(np.uint64)
    one = np.uint64(1)
    return (((one << hi) - one) ^ ((one << lo) - one)).astype(np.uint32)


def unpack_host(words: np.ndarray) -> np.ndarray:
    """uint32[..., W] -> bool[..., W * 32], little-endian bits."""
    w = np.ascontiguousarray(words, np.uint32)
    return np.unpackbits(w.view(np.uint8), axis=-1, bitorder="little").astype(bool)
