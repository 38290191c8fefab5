"""Bytes a batch of queries needs, fixed by the data and the queries.

Per tile every query node is all-zero, all-one or undecided, from the
tile classes of the columns it reads.  A symmetric leaf is decided where
its all-one members already settle the count (for a threshold: they
reach it, or the all-one plus dirty members cannot).  An undecided leaf
needs the dirty tiles of its members.  A batch reads each needed
(column, tile) once, in the cheapest container encoding
(:func:`yardstick.words.tile_stats`), and writes each query's output
words once.  Nothing here imports ``repro``.
"""
from __future__ import annotations

import numpy as np

from .reference import members_of

ZERO, ONE, DIRTY, UNDECIDED = 0, 1, 2, -1


def _leaf(cls, members, lo: int, hi: int | None):
    """(values, needed) of 'count of members in [lo, hi]' per tile."""
    sub = cls[list(members)]
    ones = (sub == ONE).sum(axis=0)
    most = ones + (sub == DIRTY).sum(axis=0)
    hi = most.max(initial=0) if hi is None else hi
    val = np.full(cls.shape[1], UNDECIDED, np.int8)
    val[(ones >= lo) & (most <= hi)] = ONE
    val[(most < lo) | (ones > hi)] = ZERO
    need = np.zeros(cls.shape, bool)
    need[list(members)] = (sub == DIRTY) & (val == UNDECIDED)
    return val, need


def evaluate(cls: np.ndarray, q):
    """(int8[n_tiles] value per tile: 0, 1 or -1 undecided;
    bool[n, n_tiles] dirty cells needed) of one query description."""
    n = cls.shape[0]
    op = q[0]
    if op == "threshold":
        return _leaf(cls, members_of(q[1], n), q[2], None)
    if op == "interval":
        return _leaf(cls, members_of(q[1], n), q[2], q[3])
    if op == "col":
        val = np.where(cls[q[1]] == DIRTY, UNDECIDED, cls[q[1]]).astype(np.int8)
        need = np.zeros(cls.shape, bool)
        need[q[1]] = cls[q[1]] == DIRTY
        return val, need
    if op == "not":
        val, need = evaluate(cls, q[1])
        return np.where(val == UNDECIDED, val, 1 - val).astype(np.int8), need
    if op == "and":
        parts = [evaluate(cls, c) for c in q[1]]
        zero = np.any([v == ZERO for v, _ in parts], axis=0)
        one = np.all([v == ONE for v, _ in parts], axis=0)
        val = np.full(cls.shape[1], UNDECIDED, np.int8)
        val[one] = ONE
        val[zero] = ZERO
        need = np.zeros(cls.shape, bool)
        for v, nd in parts:
            need |= nd & (v == UNDECIDED)[None, :]
        need &= (val == UNDECIDED)[None, :]
        return val, need
    raise ValueError(f"unknown query op {op!r}")


def needed_bytes(cls: np.ndarray, nbytes: np.ndarray, batch, n_words: int) -> int:
    """Bytes read and written by the least work that answers ``batch``
    (query descriptions submitted together) over the whole row space."""
    if not batch:
        return 0
    need = np.zeros(cls.shape, bool)
    for q in batch:
        need |= evaluate(cls, q)[1]
    return int(nbytes[need].sum()) + 4 * n_words * len(batch)
