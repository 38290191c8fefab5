"""The plain reference: query answers from a word-parallel counter.

A query is described as nested tuples over column slots: ``("threshold",
members, t)``, ``("interval", members, lo, hi)`` (``members`` a tuple of
slots, or ``None`` for every column), ``("col", slot)``, ``("not", q)``
and ``("and", (q, ...))``.  :func:`count_planes` and
:func:`count_at_least` are the numpy counter; :class:`DeviceReference`
does the same arithmetic in plain ``jnp`` on the device, with the member
set and the threshold as run-time values, so one compiled program
answers every query.  Nothing here imports ``repro``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ALL_ONES = 0xFFFFFFFF


def count_planes(cols: np.ndarray, chunk: int = 1 << 15) -> list:
    """Bit-sliced per-row counts of ``cols`` (uint32[n, n_words]): plane j
    holds bit j of every row's count, least significant plane first."""
    n, n_words = cols.shape
    planes = [np.zeros(n_words, np.uint32) for _ in range(n.bit_length())]
    for w0 in range(0, n_words, chunk):
        sub = [p[w0:w0 + chunk] for p in planes]
        for row in cols[:, w0:w0 + chunk]:
            carry = row
            for p in sub:
                nxt = p & carry
                p ^= carry
                carry = nxt
    return planes


def count_at_least(planes: list, t: int) -> np.ndarray:
    """Rows whose count is >= t, as packed words."""
    ones = np.full_like(planes[0], ALL_ONES)
    if t <= 0:
        return ones
    if t >= 1 << len(planes):
        return np.zeros_like(planes[0])
    gt, eq = np.zeros_like(ones), ones
    for j in reversed(range(len(planes))):
        if (t >> j) & 1:
            eq = eq & planes[j]
        else:
            gt = gt | (eq & planes[j])
            eq = eq & ~planes[j]
    return gt | eq


def members_of(spec, n_cols: int) -> tuple:
    """The member slots of a symmetric leaf (``None`` means every column)."""
    return tuple(range(n_cols)) if spec is None else tuple(spec)


@functools.partial(jax.jit, static_argnames=("n_planes",))
def _planes_dev(cols, mask, n_planes: int):
    """Count planes of the masked rows of ``cols`` (mask: uint32[n], each
    0 or all ones), one row added per loop step."""
    def add_row(i, planes):
        carry = jax.lax.dynamic_index_in_dim(cols, i, keepdims=False) & mask[i]
        out = []
        for p in planes:
            out.append(p ^ carry)
            carry = p & carry
        return tuple(out)

    zero = jnp.zeros(cols.shape[1], jnp.uint32)
    return jax.lax.fori_loop(0, cols.shape[0], add_row, (zero,) * n_planes)


@jax.jit
def _at_least_dev(planes, t):
    """Rows whose count is >= t (a run-time int32), as packed words."""
    ones = jnp.full_like(planes[0], ALL_ONES)
    gt, eq = jnp.zeros_like(ones), ones
    for j in reversed(range(len(planes))):
        bit = ((t >> j) & 1) == 1
        p = planes[j]
        gt = jnp.where(bit, gt, gt | (eq & p))
        eq = jnp.where(bit, eq & p, eq & ~p)
    out = gt | eq
    out = jnp.where(t >= (1 << len(planes)), jnp.zeros_like(ones), out)
    return jnp.where(t <= 0, ones, out)


class DeviceReference:
    """Expected answers over the whole row space of ``cols``
    (uint32[n, n_words] on the device).

    ``drop_last_member=True`` is the control: every symmetric leaf counts
    all its members but the last, the shortcut of a query path that loses
    one member on the way, which breaks the guarantee of exact answers."""

    def __init__(self, cols: jax.Array, *, drop_last_member: bool = False):
        self.cols = cols
        self.n = int(cols.shape[0])
        self.n_planes = self.n.bit_length()
        self.drop_last_member = drop_last_member
        self._planes: dict = {}

    def planes(self, members: tuple):
        if self.drop_last_member:
            members = members[:-1]
        got = self._planes.get(members)
        if got is None:
            mask = np.zeros(self.n, np.uint32)
            mask[list(members)] = ALL_ONES
            got = _planes_dev(self.cols, jnp.asarray(mask), self.n_planes)
            if len(self._planes) >= 8:
                self._planes.clear()
            self._planes[members] = got
        return got

    def at_least(self, members: tuple, t: int) -> jax.Array:
        return _at_least_dev(self.planes(members), jnp.int32(t))

    def answer(self, q) -> jax.Array:
        op = q[0]
        if op == "threshold":
            return self.at_least(members_of(q[1], self.n), q[2])
        if op == "interval":
            m = members_of(q[1], self.n)
            return self.at_least(m, q[2]) & ~self.at_least(m, q[3] + 1)
        if op == "col":
            return self.cols[q[1]]
        if op == "not":
            return ~self.answer(q[1])
        if op == "and":
            out = self.answer(q[1][0])
            for c in q[1][1:]:
                out = out & self.answer(c)
            return out
        raise ValueError(f"unknown query op {op!r}")


def answer_host(cols: np.ndarray, q) -> np.ndarray:
    """The same answers from the numpy counter (small sizes, tests)."""
    n = cols.shape[0]
    op = q[0]
    if op in ("threshold", "interval"):
        planes = count_planes(cols[list(members_of(q[1], n))])
        if op == "threshold":
            return count_at_least(planes, q[2])
        return count_at_least(planes, q[2]) & ~count_at_least(planes, q[3] + 1)
    if op == "col":
        return cols[q[1]].copy()
    if op == "not":
        return ~answer_host(cols, q[1])
    if op == "and":
        out = answer_host(cols, q[1][0])
        for c in q[1][1:]:
            out &= answer_host(cols, c)
        return out
    raise ValueError(f"unknown query op {op!r}")
